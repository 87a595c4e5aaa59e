"""Host time a request spends enqueuing the sharded program with the per-call
host arrays it sends to every chip (span engine.dispatch_sharded; attrs route,
devices, schedule)."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "engine.dispatch_sharded")
