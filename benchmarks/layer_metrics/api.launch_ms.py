"""Time of the call that enqueues a request's program (span engine.dispatch), and
nothing else: the executable is obtained under engine.program."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "engine.dispatch")
