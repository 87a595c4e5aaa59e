"""Host time of a request inside the entry (span bench.evaluate) that no
device operation covers: dispatch, packing, and for the counts entry the round
trip it waits out.  Spans and operations are read on the trace's one clock."""

from benchmarks import trace_reduce


def read(layers):
    if not layers.device_events:
        return None
    calls = [sp for sp in layers.host_spans if sp[0] == "bench.evaluate"]
    if not calls:
        return None
    host = sum(e - s for _, s, e in calls)
    covered = trace_reduce.busy_inside(layers.device_events, calls)
    return 1e3 * (host - covered) / len(calls)
