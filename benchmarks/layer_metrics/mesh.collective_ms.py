"""Device time a request spends in operations that move data between chips
(all-gather, collective-permute, all-to-all, all-reduce, their -start and
-done halves included), summed on each chip and averaged over the chips."""

from benchmarks import mesh_trace


def read(layers):
    devices = mesh_trace.per_device(layers)
    if not devices or not layers.requests:
        return None
    spent = [
        sum(e - s for n, s, e in evs if mesh_trace.is_collective(n))
        for evs in devices.values()
    ]
    if not any(spent):
        return None  # no collective in the trace: nothing to read, not 0
    return 1e3 * sum(spent) / len(spent) / layers.requests
