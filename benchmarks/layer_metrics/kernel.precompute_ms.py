"""Device time a request spends in every operation but the Pallas counts
kernel (whose name holds `_verdict_counts_pallas`): the unpack, the namespace
sort and `tiled._precompute` with its prefix-mask rows, which the fused dense
counts program runs again on every request.  With `kernel.device_ms` it says
how much of a request is case-independent work done again.  Summed on each
chip, averaged over the chips.  Nothing (never 0) where the window shows no
Pallas counts operation at all: the route is then not the one this is about.
Operations that overlap count once (a union, as `kernel.device_ms` is)."""

from benchmarks import mesh_trace

KERNEL = "_verdict_counts_pallas"


def read(layers):
    devices = mesh_trace.per_device(layers)
    if not devices or not layers.requests:
        return None
    if not any(KERNEL in name for evs in devices.values() for name, _, _ in evs):
        return None
    spent = [
        mesh_trace.busy_seconds([ev for ev in evs if KERNEL not in ev[0]])
        for evs in devices.values()
    ]
    if not any(spent):
        return None  # the kernel alone (a steady state): nothing to read, not 0
    return 1e3 * sum(spent) / len(spent) / layers.requests
