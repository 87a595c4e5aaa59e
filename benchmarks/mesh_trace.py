"""What the mesh readers (`benchmarks/layer_metrics/mesh.*.py`) share: the
traced window's device operations, device by device.

`trace_reduce.reduce_events` averages over the devices; a mesh cell also asks
how the devices differ and what their collectives cost, so these read the raw
tuples (`LayerContext.device_events`) clipped to the host span that wraps the
measured window.  Nothing here knows a cell; a trace with no device event
reads as nothing, never as 0.
"""

from benchmarks import trace_reduce

WINDOW = "bench.window"
# HLO instruction names of the operations that move data between chips, as
# `trace_reduce.op_name` leaves them (`all-gather.3_...`, `all-gather-start.1_...`)
COLLECTIVES = ("all-gather", "collective-permute", "all-to-all", "all-reduce")


def per_device(layers) -> dict:
    """{device: [(op name, start_s, end_s)]} inside the window; {} where the
    run has no device trace."""
    if not layers.device_events:
        return {}
    window = [(s, e) for n, s, e in layers.host_spans or () if n == WINDOW]
    if not window:
        return {d: list(evs) for d, evs in layers.device_events.items()}
    lo, hi = window[0]
    return {
        d: [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]
        for d, evs in layers.device_events.items()
    }


def busy_seconds(events) -> float:
    """The union of one device's operation intervals."""
    return sum(e - s for s, e in trace_reduce._union([(s, e) for _, s, e in events]))


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)
