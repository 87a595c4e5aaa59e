"""The busiest chip's busy time in the window over the mean of all chips: 1.0
where the mesh shares the work evenly, the chip count where one chip does it
all."""

from benchmarks import mesh_trace


def read(layers):
    busy = [mesh_trace.busy_seconds(evs) for evs in mesh_trace.per_device(layers).values()]
    if not busy or not sum(busy):
        return None
    return max(busy) * len(busy) / sum(busy)
