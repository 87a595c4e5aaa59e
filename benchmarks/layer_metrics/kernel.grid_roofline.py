"""The least time the chip's HBM needs for a request's bytes (peaks.py: from
the cell's shapes alone) over the device time a request took.  A loose bound
by construction; see peaks.py for why no tighter one is fair."""

from benchmarks import generators, peaks, program


def read(layers):
    if not layers.trace or not layers.trace["busy_s"] or not layers.requests:
        return None
    cell = layers.cell
    sets = generators.case_sets(cell.traffic["case_sets"])
    least = peaks.grid_min_seconds(
        layers.device["kind"],
        pods=cell.sizes["pods"], policies=cell.sizes["policies"],
        port_cases=len(sets[0]),
        result=program.ENTRIES[cell.config["entry"]].result,
    )
    return 100.0 * least * layers.requests / layers.trace["busy_s"]
