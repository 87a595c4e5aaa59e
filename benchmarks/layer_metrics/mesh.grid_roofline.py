"""The least time the cell's chips' HBM needs for a request's bytes
(peaks.grid_min_bytes: from the cell's shapes alone, the tables in their
densest exact form, whatever implements them) over the mean per-chip device
time a request took.  The bytes are spread over ALL the cell's chips, so the
share is the whole mesh's; a loose bound by construction (peaks.py)."""

from benchmarks import generators, peaks


def read(layers):
    if not layers.trace or not layers.trace["busy_s"] or not layers.requests:
        return None
    cell = layers.cell
    sets = generators.case_sets(cell.traffic["case_sets"])
    least = peaks.grid_min_bytes(
        pods=cell.sizes["pods"], policies=cell.sizes["policies"],
        port_cases=len(sets[0]), result="tables",
    ) / (cell.chips * peaks.peaks_for(layers.device["kind"])["hbm_bytes_per_s"])
    return 100.0 * least * layers.requests / layers.trace["busy_s"]
