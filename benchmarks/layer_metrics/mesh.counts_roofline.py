"""The least time the cell's chips' HBM needs for a COUNTS request's bytes
(peaks.grid_min_bytes(result="counts"): the cluster and the policy set read
once, four 64-bit integers written, from the cell's shapes alone, whatever
implements them) over the mean per-chip device time a request took.  The
bytes are spread over ALL the cell's chips, so the share is the whole mesh's,
as `mesh.grid_roofline` is for tables.

A very small share by construction (peaks.py: what lies above the bytes, the
pod x pod contraction, is exactly what an implementation is free to avoid):
a few MB against seconds of device time.  It is handed over as the float it
is, never rounded, so that it cannot read 0."""

from benchmarks import generators, peaks


def read(layers):
    if not layers.trace or not layers.trace["busy_s"] or not layers.requests:
        return None
    cell = layers.cell
    sets = generators.case_sets(cell.traffic["case_sets"])
    least = peaks.grid_min_bytes(
        pods=cell.sizes["pods"], policies=cell.sizes["policies"],
        port_cases=len(sets[0]), result="counts",
    ) / (cell.chips * peaks.peaks_for(layers.device["kind"])["hbm_bytes_per_s"])
    return 100.0 * least * layers.requests / layers.trace["busy_s"]
