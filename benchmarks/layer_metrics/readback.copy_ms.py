"""Time a request spends bringing finished tables to the host (span grid.copy:
`np.asarray` of each table; the transfer and the runtime's un-tiling on the host)."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "grid.copy")
