"""Time a counts request waits in its readback barrier (span engine.execute:
`np.asarray` of the row sums, which waits out the kernel)."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "engine.execute")
