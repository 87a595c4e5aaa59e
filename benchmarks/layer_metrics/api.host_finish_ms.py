"""Host work of a counts request after the readback (span engine.finish: the exact
int64 class-size weighting of the row sums)."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "engine.finish")
