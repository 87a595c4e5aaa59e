"""Time a what-if spends obtaining executables (span engine.program: adopting from
the persistent cache, or lowering and compiling, each program of the new engine)."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "engine.program")
