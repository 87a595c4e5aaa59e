"""Set-up: window start less process start less the seven phases: the benchmark's
imports, its generator, the parser, require_device, the profiler's start, gaps."""

from benchmarks import startup_spans


def read(layers):
    return startup_spans.read(layers, "setup.outside_s")
