"""Set-up: the engine's constructor (span engine.new) less what setup.classes_s,
the imports, the backend's start and compiles took inside it."""

from benchmarks import startup_spans


def read(layers):
    return startup_spans.read(layers, "setup.engine_s")
