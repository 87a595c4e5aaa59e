"""Set-up: the constructor's compression half before the window (spans
engine.cidrspace, engine.classify, engine.class_tensors, engine.compact,
engine.partition)."""

from benchmarks import startup_spans


def read(layers):
    return startup_spans.read(layers, "setup.classes_s")
