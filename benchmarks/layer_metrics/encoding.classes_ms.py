"""The constructor's compression half, a what-if (spans engine.compact,
engine.partition, engine.cidrspace, engine.classify and engine.class_tensors)."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(
        layers,
        "engine.compact",
        "engine.partition",
        "engine.cidrspace",
        "engine.classify",
        "engine.class_tensors",
    )
