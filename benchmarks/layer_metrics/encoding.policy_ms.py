"""The constructor's policy half, a what-if (spans engine.encode_policy: policy and
cluster to arrays, and engine.build_tensors: the tensor dict)."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "engine.encode_policy", "engine.build_tensors")
