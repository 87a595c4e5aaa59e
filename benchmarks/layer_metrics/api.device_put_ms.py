"""Time a what-if spends packing and sending tensors to the device (span
engine.device_put: the packed class tensors, the pod-to-class map)."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "engine.device_put")
