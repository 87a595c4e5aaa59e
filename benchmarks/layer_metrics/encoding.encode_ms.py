"""Mean host time of `TpuPolicyEngine(policy, pods, namespaces)` in a what-if
(span bench.engine.new): the encoding of cluster and policies, with whatever
the constructor sends to the device (the program has no span that splits it)."""


def read(layers):
    return layers.span_mean_ms("bench.engine.new")
