"""Mean host time of `build_network_policies` in a what-if (span bench.matcher.build)."""


def read(layers):
    return layers.span_mean_ms("bench.matcher.build")
