"""Mean time of the first `evaluate_grid` of a new engine up to
`block_until_ready` (span bench.first_dispatch): pack, device_put, dispatch
and the kernels' run."""


def read(layers):
    return layers.span_mean_ms("bench.first_dispatch")
