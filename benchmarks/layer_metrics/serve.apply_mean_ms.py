"""The child's own `cyclonus_tpu_serve_apply_seconds` histogram over the
window: sum over count, all modes."""


def read(layers):
    count = layers.counters.get("apply_count")
    return 1e3 * layers.counters["apply_seconds_sum"] / count if count else None
