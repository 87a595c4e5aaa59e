"""Delta lines whose reply said `"Mode": "incremental"`, of all delta lines."""


def read(layers):
    lines = layers.counters.get("delta_lines")
    return 100.0 * layers.counters["incremental_lines"] / lines if lines else None
