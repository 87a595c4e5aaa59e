"""How late the generator wrote a line against its schedule, 99th percentile:
a starved generator must not read as a fast server."""


def read(layers):
    return layers.counters.get("late_p99_ms")
