"""`memory_stats()["peak_bytes_in_use"]` of the fullest chip after the window."""


def read(layers):
    return layers.device.get("memory_peak_bytes") or None
