"""Device-busy time of the traced window over the requests it completed."""


def read(layers):
    if not layers.trace or not layers.trace["busy_s"] or not layers.requests:
        return None
    return 1e3 * layers.trace["busy_s"] / layers.requests
