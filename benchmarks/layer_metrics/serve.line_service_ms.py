"""Mean reply time of a query line less its wait: from when it was written,
or from the reply to the line before it if that came later, to its reply."""


def read(layers):
    return layers.span_mean_ms("wire.query_service")
