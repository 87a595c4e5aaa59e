"""Time a request's fetches wait for the device to finish the tables (span grid.wait:
`block_until_ready` on each table before its copy)."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "grid.wait")
