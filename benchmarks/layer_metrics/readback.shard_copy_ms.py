"""Time a request spends bringing its tables' shards to the host one by one
(span grid.shard_copy, one a shard inside grid.copy; attrs device, bytes,
dtype): waiting out the shard's transfer and un-tiling, and laying it into the
table's one host buffer."""

from benchmarks import program_spans


def read(layers):
    return program_spans.per_request_ms(layers, "grid.shard_copy")
