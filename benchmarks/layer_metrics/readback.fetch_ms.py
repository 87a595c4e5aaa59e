"""Mean time of bringing a request's tables to the host (span bench.fetch);
it also waits out whatever the device has not finished."""

from benchmarks import program


def read(layers):
    if program.ENTRIES[layers.cell.config["entry"]].result != "tables":
        return None  # the counts entry reads back inside its own call
    return layers.span_mean_ms("bench.fetch")
