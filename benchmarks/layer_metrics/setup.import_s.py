"""Set-up: the first imports of JAX (with libtpu) and of Pallas (span startup.import),
as far as the program's own code makes them."""

from benchmarks import startup_spans


def read(layers):
    return startup_spans.read(layers, "setup.import_s")
