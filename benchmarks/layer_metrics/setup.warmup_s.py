"""Set-up: the warm-up requests' evaluations and fetches (spans engine.eval,
grid.fetch, grid.wait) less what setup.program_s took of them."""

from benchmarks import startup_spans


def read(layers):
    return startup_spans.read(layers, "setup.warmup_s")
