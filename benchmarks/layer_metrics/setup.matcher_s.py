"""Set-up: build_network_policies before the window (span matcher.build)."""

from benchmarks import startup_spans


def read(layers):
    return startup_spans.read(layers, "setup.matcher_s")
