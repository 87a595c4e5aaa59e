"""Set-up: the start of the TPU runtime, the process's first jax.devices() (span
startup.backend).  Nothing to read where the benchmark starts the backend before
the program does (the one-shot cell: closed_loop's require_device comes first)."""

from benchmarks import startup_spans


def read(layers):
    return startup_spans.read(layers, "setup.backend_s")
