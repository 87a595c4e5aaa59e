"""Set-up: backend compiles before the window that JAX's persistent cache did not
serve (jax.compile spans of stage backend_compile, cache != hit): a cold run reads high."""

from benchmarks import startup_spans


def read(layers):
    return startup_spans.read(layers, "setup.compiles")
