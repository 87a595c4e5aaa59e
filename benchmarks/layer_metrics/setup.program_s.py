"""Set-up: getting executables (spans engine.program, engine.static_pre,
engine.autotune, and JAX's own compile stages outside them, jax.compile)."""

from benchmarks import startup_spans


def read(layers):
    return startup_spans.read(layers, "setup.program_s")
