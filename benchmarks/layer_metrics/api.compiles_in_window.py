"""Programs JAX compiled, or loaded from its cache, inside the window (JAX's
backend-compile events): 0 where every shape was warmed in set-up."""


def read(layers):
    return layers.counters.get("compiles_in_window")
