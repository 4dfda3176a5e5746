"""JAX compile events (tracing, lowering, compiling, cache loads) inside the
measured window; it should read 0."""

from vbench import readers


def read(run):
    return float(readers.compiles_in_window(run))
