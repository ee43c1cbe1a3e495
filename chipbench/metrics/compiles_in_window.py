"""Executables JAX compiled or loaded from its cache inside the measured
window (``jax.monitoring``'s backend-compile events): 0 when set-up
warmed every shape the window uses."""


def read(record):
    return record["compiles_in_window"]
