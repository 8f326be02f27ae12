"""Executables JAX built or loaded from its cache inside the measured
window (``/jax/core/compile/backend_compile_duration`` events). Warm-up
serves every shape the window uses, so this should read 0."""


def read(ctx):
    return ctx["compiles_in_window"]
