"""Executables built inside the measured window: JAX's
``/jax/core/compile/backend_compile_duration`` event, which fires for a
compile and for a load from the persistent cache.  Set-up warms every
shape, so any count here is a compile the window paid for."""


def read(run):
    return run.layer.get("compiles_in_window")
