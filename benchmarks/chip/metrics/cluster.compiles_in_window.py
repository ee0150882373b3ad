"""Executables built inside the served window: JAX's
``/jax/core/compile/backend_compile_duration`` event, which fires for a
compile and for a load from the persistent cache.  Set-up compiles every
batch shape the scheduler can form and serves the warm requests, so any
count here is a compile that a request of the window waited for."""


def read(run):
    return run.layer.get("compiles_in_window")
