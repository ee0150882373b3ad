"""Roofline share of the whole-front-in-VMEM kernel (``front_factor_vmem``,
``kernels/frontal_cholesky.py``) over the traced window, in %.

The work is that of the true front orders (m, nb) of the fronts that took
this path, once per factorization of the window (``work.py``); the time
is the summed device duration of the kernel's events (``trace.py``)."""


def read(run):
    return run.work.path_roofline(run, "front_factor_vmem", ("front_factor_vmem",))
