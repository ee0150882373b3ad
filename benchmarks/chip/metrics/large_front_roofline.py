"""Roofline share of the large-front path (fronts padded past
VMEM_FRONT_MAX: ``panel_factor`` then ``syrk_downdate`` per 512-column
panel, ``kernels/ops.py``) over the traced window, in %.

The work is that of the true front orders (m, nb) of the fronts that took
this path, once per factorization of the window (``work.py``); the time
is the summed device duration of both kernels' events (``trace.py``)."""


def read(run):
    return run.work.path_roofline(run, "large_front", ("large_front",))
