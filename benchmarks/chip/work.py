"""The work of a front, counted by the benchmark itself.

A front of order m with nb pivots needs the flops of eliminating nb
columns of a symmetric m x m matrix (the formula of
``repro.sparse.symbolic.partial_factor_flops``, copied here so that no
change to the program moves the count):

    sum over i < nb of (m - i)^2 + (m - i) + 1

and, as the least traffic, the f32 front read once and written once:
2 * 4 * m^2 bytes.  Both come from the true front orders, never from a
kernel's padded shape or its actual MXU passes, so the same work is
counted whatever implements it.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

F32 = 4


def partial_factor_flops(m: int, nb: int) -> float:
    i = np.arange(nb, dtype=np.float64)
    mi = m - i
    return float(np.sum(mi**2 + mi + 1.0))


def front_bytes(m: int) -> float:
    return 2.0 * F32 * m * m


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def roofline_share(fronts, times: int, kernel_s: float, peak: dict):
    """(share in %, bound) of ``times`` passes over ``fronts`` [(m, nb)]
    that took ``kernel_s`` device seconds: the least time the chip could
    take, max(flops / peak FLOP/s, bytes / peak bytes/s), over the time
    taken.  ``bound`` says which of the two is larger."""
    flops = times * sum(partial_factor_flops(m, nb) for m, nb in fronts)
    nbytes = times * sum(front_bytes(m) for m, _ in fronts)
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / kernel_s, bound


def path_roofline(run, path: str, kernels) -> float | None:
    """Roofline share (%) of the fronts that took kernel path ``path`` in
    a traced run, over the device time of the events of ``kernels``;
    None where the run has no such front or no such event."""
    tr = run.trace
    fronts = run.layer.get("front_paths", {}).get(path)
    if tr is None or not fronts:
        return None
    kernel_s = sum(tr["kernels"].get(k, 0.0) for k in kernels)
    if kernel_s <= 0:
        return None
    share, bound = roofline_share(fronts, run.layer["factorizations"],
                                  kernel_s, peaks(run.device_kind))
    run.layer.setdefault("roofline_bound", {})[path] = bound
    return share
