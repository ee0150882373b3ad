"""The plain reference that decides ``correct``: the backward error of a
Cholesky factor against the matrix it factors.

A factorization answers ``P A P^T = L L^T`` with L lower triangular.  The
benchmark generated A itself (``matrices/``), so it holds the exact
answer's defining equation and needs no second factorization: it checks

    ||L L^T - P A P^T||_F / ||A||_F

in float64, on the L the timed path returned and the ordering P it used
(checked to be a permutation).  The norm of the residual is estimated
with Gaussian probes X (n x k): E||R X||_F^2 = k ||R||_F^2, so
``||R X||_F / sqrt(k)`` estimates ||R||_F at the cost of two sparse
triangular products per probe.  A wrong entry anywhere in L, a front
left unfactored or a factor of lower precision raises it; so does any
entry missing from L.

This module imports nothing of the program under test.  It reads the
program's answer in its supernodal form only: per supernode the pivot
columns ``cols``, the front rows ``rows`` and the (m, nb) panel whose
column k holds L[rows, cols[k]] on the rows at or below ``cols[k]``.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np
import scipy.sparse as sp

N_PROBES = 16


class SupernodalLayout:
    """Where each entry of L sits in the concatenated panels.

    Built once per pattern from the supernodes' ``rows`` and ``cols``;
    :meth:`l_matrix` then turns one factorization's panels into a sparse
    L with one gather.
    """

    def __init__(self, n: int, rows: Sequence[np.ndarray], cols: Sequence[np.ndarray]):
        self.n = int(n)
        self.shapes = []
        pos, li, lj = [], [], []
        offset = 0
        for r, c in zip(rows, cols):
            r = np.asarray(r, dtype=np.int64)
            c = np.asarray(c, dtype=np.int64)
            m, nb = r.size, c.size
            self.shapes.append((m, nb))
            i, k = np.nonzero(r[:, None] >= c[None, :])
            pos.append(offset + i * nb + k)
            li.append(r[i])
            lj.append(c[k])
            offset += m * nb
        self.size = offset
        pos = np.concatenate(pos)
        li = np.concatenate(li)
        lj = np.concatenate(lj)
        covered = np.zeros(self.n, dtype=bool)
        covered[lj] = True
        if not covered.all():
            raise ValueError("the supernodes do not cover every column")
        # CSC order of the entries, so each factorization is one gather
        order = np.lexsort((li, lj))
        self.pos = pos[order]
        self.indices = li[order]
        self.indptr = np.searchsorted(lj[order], np.arange(self.n + 1))

    def l_matrix(self, panels: Sequence[np.ndarray]) -> sp.csc_matrix:
        if len(panels) != len(self.shapes):
            raise ValueError(
                f"{len(panels)} panels for {len(self.shapes)} supernodes"
            )
        flat = []
        for p, shape in zip(panels, self.shapes):
            p = np.asarray(p)
            if p.shape != shape:
                raise ValueError(f"panel of shape {p.shape}, expected {shape}")
            flat.append(p.astype(np.float64).ravel())
        data = np.concatenate(flat)[self.pos]
        return sp.csc_matrix(
            (data, self.indices, self.indptr), shape=(self.n, self.n)
        )


def probes(n: int, seed: int, k: int = N_PROBES) -> np.ndarray:
    return np.random.default_rng([seed % 2**63, 0xC0FFEE]).standard_normal((n, k))


def backward_error(l: sp.csc_matrix, ap: sp.csr_matrix, x: np.ndarray) -> float:
    """Estimated ||L L^T - ap||_F / ||ap||_F with probes ``x``; NaN
    anywhere in L gives +inf."""
    r = l @ (l.T @ x) - ap @ x
    est = np.linalg.norm(r) / np.sqrt(x.shape[1])
    err = float(est / np.linalg.norm(ap.data))
    return err if np.isfinite(err) else float("inf")


def exact_backward_error(l: sp.csc_matrix, ap: sp.csr_matrix) -> float:
    """||L L^T - ap||_F / ||ap||_F, exactly (costs a sparse product)."""
    r = (l @ l.T - ap).tocsr()
    return float(np.linalg.norm(r.data) / np.linalg.norm(ap.data))
