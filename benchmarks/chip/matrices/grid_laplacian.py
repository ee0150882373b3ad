"""Weighted k-point grid Laplacians with Dirichlet boundary, vectorised.

The benchmark's own generator: it imports nothing of the program under
test.  A grid of ``dims`` points carries one positive weight on every
link between neighbours, and on every link from a boundary point to the
(eliminated) Dirichlet boundary.  Then

    A[i, i] = sum of the weights of the links at point i
    A[i, j] = -w(i, j)                 for neighbours i, j

which with all weights 1 is the plain 5-point (2D) or 7-point (3D)
Laplacian.  It is irreducibly diagonally dominant, so SPD for any
positive weights.  Points are numbered row-major, the last axis fastest,
as ``repro.sparse.grid_laplacian_2d`` / ``_3d`` number them.

The pattern is fixed by ``dims``; only the values change from one
factorization to the next.  :class:`GridPattern` builds the pattern once
(and, for an ordering, its permuted CSR form) and then makes the values
of any draw with one gather.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np
import scipy.sparse as sp


def _links(dims: Sequence[int]):
    """Interior links (i, j) with i < j, and the boundary link count of
    every point, along each axis in turn."""
    n = int(np.prod(dims))
    ids = np.arange(n, dtype=np.int64).reshape(dims)
    lo, hi = [], []
    boundary = np.zeros(dims, dtype=np.int64)
    for ax in range(len(dims)):
        a = np.moveaxis(ids, ax, 0)
        lo.append(a[:-1].ravel())
        hi.append(a[1:].ravel())
        b = np.moveaxis(boundary, ax, 0)
        b[0] += 1
        b[-1] += 1
    return np.concatenate(lo), np.concatenate(hi), boundary.ravel()


class GridPattern:
    """The fixed pattern of a weighted grid Laplacian on ``dims``.

    Values are drawn per link: ``n_links`` interior weights and one
    boundary weight per boundary link (``n_boundary``), uniform in
    ``weight_range``.
    """

    def __init__(self, dims: Sequence[int], weight_range=(0.5, 1.5)):
        self.dims = tuple(int(d) for d in dims)
        self.n = int(np.prod(self.dims))
        self.weight_range = tuple(float(w) for w in weight_range)
        lo, hi, nbound = _links(self.dims)
        self.lo, self.hi = lo, hi
        self.n_links = lo.size
        # boundary weights: point p owns nbound[p] of them
        self.bound_owner = np.repeat(np.arange(self.n), nbound)
        self.n_boundary = self.bound_owner.size
        # COO of the full symmetric matrix: diagonal, lower, upper
        diag = np.arange(self.n)
        self.rows = np.concatenate([diag, hi, lo])
        self.cols = np.concatenate([diag, lo, hi])

    def weights(self, rng: np.random.Generator) -> np.ndarray:
        """One draw of every link weight (interior links, then boundary)."""
        a, b = self.weight_range
        return rng.uniform(a, b, size=self.n_links + self.n_boundary)

    def coo_values(self, w: np.ndarray) -> np.ndarray:
        """Values in the order of ``rows``/``cols``."""
        wl, wb = w[: self.n_links], w[self.n_links :]
        d = np.bincount(self.lo, wl, self.n) + np.bincount(self.hi, wl, self.n)
        d += np.bincount(self.bound_owner, wb, self.n)
        return np.concatenate([d, -wl, -wl])

    def csr(self, w: np.ndarray, perm=None) -> sp.csr_matrix:
        """A (or P A P^T when ``perm`` is given: row k of the result is
        point ``perm[k]``), in canonical CSR, float64."""
        return self.layout(perm).csr(self.coo_values(w))

    def layout(self, perm=None) -> "CsrLayout":
        rows, cols = self.rows, self.cols
        if perm is not None:
            inv = inverse_permutation(perm, self.n)
            rows, cols = inv[rows], inv[cols]
        return CsrLayout(rows, cols, self.n)


class CsrLayout:
    """Canonical CSR of a fixed COO pattern: values in COO order map to
    CSR data by one gather."""

    def __init__(self, rows: np.ndarray, cols: np.ndarray, n: int):
        src = sp.csr_matrix(
            (np.arange(rows.size, dtype=np.float64), (rows, cols)), shape=(n, n)
        )
        src.sort_indices()
        self.order = src.data.astype(np.int64)
        self.indices = src.indices.copy()
        self.indptr = src.indptr.copy()
        self.n = n

    def csr(self, coo_values: np.ndarray) -> sp.csr_matrix:
        return sp.csr_matrix(
            (coo_values[self.order], self.indices.copy(), self.indptr.copy()),
            shape=(self.n, self.n),
        )


def inverse_permutation(perm, n: int) -> np.ndarray:
    """``inv`` with ``inv[perm[k]] = k``; raises unless ``perm`` is a
    permutation of ``range(n)``."""
    perm = np.asarray(perm, dtype=np.int64)
    if perm.shape != (n,) or not np.array_equal(np.sort(perm), np.arange(n)):
        raise ValueError("ordering is not a permutation of the unknowns")
    inv = np.empty(n, dtype=np.int64)
    inv[perm] = np.arange(n)
    return inv


def nested_dissection(*dims: int, leaf: int = 4) -> np.ndarray:
    """Geometric nested dissection of a grid of ``dims`` points: cut the
    box across its longest axis by the middle plane, order the two halves
    (recursively) and then the plane; a box no wider than ``leaf`` along
    every axis is ordered as it is numbered.  Returns ``perm`` with
    ``perm[k]`` the point eliminated k-th."""
    ids = np.arange(int(np.prod(dims)), dtype=np.int64).reshape(dims)
    out = []

    def cut(box) -> None:
        part = ids[box]
        if part.size == 0:
            return
        if max(part.shape) <= leaf:
            out.append(part.ravel())
            return
        ax = int(np.argmax(part.shape))
        lo, hi = box[ax].start, box[ax].stop
        mid = lo + part.shape[ax] // 2

        def along(a: int, b: int):
            return box[:ax] + (slice(a, b),) + box[ax + 1 :]

        cut(along(lo, mid))
        cut(along(mid + 1, hi))
        out.append(ids[along(mid, mid + 1)].ravel())

    cut(tuple(slice(0, d) for d in ids.shape))
    return np.concatenate(out)


def pattern(cfg: dict) -> GridPattern:
    """The pattern a configuration's ``matrix`` block describes."""
    m = cfg["matrix"]
    return GridPattern(m["grid"], m.get("weight_range", (0.5, 1.5)))
