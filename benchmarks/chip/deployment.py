"""The matrices a configuration factors: one fixed pattern, ordered once,
with new values for every draw.

Both drivers take their matrices from here, so the configuration file is
the one place that names a deployment's matrix and its ordering:

    "matrix":   {"family": <matrices/ module>, "grid": [...], ...}
    "ordering": {"by": "program" | "benchmark", "call": <function>,
                 "input": "grid" | "matrix"}

``by: program`` calls ``repro.sparse.<call>``, the program's own ordering
(timed as ``ordering_s``); ``by: benchmark`` calls ``<call>`` of the
matrix family, an ordering the user brings, as one computed by an outside
tool is passed to ``Session.analyze``.  ``input`` says whether the call
takes the grid's dimensions or the matrix.
"""
from __future__ import annotations

import numpy as np

WARM = 2**32  # draws of warm-up work: WARM, WARM + 1, ...; the window's are 1, 2, ...


def draw(seed: int, k: int) -> np.random.Generator:
    """The random stream of draw ``k`` of a run with ``seed``."""
    return np.random.default_rng([seed % 2**63, k])


class Matrices:
    """A configuration's pattern, and its values per ``(seed, k)``."""

    def __init__(self, family, cfg: dict) -> None:
        self.family = family
        self.pattern = family.pattern(cfg)
        self.spec = cfg["ordering"]
        self.layout = None  # CSR layout of P A P^T, set by ``order``

    def first(self, seed: int):
        """A of the run's first draw, in the natural order: what the
        ordering and the symbolic analysis see."""
        p = self.pattern
        return p.csr(p.weights(draw(seed, 0)))

    def order(self, a) -> np.ndarray:
        """The configuration's fill-reducing ordering of ``a``."""
        spec = self.spec
        if spec["by"] == "program":
            import repro.sparse as rs

            fn = getattr(rs, spec["call"])
        else:
            fn = getattr(self.family, spec["call"])
        perm = fn(*self.pattern.dims) if spec["input"] == "grid" else fn(a)
        self.layout = self.pattern.layout(perm)
        return perm

    def values(self, seed: int, k: int):
        """P A P^T of draw ``k``, float64 CSR."""
        p = self.pattern
        return self.layout.csr(p.coo_values(p.weights(draw(seed, k))))
