"""Jitted public wrappers around the Pallas frontal-factorization kernels.

``partial_cholesky(front, nb)`` matches ``ref.partial_cholesky_ref`` exactly
(up to dtype roundoff): it pads the front to 128-multiples with a unit
diagonal (padded pivots factor to no-ops), picks the VMEM-resident kernel
for fronts ≤ VMEM_FRONT_MAX and the panel+SYRK pipeline above that, and
slices the (panel, schur) outputs back to the caller's shapes.

On non-TPU backends the kernels run in interpret mode (the body executes as
plain JAX ops) — the CPU validation path; on TPU the same code lowers to
Mosaic.  ``should_interpret`` is the one place that rule lives.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from .frontal_cholesky import (
    TILE,
    VMEM_FRONT_MAX,
    front_factor_vmem,
    panel_factor,
    syrk_downdate,
)

OUTER_PANEL = 512  # large-front pivot panel width


def should_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether Pallas kernels run in interpret mode: an explicit choice
    wins, otherwise exactly when JAX's default backend is not a TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@partial(jax.jit, static_argnames=("nb", "interpret"))
def _partial_cholesky_impl(
    front: jax.Array, nb: int, interpret: bool
) -> Tuple[jax.Array, jax.Array]:
    m = front.shape[0]
    mb = m - nb  # border size
    nbp = _round_up(max(nb, 1), TILE)
    mbp = _round_up(mb, TILE) if mb > 0 else 0
    mp = nbp + mbp

    # padded front with unit diagonal; real blocks placed so pivots occupy
    # [0, nb) and the border occupies [nbp, nbp+mb)
    f = jnp.eye(mp, dtype=front.dtype)
    f = f.at[:nb, :nb].set(front[:nb, :nb])
    if mb > 0:
        f = f.at[nbp : nbp + mb, :nb].set(front[nb:, :nb])
        f = f.at[:nb, nbp : nbp + mb].set(front[:nb, nb:])
        f = f.at[nbp : nbp + mb, nbp : nbp + mb].set(front[nb:, nb:])

    if mp <= VMEM_FRONT_MAX:
        out = front_factor_vmem(f, nb, interpret=interpret)
    else:
        out = f
        for k in range(0, nbp, OUTER_PANEL):
            pw = min(OUTER_PANEL, nbp - k)
            slab = jax.lax.dynamic_slice(out, (k, k), (mp - k, pw))
            lp = panel_factor(slab, interpret=interpret)
            out = jax.lax.dynamic_update_slice(out, lp, (k, k))
            trail = mp - k - pw
            if trail > 0:
                c = jax.lax.dynamic_slice(out, (k + pw, k + pw), (trail, trail))
                tile = 256 if trail % 256 == 0 else TILE
                c = syrk_downdate(c, lp[pw:, :], tile=tile, interpret=interpret)
                out = jax.lax.dynamic_update_slice(out, c, (k + pw, k + pw))

    # gather outputs back to unpadded shapes
    top = out[:nb, :nb]
    if mb > 0:
        bottom = out[nbp : nbp + mb, :nb]
        panel = jnp.concatenate([top, bottom], axis=0)
        schur = out[nbp : nbp + mb, nbp : nbp + mb]
    else:
        panel = top
        schur = jnp.zeros((0, 0), dtype=front.dtype)
    # the kernels leave garbage in the strictly-upper triangle of L11
    tri = jnp.tril(jnp.ones((nb, nb), dtype=bool))
    panel = panel.at[:nb, :].set(jnp.where(tri, panel[:nb, :], 0))
    # symmetrize the Schur complement (kernels keep the lower triangle)
    if mb > 0:
        low = jnp.tril(schur)
        schur = low + low.T - jnp.diag(jnp.diag(low))
    return panel, schur


def partial_cholesky(
    front: jax.Array, nb: int, interpret: Optional[bool] = None
) -> Tuple[jax.Array, jax.Array]:
    """Pallas-backed partial Cholesky: (panel (m,nb), schur (m−nb, m−nb))."""
    return _partial_cholesky_impl(front, nb, should_interpret(interpret))


def factor_fn(interpret: Optional[bool] = None):
    """A FactorFn (front, nb) → (panel, schur) for the multifrontal driver."""

    def fn(front: jax.Array, nb: int):
        return partial_cholesky(front, nb, interpret=interpret)

    return fn


# ----------------------------------------------------------------------
# Batched dispatch (the plan executor's path).
#
# Fronts are padded host-side to a 128-aligned (mp, mp) stack and factored
# in ONE pallas_call with a grid over the stack — one dispatch per shape
# class instead of one per front.  The layout is tight: the true front
# fills [0, m)², pivots first, and everything past m is a unit diagonal.
# Each lane's pivot count reaches the kernel as an operand, so fronts with
# different true (m, nb) share a class as long as they round to the same
# (mp, nbp).
# ----------------------------------------------------------------------
def padded_shape(m: int, nb: int) -> Tuple[int, int]:
    """(mp, nbp): the shape class of an (m, m) front with nb pivots — its
    order and its pivot count, each rounded up to a multiple of 128."""
    return _round_up(m, TILE), _round_up(max(nb, 1), TILE)


def pad_front_np(front: np.ndarray, nb: int, dtype=None) -> np.ndarray:
    """Host-side padding of an (m, m) front to its (mp, mp) shape class:
    the front at [0, m)², a unit diagonal past it."""
    m = front.shape[0]
    mp, _ = padded_shape(m, nb)
    f = np.zeros((mp, mp), dtype=dtype or front.dtype)
    f[:m, :m] = front
    i = np.arange(m, mp)
    f[i, i] = 1
    return f


def extract_panel_schur(
    out: np.ndarray, m: int, nb: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Slice a factored padded front back to ((m, nb) panel, (m−nb)² schur).

    Host-side analogue of the output gather in _partial_cholesky_impl:
    zero the garbage above L11's diagonal, symmetrize the Schur block.
    """
    panel = out[:m, :nb].copy()
    panel[:nb] = np.tril(panel[:nb])
    low = np.tril(out[nb:m, nb:m])
    schur = low + low.T - np.diag(np.diag(low))
    return panel, schur


@partial(jax.jit, static_argnames=("interpret", "mesh"))
def _batched_front_factor(
    fronts: jax.Array, nb: jax.Array, interpret: bool, mesh=None
) -> jax.Array:
    def factor(x, n):
        return front_factor_vmem(x, n, interpret=interpret)

    if mesh is None:
        return factor(fronts, nb)
    # GSPMD cannot partition a Mosaic call; shard_map gives each device
    # its own lanes of the batch (lanes are independent fronts)
    spec = PartitionSpec(mesh.axis_names[0])
    return jax.shard_map(
        factor, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
        check_vma=False,
    )(fronts, nb)


def batched_front_factor(
    fronts: jax.Array,
    nb,
    interpret: Optional[bool] = None,
    mesh=None,
) -> jax.Array:
    """Factor a (B, mp, mp) stack of padded fronts in one kernel call.

    ``nb`` is each lane's pivot count, B ints (or one int for every
    lane).  Requires mp ≤ VMEM_FRONT_MAX (the executor routes larger
    fronts through the per-front panel pipeline of ``partial_cholesky``).
    With a 1-D ``mesh`` the batch axis is split over its devices (B must
    be a multiple of the mesh size); each device factors its own lanes.
    """
    b, mp, mp2 = fronts.shape
    assert mp == mp2 and mp <= VMEM_FRONT_MAX
    if not isinstance(nb, jax.Array):
        nb = np.broadcast_to(np.asarray(nb, np.int32), (b,))
    return _batched_front_factor(fronts, nb, should_interpret(interpret), mesh)
