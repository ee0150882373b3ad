"""Pallas TPU kernels: blocked partial Cholesky of a frontal matrix.

TPU adaptation of the paper's task interior (§3: tiled BLAS panels under a
runtime).  On TPU the front lives in HBM; factorization is staged through
VMEM in MXU-aligned 128-tiles:

* ``front_factor_vmem`` — whole-front-in-VMEM partial factorization for
  fronts up to ``VMEM_FRONT_MAX`` (the common case: the vast majority of
  assembly-tree fronts).  Inner loop: per-128-column block, unblocked
  rank-1 panel factorization (VPU work, O(m·tb) per block) followed by one
  MXU matmul Schur downdate of the trailing columns — the O(m²·tb) flops
  land on the MXU.
* ``panel_factor`` — (M, NB) slab factorization for the large-front path
  (ops.py loops panels and applies the tiled SYRK between them).
* ``syrk_downdate`` — grid-tiled C −= A·Aᵀ trailing update; C tiles stream
  through VMEM, the two A slabs are fetched per tile.

Masking convention: fronts are symmetric and only the lower triangle is
kept correct.  Padding: ops.py pads fronts with a unit diagonal so padded
pivot columns factor to no-ops (L column = e_j, zero Schur contribution),
keeping every kernel shape a static multiple of 128.

Multiplier-extraction trick: the rank-1 update of column c by the freshly
factored column ℓ needs the scalar ℓ[c] (a gather along rows).  Gathers are
awkward on TPU; instead ``mult[0, c] = Σ_r [r == c]·ℓ[r]`` — a masked
reduction the VPU does in one pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # MXU-aligned tile edge
VMEM_FRONT_MAX = 1024  # largest padded front factored whole in VMEM
# Scoped VMEM for the whole-front and panel kernels.  Their bodies keep a
# few (mp, mp) fp32 temporaries live; at mp = 1024 that is 16.3 MiB, over
# the v5e default of 16 MiB.  v5e has 128 MiB of VMEM per core.
_FRONT_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2**20)
# f32 Schur downdates at full f32 accuracy.  Mosaic's default contracts
# f32 operands in one bf16 pass, which left a backward error of 5.5e-4 on
# a 65k-unknown 2D grid (v5e) against a tolerance of 100 eps_f32.
_MXU_PRECISION = jax.lax.Precision.HIGHEST


def _factor_block_columns(a, off, tb, mp, ncols):
    """Unblocked Cholesky of columns [off, off+tb) of an (mp, ncols) slab
    whose row i aligns with column i (diagonal at [i, i]).

    Returns the slab with those columns replaced by L columns and the
    remaining columns of the *block* rank-1-downdated.  Columns right of the
    block are untouched (the caller applies the MXU block downdate).
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, ncols), 1)

    def col_step(j, a):
        idx = off + j
        is_col = cols == idx
        is_row = rows == idx
        # column idx and its pivot by masked reductions (Mosaic has no
        # value-level dynamic_slice); the sums add zeros, so they are exact
        col = jnp.sum(jnp.where(is_col, a, 0.0), axis=1, keepdims=True)
        d = jnp.sum(jnp.where(is_row, col, 0.0), axis=0, keepdims=True)
        dsq = jnp.sqrt(d)
        below = rows > idx
        lcol = jnp.where(below, col / dsq, 0.0)
        lcol = jnp.where(is_row, dsq, lcol)
        # rank-1 downdate of the remaining columns of this block:
        # a[:, c] -= lcol * lcol[c]; extract lcol[c] by masked reduction.
        l_below = jnp.where(below, lcol, 0.0)
        mult = jnp.sum(jnp.where(rows == cols, l_below, 0.0), axis=0, keepdims=True)
        in_block = (cols > idx) & (cols < off + tb)
        upd = l_below * jnp.where(in_block, mult, 0.0)
        return jnp.where(is_col, lcol, a - upd).astype(a.dtype)

    return jax.lax.fori_loop(0, tb, col_step, a)


# ----------------------------------------------------------------------
# Whole-front VMEM-resident kernel
# ----------------------------------------------------------------------
def _front_factor_body(front_ref, out_ref, *, mp: int, nbp: int, tb: int):
    a = front_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, mp), 1)

    def block_step(kb, a):
        off = kb * tb
        a = _factor_block_columns(a, off, tb, mp, mp)
        # MXU Schur downdate of all columns right of the block
        blockmask = (cols >= off) & (cols < off + tb)
        panel = jnp.where(blockmask & (rows > cols), a, 0.0)  # (mp, mp)
        upd = jax.lax.dot_general(
            panel, panel, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.promote_types(a.dtype, jnp.float32),
            precision=_MXU_PRECISION,
        ).astype(a.dtype)
        trailing = cols >= off + tb
        return jnp.where(trailing, a - upd, a)

    a = jax.lax.fori_loop(0, nbp // tb, block_step, a)
    out_ref[...] = a


def front_factor_vmem(
    front: jax.Array, nbp: int, interpret: bool = False
) -> jax.Array:
    """Factor the leading ``nbp`` (multiple-of-128) columns of a padded
    (mp, mp) front in one VMEM-resident pallas_call.  Returns the updated
    matrix: factor panel in the first nbp columns (lower triangle), Schur
    complement in the trailing block."""
    mp = front.shape[0]
    assert front.shape == (mp, mp) and mp % TILE == 0 and nbp % TILE == 0
    body = functools.partial(_front_factor_body, mp=mp, nbp=nbp, tb=TILE)
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((mp, mp), front.dtype),
        in_specs=[pl.BlockSpec((mp, mp), lambda: (0, 0))],
        out_specs=pl.BlockSpec((mp, mp), lambda: (0, 0)),
        compiler_params=_FRONT_PARAMS,
        interpret=interpret,
        name="front_factor_vmem",
    )(front)


# ----------------------------------------------------------------------
# Panel kernel for the large-front path
# ----------------------------------------------------------------------
def _panel_factor_body(slab_ref, out_ref, *, mp: int, nb: int, tb: int):
    a = slab_ref[...]  # (mp, nb); diagonal block is the leading nb rows
    rows = jax.lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)

    def block_step(kb, a):
        off = kb * tb
        a = _factor_block_columns(a, off, tb, mp, nb)
        # MXU downdate of the slab columns right of the block:
        # upd[r, c] = Σ_k panel[r, k]·panel[c, k]; rows c of the panel are
        # its leading nb rows (row i ↔ column i alignment).
        blockmask = (cols >= off) & (cols < off + tb)
        panel = jnp.where(blockmask & (rows > cols), a, 0.0)  # (mp, nb)
        top = panel[:nb, :]  # (nb, nb)
        upd = jax.lax.dot_general(
            panel, top, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.promote_types(a.dtype, jnp.float32),
            precision=_MXU_PRECISION,
        ).astype(a.dtype)
        trailing = cols >= off + tb
        return jnp.where(trailing, a - upd, a)

    a = jax.lax.fori_loop(0, nb // tb, block_step, a)
    out_ref[...] = a


def panel_factor(slab: jax.Array, interpret: bool = False) -> jax.Array:
    """Factor an (mp, nb) slab (mp ≥ nb, both multiples of 128): leading
    nb×nb block Cholesky + TRSM of the rows below."""
    mp, nb = slab.shape
    assert mp % TILE == 0 and nb % TILE == 0 and mp >= nb
    body = functools.partial(_panel_factor_body, mp=mp, nb=nb, tb=TILE)
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((mp, nb), slab.dtype),
        in_specs=[pl.BlockSpec((mp, nb), lambda: (0, 0))],
        out_specs=pl.BlockSpec((mp, nb), lambda: (0, 0)),
        compiler_params=_FRONT_PARAMS,
        interpret=interpret,
        name="panel_factor",
    )(slab)


# ----------------------------------------------------------------------
# Tiled SYRK downdate: C -= A·Aᵀ (the large-front Schur update)
# ----------------------------------------------------------------------
def _syrk_body(a_row_ref, a_col_ref, c_ref, o_ref):
    acc = c_ref[...]
    o_ref[...] = acc - jax.lax.dot_general(
        a_row_ref[...],
        a_col_ref[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.promote_types(acc.dtype, jnp.float32),
        precision=_MXU_PRECISION,
    ).astype(acc.dtype)


def syrk_downdate(
    c: jax.Array, a: jax.Array, tile: int = 256, interpret: bool = False
) -> jax.Array:
    """C − A·Aᵀ with C (M, M), A (M, K); M a multiple of ``tile``.

    Grid (i, j) over C tiles; each step streams the two A slabs it needs.
    The panel width K stays whole in VMEM: tile·K·4B per slab — with
    tile=256, K=512, fp32 that is 0.5 MiB per operand.
    """
    m, k = a.shape
    assert c.shape == (m, m) and m % tile == 0
    grid = (m // tile, m // tile)
    return pl.pallas_call(
        _syrk_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tile, k), lambda i, j: (j, 0)),
            pl.BlockSpec((tile, tile), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((tile, tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, m), c.dtype),
        interpret=interpret,
        name="syrk_downdate",
    )(a, a, c)
