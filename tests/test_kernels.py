"""Pallas frontal-factorization kernels vs the pure-jnp oracle.

Sweeps shapes and dtypes in interpret mode (CPU container; on TPU the same
calls lower to Mosaic).  Covers both execution paths: the VMEM-resident
whole-front kernel and the panel+SYRK large-front pipeline.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ops as ops
from repro.kernels.frontal_cholesky import TILE, panel_factor, syrk_downdate
from repro.kernels.ref import panel_factor_ref, partial_cholesky_ref, syrk_update_ref


def _spd(m, rng, dtype=np.float32):
    b = rng.normal(size=(m, m)).astype(np.float64)
    a = b @ b.T + m * np.eye(m)
    return a.astype(dtype)


@pytest.mark.parametrize(
    "m,nb",
    [(16, 8), (32, 32), (100, 60), (128, 128), (192, 64), (256, 128),
     (300, 140), (384, 256)],
)
def test_partial_cholesky_matches_ref_f32(m, nb, rng):
    f = jnp.asarray(_spd(m, rng))
    pan, sch = ops.partial_cholesky(f, nb)
    pr, sr = partial_cholesky_ref(f, nb)
    scale = max(1.0, float(jnp.abs(pr).max()))
    assert np.abs(np.asarray(pan) - np.asarray(pr)).max() / scale < 5e-5
    if sch.size:
        s2 = max(1.0, float(jnp.abs(sr).max()))
        assert np.abs(np.asarray(sch) - np.asarray(sr)).max() / s2 < 5e-5


def test_partial_cholesky_f64(rng):
    jax.config.update("jax_enable_x64", True)
    try:
        f = jnp.asarray(_spd(96, rng, np.float64))
        pan, sch = ops.partial_cholesky(f, 48)
        pr, sr = partial_cholesky_ref(f, 48)
        assert np.abs(np.asarray(pan) - np.asarray(pr)).max() < 1e-11
        assert np.abs(np.asarray(sch) - np.asarray(sr)).max() < 1e-11
    finally:
        jax.config.update("jax_enable_x64", False)


def test_large_front_panel_path(rng, monkeypatch):
    monkeypatch.setattr(ops, "VMEM_FRONT_MAX", 256)
    monkeypatch.setattr(ops, "OUTER_PANEL", 256)
    f = jnp.asarray(_spd(520, rng))
    pan, sch = ops.partial_cholesky(f, 384)
    pr, sr = partial_cholesky_ref(f, 384)
    scale = max(1.0, float(jnp.abs(pr).max()))
    assert np.abs(np.asarray(pan) - np.asarray(pr)).max() / scale < 1e-4
    s2 = max(1.0, float(jnp.abs(np.asarray(sr)).max()))
    assert np.abs(np.asarray(sch) - np.asarray(sr)).max() / s2 < 1e-4


def test_panel_factor_kernel(rng):
    mp, nb = 256, TILE
    slab = np.zeros((mp, nb), np.float32)
    a = _spd(mp, rng)
    slab[:, :] = a[:, :nb]
    out = panel_factor(jnp.asarray(slab), interpret=True)
    ref = panel_factor_ref(jnp.asarray(slab))
    tri = np.tril(np.ones((nb, nb), bool))
    got, want = np.asarray(out), np.asarray(ref)
    scale = max(1.0, np.abs(want).max())
    assert np.abs(np.where(tri, got[:nb], 0) - np.where(tri, want[:nb], 0)).max() / scale < 5e-5
    assert np.abs(got[nb:] - want[nb:]).max() / scale < 5e-5


@pytest.mark.parametrize("m,k,tile", [(256, 128, 128), (512, 256, 256)])
def test_syrk_downdate_kernel(m, k, tile, rng):
    c = rng.normal(size=(m, m)).astype(np.float32)
    a = rng.normal(size=(m, k)).astype(np.float32)
    out = syrk_downdate(jnp.asarray(c), jnp.asarray(a), tile=tile, interpret=True)
    ref = syrk_update_ref(jnp.asarray(c), jnp.asarray(a))
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() < 1e-2  # |C|~k


def test_multifrontal_with_pallas_kernel(rng):
    from repro.kernels.ops import factor_fn
    from repro.sparse import (
        analyze,
        factorize,
        grid_laplacian_2d,
        nested_dissection_2d,
        permute_symmetric,
    )

    a = grid_laplacian_2d(13, 13)
    ap = permute_symmetric(a, nested_dissection_2d(13, 13))
    symb = analyze(ap, relax=2)
    fact = factorize(ap, symb, factor_fn=factor_fn())
    l = fact.to_dense_l()
    assert np.abs(l @ l.T - ap.toarray()).max() < 5e-4


def test_padding_pivots_are_inert(rng):
    """nb not a multiple of 128: padded pivots must not change results."""
    f = jnp.asarray(_spd(160, rng))
    pan, sch = ops.partial_cholesky(f, 37)
    pr, sr = partial_cholesky_ref(f, 37)
    scale = max(1.0, float(jnp.abs(pr).max()))
    assert np.abs(np.asarray(pan) - np.asarray(pr)).max() / scale < 5e-5
    assert np.abs(np.asarray(sch) - np.asarray(sr)).max() / max(
        1.0, float(jnp.abs(sr).max())
    ) < 5e-5


def _batched_parity(fronts, nbs, rtol=5e-5):
    """pad_front_np → batched_front_factor (per-lane nb) → extract_panel_schur
    against partial_cholesky_ref, lane by lane; the fronts share a class."""
    classes = {ops.padded_shape(f.shape[0], nb) for f, nb in zip(fronts, nbs)}
    assert len(classes) == 1
    batch = np.stack([ops.pad_front_np(f, nb) for f, nb in zip(fronts, nbs)])
    out = np.asarray(ops.batched_front_factor(jnp.asarray(batch), nbs, True))
    for o, f, nb in zip(out, fronts, nbs):
        m = f.shape[0]
        pan, sch = ops.extract_panel_schur(o, m, nb)
        pr, sr = (np.asarray(x) for x in partial_cholesky_ref(jnp.asarray(f), nb))
        assert pan.shape == (m, nb) and sch.shape == (m - nb, m - nb)
        assert np.abs(pan - pr).max() / max(1.0, np.abs(pr).max()) < rtol
        if sch.size:
            assert np.abs(sch - sr).max() / max(1.0, np.abs(sr).max()) < rtol


@pytest.mark.parametrize(
    "m,nb",
    [(8, 1), (8, 8), (100, 3), (127, 127), (128, 1), (128, 128), (129, 1),
     (129, 128), (200, 129), (256, 255), (1000, 37)],
)
def test_tight_front_layout_matches_ref(m, nb, rng):
    """The whole-front path across the class edges: the front fills
    [0, m)², and the kernel eliminates exactly its nb pivots."""
    _batched_parity([_spd(m, rng)], [nb])


def test_mixed_pivot_counts_in_one_class(rng):
    """Lanes of one (256, 256) class with different orders and pivot
    counts, each checked on its own."""
    shapes = [(256, 255), (200, 129), (130, 130), (256, 256), (180, 150)]
    _batched_parity([_spd(m, rng) for m, _ in shapes], [nb for _, nb in shapes])


def test_padded_shape_never_exceeds_the_split_rule():
    """The tight class is never larger than the old rule, which rounded
    the pivot block and the border block up to 128 each."""
    for m in range(1, 1101):
        for nb in range(1, m + 1):
            mb = m - nb
            old_nbp = -(-nb // TILE) * TILE
            old_mp = old_nbp + (-(-mb // TILE) * TILE)
            mp, nbp = ops.padded_shape(m, nb)
            assert mp <= old_mp and nbp == old_nbp and mp % TILE == 0
            assert m <= mp < m + TILE


@pytest.mark.parametrize(
    "kernel,shapes",
    [
        (lambda f: ops.front_factor_vmem(f, 128), [(256, 256)]),
        (panel_factor, [(256, 128)]),
        (lambda c, a: syrk_downdate(c, a, tile=128), [(256, 256), (256, 128)]),
    ],
    ids=["front_factor_vmem", "panel_factor", "syrk_downdate"],
)
def test_kernel_matmuls_contract_at_full_f32_precision(kernel, shapes):
    """Every MXU contraction in the front kernels asks for HIGHEST
    precision.  Interpret mode cannot show the difference, but on a v5e
    Mosaic's default (one bf16 pass) left a backward error of 5.5e-4 on
    a 65k-unknown 2D grid, far above the 100·eps_f32 tolerance."""
    args = [jnp.zeros(s, jnp.float32) for s in shapes]
    text = str(jax.make_jaxpr(kernel)(*args))
    dots = re.findall(r"dot_general\[(.*?)preferred_element_type", text, flags=re.S)
    assert dots
    for params in dots:
        assert "precision=(Precision.HIGHEST, Precision.HIGHEST)" in params


def _pallas_call_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                out += _pallas_call_names(sub)
    return out


@pytest.mark.parametrize(
    "fn,shape,names",
    [
        (lambda x: ops.batched_front_factor(x, 128, True), (2, 256, 256),
         {"front_factor_vmem"}),
        (lambda x: ops.partial_cholesky(x, 600, interpret=True), (1200, 1200),
         {"panel_factor", "syrk_downdate"}),
    ],
    ids=["batched_front_factor", "partial_cholesky"],
)
def test_pallas_calls_carry_stable_kernel_names(fn, shape, names):
    """Each Pallas call is named, so a device trace can match kernels
    by name and not by the jitted function that launched them."""
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros(shape, jnp.float32))
    assert set(_pallas_call_names(jaxpr.jaxpr)) == names


# ----------------------------------------------------------------------
# flash attention kernel (§Perf fix for the dense-train cells)
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,t,h,dh,bq,bkv,causal",
    [(1, 64, 2, 16, 16, 16, True), (2, 128, 3, 32, 32, 64, True),
     (1, 64, 2, 16, 32, 16, False), (1, 96, 1, 8, 32, 32, True)],
)
def test_flash_attention_matches_naive(b, t, h, dh, bq, bkv, causal):
    from repro.kernels.flash_attention import flash_attention

    key = jax.random.PRNGKey(b * 7 + t)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, h, dh))
    k = jax.random.normal(ks[1], (b, t, h, dh))
    v = jax.random.normal(ks[2], (b, t, h, dh))
    o = flash_attention(q, k, v, causal=causal, block_q=bq, block_kv=bkv,
                        interpret=True)
    scale = dh**-0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
    assert np.abs(np.asarray(o) - np.asarray(ref)).max() < 2e-5
