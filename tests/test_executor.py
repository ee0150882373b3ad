"""Malleable-plan executor: CPU interpret-mode end-to-end + unit tests."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.distributed.device_groups import (
    assign_wave_groups,
    groups_footprint,
    pow2_floor,
    scale_group,
)
from repro.runtime.executor import PlanExecutor, execute_plan
from repro.sparse import (
    analyze,
    factorize,
    grid_laplacian_2d,
    make_plan,
    nested_dissection_2d,
    permute_symmetric,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="module")
def problem():
    a = grid_laplacian_2d(9)
    ap = permute_symmetric(a, nested_dissection_2d(9))
    symb = analyze(ap, relax=1)
    plan = make_plan(symb.task_tree(), 8, alpha=0.9)
    return ap, symb, plan


def test_executor_end_to_end(problem):
    ap, symb, plan = problem
    fact, report = execute_plan(ap, symb, plan)
    dense = ap.toarray()
    l = fact.to_dense_l()
    rel = np.abs(l @ l.T - dense).max() / np.abs(dense).max()
    assert rel < 1e-5

    # one trace event per front, all with positive duration bounds
    assert sorted(e.front for e in report.trace) == list(
        range(symb.n_supernodes)
    )
    assert report.measured_makespan > 0
    assert report.n_dispatches <= len(report.trace)
    # trace respects plan precedence: child fronts finish before parents run
    ev = {e.front: e for e in report.trace}
    for s, sn in enumerate(symb.supernodes):
        if sn.parent >= 0:
            assert ev[sn.parent].t_start >= ev[s].t_end - 1e-9
    # report renders and compares measured vs projected
    text = report.summary()
    assert "measured" in text and "projected" in text
    assert report.projected_seconds() > 0
    # single device => no group-size variety => honest n/a, not a number
    assert report.fit_alpha() is None


def test_wave_batching_matches_sequential(problem):
    """Batched padded dispatch must reproduce the sequential driver."""
    ap, symb, plan = problem
    fact_batched, _ = execute_plan(ap, symb, plan)
    fact_seq = factorize(ap, symb)
    for pb, ps in zip(fact_batched.panels, fact_seq.panels):
        np.testing.assert_allclose(pb, ps, rtol=1e-8, atol=1e-8)


def test_executor_proportional_strategy(problem):
    ap, symb, _ = problem
    plan = make_plan(symb.task_tree(), 8, alpha=0.9, strategy="proportional")
    assert plan.strategy == "proportional"
    assert plan.makespan >= plan.fluid_makespan - 1e-9
    fact, _ = execute_plan(ap, symb, plan)
    dense = ap.toarray()
    l = fact.to_dense_l()
    assert np.abs(l @ l.T - dense).max() / np.abs(dense).max() < 1e-5


def test_dispatch_schedule_batches_same_shapes(problem):
    ap, symb, plan = problem
    ex = PlanExecutor(symb, plan)
    ds = ex.dispatches()
    # every front dispatched exactly once
    alls = sorted(s for d in ds for s in d.supernodes)
    assert alls == list(range(symb.n_supernodes))
    # batching actually happens: fewer dispatches than fronts
    assert len(ds) < symb.n_supernodes
    # a dispatch never mixes shape classes or waves
    for d in ds:
        for s in d.supernodes:
            sn = symb.supernodes[s]
            from repro.kernels.ops import padded_shape

            assert padded_shape(sn.m, sn.nb) == d.key


@pytest.mark.parametrize("mode", ["async", "waves"])
def test_report_counts_whole_front_stack_bytes(problem, mode):
    """``batch_bytes`` is Σ B·mp²·itemsize over the run's whole-front
    dispatches (warm-up excluded), and starts from zero on every run."""
    from repro.kernels.frontal_cholesky import VMEM_FRONT_MAX
    from repro.kernels.ops import padded_shape

    ap, symb, plan = problem
    ex = PlanExecutor(symb, plan, mode=mode)
    _, report = ex.run(ap, warmup=True)
    per_dispatch = {}
    for e in report.trace:
        sn = symb.supernodes[e.front]
        mp = padded_shape(sn.m, sn.nb)[0]
        if mp <= VMEM_FRONT_MAX:
            per_dispatch[(e.wave, e.t_start)] = e.batched * mp * mp * 8
    assert per_dispatch
    assert report.batch_bytes == sum(per_dispatch.values())
    assert ex.run(ap, warmup=False)[1].batch_bytes == report.batch_bytes
    assert "MiB sent" in report.summary()


# ----------------------------------------------------------------------
def test_pow2_floor():
    assert [pow2_floor(x) for x in (1, 2, 3, 7, 8, 9)] == [1, 2, 2, 4, 8, 8]


def test_scale_group_downscales_plan():
    # a 64-wide plan group on a 4-device mesh keeps its proportion
    assert scale_group(64, 256, 4) == 1
    assert scale_group(256, 256, 4) == 4
    assert scale_group(8, 8, 8) == 8
    assert scale_group(3, 8, 8) == 2  # pow2 floor when counts match


def test_assign_wave_groups_buddy():
    groups = assign_wave_groups({0: 4, 1: 2, 2: 2}, 8)
    touched, max_load = groups_footprint(groups)
    assert touched == 8 and max_load == 1  # disjoint, fully packed
    assert groups[0].size == 4 and groups[0].offset % 4 == 0
    for g in groups.values():
        assert g.size & (g.size - 1) == 0  # power of two


def test_assign_wave_groups_oversubscribed():
    # more demand than devices: placement degrades to time-sharing, never raises
    groups = assign_wave_groups({i: 2 for i in range(5)}, 4)
    assert len(groups) == 5
    _, max_load = groups_footprint(groups)
    assert max_load >= 2


@pytest.mark.slow
def test_executor_multi_device_forged():
    """Device-group execution on 4 forged CPU devices, unsharded and
    sharded (subprocess owns the XLA device-forging flag before jax
    initializes)."""
    code = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.sparse import analyze, grid_laplacian_2d, make_plan, \
    nested_dissection_2d, permute_symmetric
from repro.runtime import execute_plan

assert jax.device_count() == 4
a = grid_laplacian_2d(9)
ap = permute_symmetric(a, nested_dissection_2d(9))
symb = analyze(ap, relax=1)
plan = make_plan(symb.task_tree(), 4, alpha=0.9)
fact, rep = execute_plan(ap, symb, plan)
dense = ap.toarray()
l = fact.to_dense_l()
assert np.abs(l @ l.T - dense).max() / np.abs(dense).max() < 1e-5
used = {e.devices_used for e in rep.trace}
assert max(used) > 1, used  # groups actually span devices
# sharded dispatch: each device factors its own lanes under shard_map,
# batches land on their carved devices; lanes are independent fronts,
# so the factor bits match the unsharded run
for mode in ("async", "waves"):
    f_s, r_s = execute_plan(ap, symb, plan, shard_dispatch=True, mode=mode)
    assert all(np.array_equal(p, q) for p, q in zip(fact.panels, f_s.panels))
    assert max(e.dispatch_devices for e in r_s.trace) > 1
print("MULTIDEV_OK", sorted(used), rep.fit_alpha())
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEV_OK" in out.stdout


def test_compile_cache_dir_env_or_checkout(monkeypatch):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says, else to the checkout's fixed .jax_cache."""
    from repro.runtime.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == path
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
