"""Async futures executor: event-driven dispatch vs the wave barrier.

The contract under test (see ``repro.runtime.executor``): the async
runner may complete fronts in any order the tree admits — stragglers
stall only their ancestors — yet the factors stay bit-identical to the
wave path, precedence is never violated, and freed-buffer accounting
keeps the measured peak within the wave path's when capped.
"""
import math
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from repro.distributed.device_groups import BuddyAllocator, pow2_floor
from repro.kernels.frontal_cholesky import VMEM_FRONT_MAX
from repro.runtime import executor as executor_mod
from repro.runtime.executor import MODES, PlanExecutor, ReadyQueue
from repro.runtime.straggler import FrontDelays
from repro.sparse import (
    analyze,
    grid_laplacian_2d,
    make_plan,
    nested_dissection_2d,
    permute_symmetric,
)
from repro.sparse.matrix import random_spd
from repro.sparse.ordering import min_degree

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


def _run(problem, mode, **kw):
    ap, symb, plan = problem
    return PlanExecutor(symb, plan, mode=mode, **kw).run(ap, warmup=False)


# ----------------------------------------------------------------------
# BuddyAllocator: incremental power-of-two group carving
# ----------------------------------------------------------------------
def test_buddy_alloc_pow2_aligned():
    alloc = BuddyAllocator(8)
    g4 = alloc.alloc(4)
    g2 = alloc.alloc(2)
    g1 = alloc.alloc(3)  # 3 floors to 2, halves to fit the free single
    for g in (g4, g2, g1):
        assert g is not None
        assert g.size & (g.size - 1) == 0
        assert g.offset % g.size == 0
    assert g4.size == 4 and g2.size == 2
    assert alloc.n_free == 8 - g4.size - g2.size - g1.size


def test_buddy_exhaustion_and_free():
    alloc = BuddyAllocator(4)
    gs = [alloc.alloc(1) for _ in range(4)]
    assert all(g is not None for g in gs)
    assert alloc.n_free == 0
    assert alloc.alloc(1) is None  # full: caller must wait for a free
    alloc.free(gs[1])
    assert alloc.n_free == 1
    g = alloc.alloc(4)  # only one device free: degrades, never None
    assert g is not None and g.size == 1 and g.offset == gs[1].offset


def test_buddy_double_free_asserts():
    alloc = BuddyAllocator(2)
    g = alloc.alloc(2)
    alloc.free(g)
    with pytest.raises(AssertionError):
        alloc.free(g)


# ----------------------------------------------------------------------
# FrontDelays: the deterministic straggler injection
# ----------------------------------------------------------------------
def test_front_delays_random_seeded():
    d1 = FrontDelays.random(range(40), 5, 0.25, seed=3)
    d2 = FrontDelays.random(range(40), 5, 0.25, seed=3)
    assert d1.delays == d2.delays  # same seed, same stragglers
    assert len(d1.delays) == 5
    assert d1.total() == pytest.approx(1.25)
    hit = next(iter(d1.delays))
    assert d1(hit) == 0.25
    miss = next(s for s in range(40) if s not in d1.delays)
    assert d1(miss) == 0.0


def test_bad_mode_rejected(problem):
    ap, symb, plan = problem
    with pytest.raises(ValueError):
        PlanExecutor(symb, plan, mode="eager")
    assert MODES == ("async", "waves")


# ----------------------------------------------------------------------
# Bit-identical factors + per-front observables
# ----------------------------------------------------------------------
def test_async_bit_identical_to_waves(problem):
    ap, symb, plan = problem
    fw, rw = _run(problem, "waves")
    fa, ra = _run(problem, "async")
    for pw, pa in zip(fw.panels, fa.panels):
        np.testing.assert_array_equal(pw, pa)
    dense = ap.toarray()
    l = fa.to_dense_l()
    assert np.abs(l @ l.T - dense).max() / np.abs(dense).max() < 1e-5
    assert rw.mode == "waves" and ra.mode == "async"

    # async records per-front readiness; the wave path has no such instant
    assert all(not math.isnan(e.t_ready) for e in ra.trace)
    assert all(not math.isnan(e.t_submit) for e in ra.trace)
    assert all(math.isnan(e.t_ready) for e in rw.trace)
    assert ra.mean_ready_latency() is not None
    assert rw.mean_ready_latency() is None
    # submit happens at/after ready, dispatch at/after submit
    for e in ra.trace:
        assert e.t_submit >= e.t_ready - 1e-9
        assert e.dispatch_latency >= -1e-9
        assert e.ready_latency >= -1e-9


def test_async_tree_precedence(problem):
    ap, symb, plan = problem
    _, ra = _run(problem, "async")
    ev = {e.front: e for e in ra.trace}
    assert sorted(ev) == list(range(symb.n_supernodes))
    for s, sn in enumerate(symb.supernodes):
        if sn.parent >= 0:
            # a parent's dispatch starts only after the child landed
            assert ev[sn.parent].t_start >= ev[s].t_end - 1e-9
            # and its recorded ready instant is the last child completion
            assert ev[sn.parent].t_ready >= ev[s].t_end - 1e-9


@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="overtaking needs a second device group (one device means the "
    "straggler holds the whole mesh); CI's forged 8-device job runs this",
)
def test_async_out_of_order_completion(problem):
    """A straggling leaf must not stall unrelated fronts (no barrier)."""
    ap, symb, plan = problem
    # delay the first leaf; everything outside its ancestor chain should
    # overtake it
    leaf = next(
        s for s in range(symb.n_supernodes) if not any(
            symb.supernodes[c].parent == s for c in range(symb.n_supernodes)
        )
    )
    delays = FrontDelays(delays={leaf: 0.5})
    # max_batch=1 keeps the straggler out of its siblings' dispatches
    # (coalescing would make the whole shape class as slow as its slowest
    # member, which is the point of batching — but not of this test)
    fw, rw = _run(problem, "waves", delay_fn=delays, max_batch=1)
    fa, ra = _run(problem, "async", delay_fn=delays, max_batch=1)
    for pw, pa in zip(fw.panels, fa.panels):
        np.testing.assert_array_equal(pw, pa)

    ancestors = {leaf}
    p = symb.supernodes[leaf].parent
    while p >= 0:
        ancestors.add(p)
        p = symb.supernodes[p].parent
    ev = {e.front: e for e in ra.trace}
    overtakers = [
        s
        for s in range(symb.n_supernodes)
        if s not in ancestors and ev[s].t_end < ev[leaf].t_end
    ]
    assert overtakers, "no front overtook the injected straggler"
    # the barrier pays the stall on the whole mesh; the futures runner
    # hides it behind independent work
    assert ra.measured_makespan < rw.measured_makespan


def test_async_peak_capped_by_wave_peak(problem):
    """Freed-buffer accounting: capped async stays within the wave peak."""
    _, rw = _run(problem, "waves")
    _, ra = _run(
        problem, "async", memory_cap_bytes=rw.measured_peak_bytes
    )
    assert ra.measured_peak_bytes <= rw.measured_peak_bytes
    assert ra.measured_peak_bytes > 0


def test_async_chrome_trace_export(problem):
    _, ra = _run(problem, "async")
    _, rw = _run(problem, "waves")
    evs = ra.to_trace()
    assert evs and all(e["ph"] == "X" for e in evs)
    assert all(e["dur"] > 0 for e in evs)
    assert all("ready_latency_s" in e["args"] for e in evs)
    assert all("dispatch_latency_s" in e["args"] for e in evs)
    assert {e["cat"] for e in evs} == {"async"}
    # the wave trace has no readiness observables to export
    wevs = rw.to_trace()
    assert all("ready_latency_s" not in e["args"] for e in wevs)


# ----------------------------------------------------------------------
# The public surfaces: Session.execute(mode=) and execute_online
# ----------------------------------------------------------------------
def test_session_execute_mode():
    from repro.api import DeviceMesh, Problem, Session

    g = 9
    a = grid_laplacian_2d(g)
    prob = Problem.from_matrix(
        a, 0.9, ordering=nested_dissection_2d(g), relax=1
    )
    sess = Session(DeviceMesh(plan_devices=8)).load(prob).plan("greedy")
    rep_w = sess.execute(warmup=False, mode="waves")
    rep_a = sess.execute(warmup=False)  # async is the default
    assert rep_w.detail.mode == "waves"
    assert rep_a.detail.mode == "async"
    np.testing.assert_array_equal(
        rep_w.artifact.to_dense_l(), rep_a.artifact.to_dense_l()
    )
    # no ready-latency samples under waves: the key is absent (metrics
    # never carry None/NaN — the obs layer's null-free contract)
    assert "mean_ready_latency_s" not in rep_w.metrics
    assert rep_a.metrics["mean_ready_latency_s"] >= 0.0


def test_execute_online_async():
    from repro.online.replay import execute_online

    g = 9
    a = grid_laplacian_2d(g)
    ap = permute_symmetric(a, nested_dissection_2d(g))
    symb = analyze(ap, relax=1)
    fact, exec_rep, online_rep = execute_online(
        ap, symb, 8, 0.9, warmup=False
    )
    assert exec_rep.mode == "async"
    dense = ap.toarray()
    l = fact.to_dense_l()
    assert np.abs(l @ l.T - dense).max() / np.abs(dense).max() < 1e-5


# ----------------------------------------------------------------------
# Bit-identity matrix: {async, waves, sequential} × {optimized, unopt}
# ----------------------------------------------------------------------
@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="the matrix needs real device groups to be a meaningful cross-"
    "check; CI's forged 8-device job runs this",
)
def test_bit_identity_matrix_optimized(problem):
    """Every runner × every tree rewrite lands the same factor bits.

    The amalgamated plan schedules fused groups, yet each member front
    still assembles (extend-add in tree order) and factors at its own
    padded shape class — so all six legs must agree bit-for-bit.  The
    sequential leg routes ``factorize`` through the *executor's* kernel
    path (pad → batched factor → extract), not the jnp reference
    kernel, so it is the same arithmetic by construction.
    """
    import jax.numpy as jnp

    from repro.api import DeviceMesh, Problem, Session
    from repro.kernels.frontal_cholesky import VMEM_FRONT_MAX
    from repro.kernels.ops import (
        batched_front_factor,
        extract_panel_schur,
        pad_front_np,
        padded_shape,
        partial_cholesky,
    )
    from repro.sparse import factorize

    ap, symb, plan = problem
    interpret = jax.default_backend() != "tpu"

    def kernel_factor(f, nb):
        # the executor's small-front path, one-lane batch
        fh = np.asarray(f)
        mp, _ = padded_shape(fh.shape[0], nb)
        if mp > VMEM_FRONT_MAX:
            return partial_cholesky(f, nb, interpret=interpret)
        batch = pad_front_np(fh, nb, fh.dtype)[None]
        out = np.asarray(
            jax.block_until_ready(
                batched_front_factor(jnp.asarray(batch), [nb], interpret)
            )
        )
        return extract_panel_schur(out[0], fh.shape[0], nb)

    legs = {"sequential/unopt": factorize(ap, symb, factor_fn=kernel_factor)}
    for mode in MODES:
        legs[f"{mode}/unopt"], _ = _run(problem, mode)

    prob = Problem.from_symbolic(symb, 0.9, matrix=ap)
    sess = Session(DeviceMesh()).load(prob).optimize(max_front=64)
    assert sess.problem.n < prob.n, "amalgamation found nothing to fuse"
    sess.plan("greedy")
    for mode in MODES:
        legs[f"{mode}/opt"] = sess.execute(
            warmup=False, mode=mode
        ).artifact

    ref_name, ref = next(iter(legs.items()))
    for name, fact in legs.items():
        for s, (pr, pf) in enumerate(zip(ref.panels, fact.panels)):
            np.testing.assert_array_equal(
                pr, pf, err_msg=f"panel {s}: {name} != {ref_name}"
            )


@pytest.mark.slow
def test_async_beats_waves_forged_mesh():
    """The tentpole A/B on a forged 8-device mesh (subprocess owns the
    XLA flag): with injected stragglers the futures runner must beat the
    barrier, bit-identically, within the wave path's memory peak."""
    code = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.runtime.executor import PlanExecutor
from repro.runtime.straggler import FrontDelays
from repro.sparse import analyze, grid_laplacian_2d, make_plan, \
    nested_dissection_2d, permute_symmetric

assert jax.device_count() == 8
a = grid_laplacian_2d(11)
ap = permute_symmetric(a, nested_dissection_2d(11))
symb = analyze(ap, relax=1)
plan = make_plan(symb.task_tree(), 8, alpha=0.9)
delays = FrontDelays.random(range(symb.n_supernodes), 4, 0.2, seed=1)
fw, rw = PlanExecutor(symb, plan, mode="waves", delay_fn=delays).run(ap)
fa, ra = PlanExecutor(
    symb, plan, mode="async", delay_fn=delays,
    memory_cap_bytes=rw.measured_peak_bytes,
).run(ap)
for pw, pa in zip(fw.panels, fa.panels):
    np.testing.assert_array_equal(pw, pa)
assert ra.measured_peak_bytes <= rw.measured_peak_bytes
speedup = rw.measured_makespan / ra.measured_makespan
assert speedup > 1.0, (rw.measured_makespan, ra.measured_makespan)
print("ASYNC_AB_OK", round(speedup, 3))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ASYNC_AB_OK" in out.stdout


# ----------------------------------------------------------------------
# ReadyQueue: per-shape-class heaps in place of the regrouped ready list
# ----------------------------------------------------------------------
class _RegroupingReadyList:
    """The async runner's ready set before the per-class heaps, kept as it
    was: a flat list regrouped by padded shape class at every dispatch.
    The reference the heaps must match, dispatch for dispatch."""

    def __init__(self, shape, prio, max_batch):
        self.shape, self.prio, self.max_batch = shape, prio, max_batch
        self.ready = []

    def __len__(self):
        return len(self.ready)

    def push(self, s):
        self.ready.append(s)

    def pop_batch(self):
        ready, prio = self.ready, self.prio
        classes = {}
        for s in ready:
            classes.setdefault(self.shape[s], []).append(s)
        key = min(classes, key=lambda k: min(prio[s] for s in classes[k]))
        mp, nbp = key
        members = sorted(classes[key], key=lambda s: prio[s])
        if mp > VMEM_FRONT_MAX:
            members = members[:1]
        else:
            members = members[: pow2_floor(min(len(members), self.max_batch))]
        for s in members:
            ready.remove(s)
        return key, members


def _queue(shapes, starts, max_batch=32):
    """A ReadyQueue over fronts 0.. with the given classes and start times
    (priority ``(start, s)``, as the runner builds it)."""
    prio = {s: (float(t), s) for s, t in enumerate(starts)}
    return ReadyQueue(shapes, prio, max_batch)


def test_ready_queue_pops_class_of_earliest_front():
    a, b = (256, 128), (384, 128)
    q = _queue([a, b, a, b, a], [5, 1, 2, 3, 4])
    for s in range(5):
        q.push(s)
    assert q.pop_batch() == (b, [1, 3])  # front 1 leads: its class goes
    assert q.pop_batch() == (a, [2, 4])  # pow2 cut of 3: 2, 4 before 0
    assert q.pop_batch() == (a, [0])
    assert len(q) == 0


def test_ready_queue_pow2_cut_and_max_batch():
    shape = (256, 128)
    q = _queue([shape] * 13, range(13), max_batch=4)
    for s in reversed(range(13)):
        q.push(s)
    sizes = []
    while q:
        key, members = q.pop_batch()
        assert key == shape and members == sorted(members)
        sizes.append(len(members))
    assert sizes == [4, 4, 4, 1]
    q = _queue([shape] * 13, range(13))
    for s in range(13):
        q.push(s)
    assert [len(q.pop_batch()[1]) for _ in range(3)] == [8, 4, 1]


def test_ready_queue_one_front_past_vmem():
    big = (VMEM_FRONT_MAX + 128, 128)
    q = _queue([big] * 3, [2, 0, 1])
    for s in range(3):
        q.push(s)
    assert [q.pop_batch() for _ in range(3)] == [(big, [1]), (big, [2]), (big, [0])]


def test_ready_queue_push_back_and_count():
    a, b = (256, 128), (128, 128)
    q = _queue([a] * 6 + [b], [0, 1, 2, 3, 4, 5, 9])
    for s in range(7):
        q.push(s)
    assert len(q) == 7
    key, members = q.pop_batch()
    assert (key, members) == (a, [0, 1, 2, 3])
    assert len(q) == 3
    q.push(members.pop())  # shed the lowest priority under a memory cap
    q.push(members.pop())
    assert len(q) == 5
    # the shed fronts lead again, ahead of the rest of their class
    assert q.pop_batch() == (a, [2, 3, 4, 5])
    for s in members:  # a dispatch that could not launch hands all back
        q.push(s)
    assert q.pop_batch() == (a, [0, 1])
    assert q.pop_batch() == (b, [6])
    assert len(q) == 0 and not q


@pytest.mark.parametrize("seed", range(6))
def test_ready_queue_replays_regrouping(seed):
    """Random forests and classes (some past ``VMEM_FRONT_MAX``), ties in
    planned start, several dispatches in flight completing out of order,
    shedding and hand-backs: the heaps choose what the regrouping chose."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(50, 400))
    parent = [
        int(rng.integers(s + 1, n)) if s < n - 1 and rng.random() < 0.95 else -1
        for s in range(n)
    ]
    classes = [(256, 128), (384, 128), (256, 256), (128, 128),
               (VMEM_FRONT_MAX + 128, 128), (VMEM_FRONT_MAX + 512, 256)]
    pick = rng.integers(0, len(classes), n)
    shapes = [classes[i] for i in pick]
    starts = rng.integers(0, n // 4, n)  # ties broken by the front id
    max_batch = int(rng.choice([1, 2, 5, 32]))
    ndev = int(rng.choice([1, 2, 4]))
    prio = {s: (float(starts[s]), s) for s in range(n)}

    def replay(queue_cls):
        r = np.random.default_rng(seed + 1000)
        q = queue_cls(shapes, prio, max_batch)
        waiting = [0] * n
        for p in parent:
            if p >= 0:
                waiting[p] += 1
        for s in range(n):
            if waiting[s] == 0:
                q.push(s)
        in_flight, seq, dispatched = [], [], set()
        while len(dispatched) < n or in_flight:
            while q and len(in_flight) < ndev:
                key, members = q.pop_batch()
                cap = int(r.integers(1, 6))
                while len(members) > cap:
                    q.push(members.pop())
                if in_flight and r.random() < 0.2:
                    for s in members:
                        q.push(s)
                    break
                in_flight.append(members)
                dispatched.update(members)
                seq.append((key, tuple(members)))
            for s in in_flight.pop(int(r.integers(0, len(in_flight)))):
                p = parent[s]
                if p >= 0:
                    waiting[p] -= 1
                    if waiting[p] == 0:
                        q.push(p)
            assert len(q) == sum(
                1 for s in range(n) if waiting[s] == 0 and s not in dispatched
            )
        return seq

    assert replay(ReadyQueue) == replay(_RegroupingReadyList)


@pytest.fixture(scope="module")
def mixed_problem():
    """A forest with three padded shape classes: a random sparse SPD block
    (min-degree order; (128, 128) fronts) beside two dense 10-node leaves
    over a dense 130-node clique (one (256, 128) leaf front of 10 pivots
    and a 130-row border; one (256, 256) front of the clique and the
    other leaf)."""
    rng = np.random.default_rng(0)
    r = random_spd(120, 4.0, rng)
    r = permute_symmetric(r, min_degree(r))
    n = 150
    d = n * np.eye(n)
    for leaf in (range(0, 10), range(10, 20)):
        idx = np.r_[leaf, 20:n]
        g = rng.uniform(-1.0, 1.0, (idx.size, idx.size))
        d[np.ix_(idx, idx)] += (g + g.T) / 2
    ap = sp.block_diag([r, sp.csr_matrix(d)]).tocsr()
    symb = analyze(ap, relax=1)
    plan = make_plan(symb.task_tree(), 8, alpha=0.9)
    return ap, symb, plan


def _dispatches(report):
    """Per-dispatch member tuples, in dispatch order."""
    by_seq = {}
    for e in report.trace:
        by_seq.setdefault(e.wave, []).append(e.front)
    return [tuple(by_seq[k]) for k in sorted(by_seq)]


@pytest.mark.parametrize(
    "case, kw",
    [
        ("classes", {}),
        ("max_batch", {"max_batch": 2}),
        ("memory_cap", {"memory_cap_bytes": 3.0e6}),
    ],
)
def test_ready_queue_dispatches_as_regrouping(mixed_problem, monkeypatch, case, kw):
    """The executor with the heaps issues the dispatches, and returns the
    panels, of the executor with the regrouped ready list.  One device:
    one dispatch in flight, so the completion order, and with it the
    dispatch sequence, is deterministic."""
    ap, symb, plan = mixed_problem
    shapes = {
        executor_mod.padded_shape(sn.m, sn.nb) for sn in symb.supernodes
    }
    assert len(shapes) == 3

    def run():
        ex = PlanExecutor(
            symb, plan, devices=jax.devices()[:1], mode="async", **kw
        )
        return ex.run(ap, warmup=False)

    fq, rq = run()
    monkeypatch.setattr(executor_mod, "ReadyQueue", _RegroupingReadyList)
    fr, rr = run()
    got, want = _dispatches(rq), _dispatches(rr)
    assert got == want
    for pq, pr in zip(fq.panels, fr.panels):
        np.testing.assert_array_equal(pq, pr)
    sizes = [len(m) for m in got]
    if case == "max_batch":
        # a class wider than the cap leaves its remainder ready
        assert max(sizes) == 2 and sizes.count(2) > 1
    if case == "memory_cap":
        # shedding leaves batches the pow2 cut alone never makes
        assert any(k != pow2_floor(k) for k in sizes)
