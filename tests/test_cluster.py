"""Persistent serving cluster: comm codec/framing/faults, heartbeat loss
and rejoin (the Theorem-6 capacity path), worker death mid-front with
bit-identical factors, scheduler checkpoint/restore with queued tenants,
cross-tenant continuous batching, and clean drain/shutdown on both the
inproc and TCP backends."""
import threading
import time

import numpy as np
import pytest

from repro.api.problem import Problem
from repro.cluster import (
    ClusterClient,
    ClusterScheduler,
    CommError,
    FaultInjector,
    LocalCluster,
    RetryPolicy,
    Worker,
    connect,
    decode,
    encode,
    leaked_threads,
    listen,
    open_socket_count,
)

ALPHA = 0.9

# Sim-mode knobs: fast virtual work, heartbeats quick enough that a
# kill is noticed inside the test budget but slow enough not to flake.
FAST = dict(tick=0.002, work_rate=200.0)
HB = dict(heartbeat_interval=0.03, heartbeat_timeout=0.2)


def _trees(rng, n, tasks=3):
    return [
        Problem.from_lengths(rng.uniform(0.5, 2.0, size=tasks), ALPHA)
        for _ in range(n)
    ]


def _wait(pred, timeout=20.0, tick=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(tick)
    return pred()


def _grid_problem():
    """A matrix whose elimination tree is an actual tree (min_degree on
    the 8x8 Poisson grid gives ~45 supernodes; natural order collapses
    to one)."""
    from repro.sparse import grid_laplacian_2d, min_degree

    return Problem.from_matrix(
        grid_laplacian_2d(8, 8), ALPHA, ordering=min_degree
    )


# ----------------------------------------------------------------------
# Comm layer: codec, framing, faults, retry
# ----------------------------------------------------------------------
def test_codec_roundtrip_ndarray_bit_exact(rng):
    """ndarrays survive the wire envelope bit-for-bit (raw bytes, not
    repr) — the transport must not be able to perturb factors."""
    for dtype in (np.float64, np.float32, np.int32):
        a = rng.standard_normal((7, 5)).astype(dtype)
        msg = {"op": "x", "a": a, "nested": {"b": [a[0], "s", 3]}}
        out = decode(encode(msg))
        assert out["a"].dtype == a.dtype
        assert out["a"].tobytes() == a.tobytes()
        assert out["nested"]["b"][0].tobytes() == a[0].tobytes()
        assert out["nested"]["b"][1:] == ["s", 3]


def test_codec_pickle_fallback_for_problems(rng):
    p = _trees(rng, 1)[0]
    q = decode(encode({"problem": p}))["problem"]
    assert np.allclose(q.tree.lengths, p.tree.lengths)
    assert q.alpha == p.alpha


@pytest.mark.parametrize("scheme", ["inproc", "tcp"])
def test_comm_roundtrip_and_close(scheme):
    address = f"{scheme}://{'comm-rt' if scheme == 'inproc' else '127.0.0.1:0'}"
    got = []

    def serve(comm):
        while True:
            msg = comm.recv(timeout=1.0)
            if msg is None:
                continue
            got.append(msg)
            if msg.get("op") == "bye":
                return
            comm.send({"echo": msg["n"] * 2})

    # The handler contract: return promptly, hand long-lived streams to
    # their own thread (what ClusterScheduler's reader registry does).
    def handler(comm):
        threading.Thread(target=serve, args=(comm,), daemon=True).start()

    listener = listen(address, handler)
    try:
        comm = connect(listener.address)
        for n in range(3):
            comm.send({"op": "ping", "n": n})
            assert comm.recv(timeout=2.0) == {"echo": n * 2}
        comm.send({"op": "bye"})
        # The server thread drains asynchronously; wait for the bye.
        assert _wait(lambda: len(got) == 4, timeout=5.0)
        comm.close()
    finally:
        listener.close()
    assert [m["op"] for m in got] == ["ping", "ping", "ping", "bye"]


def test_fault_injector_drop_and_fail():
    faults = FaultInjector()
    faults.drop(2, op="heartbeat")
    faults.fail(1, op="data")
    assert faults.check({"op": "heartbeat"}) == "drop"
    assert faults.check({"op": "heartbeat"}) == "drop"
    assert faults.check({"op": "heartbeat"}) == "ok"  # budget spent
    assert faults.check({"op": "other"}) == "ok"  # op filter holds
    assert faults.check({"op": "data"}) == "fail"
    assert faults.dropped == 2 and faults.failed == 1


def test_connect_retry_backoff_exhaustion():
    """No listener: connect retries with exponential backoff then raises
    CommError naming the attempt count (satellite: retry exhaustion)."""
    t0 = time.perf_counter()
    with pytest.raises(CommError, match="after 3 attempts"):
        connect(
            "inproc://nobody-listening",
            retry=RetryPolicy(retries=2, backoff=0.02, factor=2.0),
        )
    # 2 retries => sleeps of ~0.02 + 0.04 between the 3 attempts.
    assert time.perf_counter() - t0 >= 0.05


# ----------------------------------------------------------------------
# Cluster end-to-end (sim mode): serve, batch, drain clean
# ----------------------------------------------------------------------
def test_inproc_cluster_serves_multi_tenant_stream(rng):
    with LocalCluster(n_workers=2, slots_per_worker=2, **FAST, **HB) as cl:
        client = cl.client()
        futs = [
            client.submit(p, tenant=i % 3, rid=i)
            for i, p in enumerate(_trees(rng, 9))
        ]
        results = client.gather(futs, timeout=30.0)
        assert all(r.ok for r in results)
        assert sorted(r.rid for r in results) == list(range(9))
        assert {r.tenant for r in results} == {0, 1, 2}
        # The latency split is carried per request (satellite 2).
        assert all(r.wait >= 0.0 and r.exec_time > 0.0 for r in results)
        stats = cl.scheduler.stats()
        assert stats["n_done"] == 9 and stats["n_reshares"] >= 1
        cl.drain()
    assert leaked_threads() == []


def test_qos_weights_skew_fair_admission_toward_heavy_tenant():
    """Weighted fair share end-to-end: with tenant 0 weighing 4x, its
    backlog is admitted ~4x as often, so its mean wait drops below the
    equal-weight tenant's on the same one-at-a-time pool."""
    with LocalCluster(
        n_workers=1,
        slots_per_worker=1,
        admission="fair",
        max_concurrent=1,
        qos_weights={0: 4.0, 1: 1.0},
        **FAST,
        **HB,
    ) as cl:
        client = cl.client()
        futs = [
            client.submit(
                Problem.from_lengths([1.0, 1.0, 1.0], ALPHA),
                tenant=i % 2,
                rid=i,
            )
            for i in range(8)
        ]
        results = client.gather(futs, timeout=60.0)
        assert all(r.ok for r in results)
        wait = {
            t: np.mean([r.wait for r in results if r.tenant == t])
            for t in (0, 1)
        }
        assert wait[0] < wait[1]
        cl.drain()
    assert leaked_threads() == []


def test_cross_tenant_batching_merges_fronts(rng):
    """Same-shape ready fronts from *different tenants* ride one
    dispatch (continuous batching), and turning batching off forbids
    it."""
    def run(batching):
        with LocalCluster(
            n_workers=1, slots_per_worker=4, batching=batching, **FAST, **HB
        ) as cl:
            client = cl.client()
            futs = [
                client.submit(p, tenant=i, rid=i)
                for i, p in enumerate(_trees(rng, 6, tasks=2))
            ]
            assert all(r.ok for r in client.gather(futs, timeout=30.0))
            return cl.scheduler.stats()["n_dispatches"], list(
                cl.scheduler.batch_tenant_mix
            )

    n_batched, mix = run(True)
    n_single, _ = run(False)
    assert n_batched < n_single  # batching coalesces dispatches
    assert any(n > 1 for n in mix)  # and some batches cross tenants


def test_tcp_cluster_end_to_end(rng):
    """The same protocol over real sockets: length-prefixed frames,
    ndarray envelopes, clean socket teardown."""
    with LocalCluster(n_workers=2, scheme="tcp", **FAST, **HB) as cl:
        assert cl.scheduler.address.startswith("tcp://127.0.0.1:")
        client = cl.client()
        futs = [
            client.submit(p, tenant=i % 2, rid=i)
            for i, p in enumerate(_trees(rng, 6))
        ]
        assert all(r.ok for r in client.gather(futs, timeout=30.0))
        cl.drain()
    assert _wait(lambda: open_socket_count(cl) == 0, timeout=5.0)
    assert leaked_threads() == []


# ----------------------------------------------------------------------
# Failure paths: heartbeats, worker death, restart
# ----------------------------------------------------------------------
def test_dropped_heartbeats_mark_worker_dead_then_rejoin():
    """Drop enough heartbeats and the failure detector fires a capacity
    event (Theorem 6: work-time inversion under p(t) change); a late
    heartbeat re-admits the worker with a second capacity event."""
    sched = ClusterScheduler(
        "inproc://hb-drop", heartbeat_timeout=0.15, tick=0.002
    )
    w = Worker("inproc://hb-drop", slots=2, heartbeat_interval=0.03)
    faults = w.comm.faults
    try:
        assert _wait(lambda: sched.total_slots() == 2, timeout=5.0)
        faults.drop(50, op="heartbeat")
        assert _wait(lambda: sched.stats()["n_worker_losses"] == 1, 10.0)
        assert sched.total_slots() == 0
        # Faults exhausted -> heartbeats flow again -> rejoin.
        assert _wait(lambda: sched.total_slots() == 2, timeout=10.0)
        assert sched.stats()["n_capacity_events"] >= 2
        assert faults.dropped == 50
    finally:
        w.stop()
        sched.stop()
    assert leaked_threads() == []


def test_worker_killed_mid_front_requeues_and_reshares(rng):
    """Kill a worker holding in-flight fronts: its batches requeue, the
    Lemma-4 re-share runs on the shrunk pool (elastic capacity event),
    and every tree still completes."""
    with LocalCluster(
        n_workers=2,
        slots_per_worker=2,
        tick=0.002,
        work_rate=10.0,
        heartbeat_interval=0.03,
        heartbeat_timeout=0.12,
    ) as cl:
        client = cl.client()
        futs = [
            client.submit(p, tenant=i % 2, rid=i)
            for i, p in enumerate(_trees(rng, 8, tasks=4))
        ]
        _wait(lambda: cl.scheduler.stats()["n_dispatches"] >= 2, timeout=10.0)
        cl.workers[0].kill()
        results = client.gather(futs, timeout=60.0)
        assert all(r.ok for r in results)
        stats = cl.scheduler.stats()
        assert stats["n_worker_losses"] >= 1
        assert stats["n_requeued"] >= 1
        # The elastic controller saw the pool shrink 4 -> 2.
        devices = [d for _, d in cl.scheduler.capacity_steps]
        assert devices[-1] == 2 and 4 in devices
    assert leaked_threads() == []


def test_scheduler_restart_resumes_queued_tenants(rng):
    """checkpoint() on a scheduler with a backlog and restore() into a
    fresh one: every queued tenant's tree is served after the restart."""
    sched = ClusterScheduler("inproc://restart-a", **FAST)
    client = ClusterClient("inproc://restart-a")
    for i, p in enumerate(_trees(rng, 5)):
        client.submit(p, tenant=i % 2, rid=i)
    _wait(lambda: sched.stats()["n_pending"] + sched.stats()["n_admitted"] == 5)
    sched.stop()  # no worker ever joined: all five are still queued
    state = sched.checkpoint()
    client.close()
    assert len(state) == 5

    sched2 = ClusterScheduler("inproc://restart-b", **FAST)
    sched2.restore(state)
    w = Worker("inproc://restart-b", slots=2, heartbeat_interval=0.03)
    try:
        assert _wait(lambda: len(sched2.records) == 5, timeout=30.0)
        assert sorted(r.rid for r in sched2.records) == list(range(5))
        assert {r.tenant for r in sched2.records} == {0, 1}
    finally:
        w.stop()
        sched2.stop()
    assert leaked_threads() == []


def test_client_futures_fail_on_scheduler_loss(rng):
    """Scheduler dies with requests in flight: pending futures resolve
    ok=False instead of hanging the client forever."""
    sched = ClusterScheduler("inproc://dies", tick=0.002)
    client = ClusterClient("inproc://dies")
    futs = [client.submit(p, rid=i) for i, p in enumerate(_trees(
        np.random.default_rng(0), 3))]
    sched.stop()
    results = client.gather(futs, timeout=10.0)
    assert all(not r.ok for r in results)
    assert any("lost" in (r.error or "") for r in results)
    client.close()


# ----------------------------------------------------------------------
# Numeric mode: factors bit-identical to the single-process path
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_numeric_cluster_factors_bit_identical():
    """Three tenants submit the same sparse problem; the cluster's
    vmapped, cross-tenant-batched factors match the single-process
    PlanExecutor path bit for bit (acceptance criterion)."""
    from repro.api.platform import SharedMemory
    from repro.api.session import Session

    prob = _grid_problem()
    ref = (
        Session(SharedMemory(4))
        .load(prob)
        .plan("greedy")
        .execute()
        .artifact.to_dense_l()
    )
    with LocalCluster(
        n_workers=2,
        slots_per_worker=2,
        tick=0.002,
        heartbeat_interval=0.03,
        heartbeat_timeout=10.0,  # kernel compile stalls are not deaths
    ) as cl:
        client = cl.client()
        futs = [client.submit(prob, tenant=t, rid=t) for t in range(3)]
        results = client.gather(futs, timeout=300.0)
        assert all(r.ok for r in results)
        for r in results:
            assert r.factor is not None
            assert np.array_equal(r.factor.to_dense_l(), ref)
    assert leaked_threads() == []


@pytest.mark.slow
def test_numeric_worker_kill_factors_survive():
    """Kill a worker mid-factorization: requeued fronts re-execute on
    the survivor and the factor is still bit-identical (determinism is
    a property of the assembly order, not the dispatch history)."""
    from repro.api.platform import SharedMemory
    from repro.api.session import Session

    prob = _grid_problem()
    ref = (
        Session(SharedMemory(4))
        .load(prob)
        .plan("greedy")
        .execute()
        .artifact.to_dense_l()
    )
    with LocalCluster(
        n_workers=2,
        slots_per_worker=2,
        tick=0.002,
        heartbeat_interval=0.03,
        heartbeat_timeout=0.2,
        dispatch_overhead_s=0.05,  # keep fronts in flight long enough
    ) as cl:
        client = cl.client()
        futs = [client.submit(prob, tenant=t, rid=t) for t in range(2)]
        _wait(lambda: cl.scheduler.stats()["n_dispatches"] >= 1, timeout=60.0)
        cl.workers[1].kill()
        results = client.gather(futs, timeout=300.0)
        assert all(r.ok for r in results)
        assert cl.scheduler.stats()["n_worker_losses"] >= 1
        for r in results:
            assert np.array_equal(r.factor.to_dense_l(), ref)
    assert leaked_threads() == []


def test_failing_kernel_resolves_future_with_worker_error(rng, monkeypatch):
    """A kernel that keeps raising on the worker (a refused compile, say)
    is retried a bounded number of times; then the request's future
    resolves ok=False with the worker's error text instead of being
    requeued forever, and other tenants' trees still complete."""
    import repro.kernels.ops as ops

    def refuse(*args, **kwargs):
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setattr(ops, "batched_front_factor", refuse)
    monkeypatch.setattr(ops, "partial_cholesky", refuse)
    with LocalCluster(n_workers=2, **FAST, **HB) as cl:
        client = cl.client()
        bad = client.submit(_grid_problem(), tenant=0, rid=0)
        good = client.submit(_trees(rng, 1)[0], tenant=1, rid=1)
        r_bad, r_good = client.gather([bad, good], timeout=60.0)
        assert not r_bad.ok
        assert "RuntimeError: kernel refused by the compiler" in r_bad.error
        assert f"failed {ClusterScheduler.MAX_RETRIES + 1} times" in r_bad.error
        assert r_good.ok
        stats = cl.scheduler.stats()
        assert stats["n_failed"] == 1
        assert stats["n_admitted"] == 0
    assert leaked_threads() == []


# ----------------------------------------------------------------------
# Session facade
# ----------------------------------------------------------------------
def test_session_serve_cluster_report(rng):
    """Session.serve(cluster=...) returns a served RunReport whose
    schedule spans reconstruct the dispatch history and whose metrics
    carry the QPS/latency split."""
    from repro.api.platform import SharedMemory
    from repro.api.session import Session
    from repro.online import poisson_arrivals

    trees = _trees(rng, 6)
    arrivals = poisson_arrivals(len(trees), 4.0, rng)
    stream = [
        (p, float(a), i % 2)
        for i, (p, a) in enumerate(zip(trees, arrivals))
    ]
    with Session(SharedMemory(4)) as sess:
        with LocalCluster(n_workers=2, slots_per_worker=2, **FAST, **HB) as cl:
            report = sess.serve(stream, cluster=cl)
    assert report.kind == "served"
    assert report.metrics["n_requests"] == 6
    assert report.metrics["n_failed"] == 0
    assert report.metrics["qps"] > 0
    assert report.metrics["p99_latency"] >= report.metrics["p50_latency"] > 0
    assert report.schedule is not None and len(report.schedule.entries) > 0
    assert report.schedule.policy == "cluster-pm"
    assert leaked_threads() == []


def test_session_serve_dashboard_lifecycle(rng):
    """Repeated serve(dashboard_port=0) must not collide on ports, and
    closing the session tears the dashboard down (satellite 6)."""
    from repro.api.platform import SharedMemory
    from repro.api.session import Session

    stream = [(p, 0.0, 0) for p in _trees(rng, 2)]
    sess = Session(SharedMemory(2))
    try:
        for _ in range(2):  # second serve reuses no stale server/port
            report = sess.serve(stream, cluster=1, dashboard_port=0)
            assert report.metrics["n_failed"] == 0
    finally:
        sess.close()
    live = [t.name for t in threading.enumerate() if "dashboard" in t.name]
    assert live == []
    assert leaked_threads() == []
