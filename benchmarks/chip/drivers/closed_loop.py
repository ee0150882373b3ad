"""Closed-loop factorization service: clients that each wait for their
factor before they send the next request.

The user is a set of simulations, grouped by tenant, that share one
factorization service and each need their factor before the next step.
The service is the program's cluster: ``LocalCluster`` (scheduler and
workers in this process, sizes from the configuration's ``serve`` block)
on the cell's chip.  Every request refactors the configuration's pattern
(analyzed once in set-up) with new link weights drawn from
``(seed, request index)``, and goes through the cluster client's
``submit`` and its future: frame codec, admission, re-share, cross-tenant
batching, the worker's kernels and the reply.

Set-up analyzes the pattern, starts the cluster, compiles every batch
shape the scheduler can form for the pattern (powers of two up to
``max_batch`` for each padded front class) and serves the traffic's
``warm_requests``.  In the window each client keeps one request outstanding
until ``--seconds`` have passed; requests in flight are then drained.
``serve_rps`` is the requests completed over the time from the window's
start to the last completion.  Every served request is checked.
"""
from __future__ import annotations

import threading
import time
import traceback
from dataclasses import replace

import numpy as np


class State:
    def __init__(self, mats, problem, cluster) -> None:
        self.mats = mats  # deployment.Matrices
        self.problem = problem
        self.cluster = cluster
        self.clients: list = []
        self.done: list = []  # (index, tenant, t_submit, t_done, TreeResult)
        self.lost: list = []  # (index, tenant, error): never answered

    def values(self, seed: int, k: int):
        return self.mats.values(seed, k)

    def reset(self) -> None:
        """Forget the window's requests (a new window on the same set-up)."""
        self.done, self.lost = [], []

    def request(self, seed: int, k: int):
        return replace(self.problem, matrix=self.values(seed, k))

    def close(self) -> None:
        for c in self.clients:
            c.close()
        self.clients = []
        if self.cluster is not None:
            self.cluster.stop()
            self.cluster = None


def prepare(run) -> State:
    from repro.api import Problem
    from repro.cluster import LocalCluster

    cfg, tr = run.cfg, run.traffic
    mats = run.deployment.Matrices(run.family, cfg)
    with run.timed("generate_s"):
        a = mats.first(run.seed)
    with run.timed("ordering_s"):
        perm = mats.order(a)
    with run.timed("analyze_s"):
        problem = Problem.from_matrix(a, cfg["alpha"], ordering=perm,
                                      relax=cfg["symbolic"]["relax"])
    sv = cfg["serve"]
    with run.timed("cluster_start_s"):
        cluster = LocalCluster(
            n_workers=sv["workers"], slots_per_worker=sv["slots_per_worker"],
            scheme=sv["scheme"], heartbeat_timeout=sv["heartbeat_timeout_s"],
            max_batch=sv["max_batch"],
        )
    st = State(mats, problem, cluster)
    st.clients = [
        cluster.client(f"tenant{t}-client{c}")
        for t, n in enumerate(tr["clients_per_tenant"]) for c in range(n)
    ]
    run.layer["fronts"] = [(sn.m, sn.nb) for sn in problem.symb.supernodes]
    return st


def warm(run, st: State) -> None:
    """Compile every batch shape the scheduler can form for the pattern,
    then serve ``warm_requests`` requests (which compile or load what
    else a request runs: the worker's assembly and the codec), all
    outside the window."""
    import jax.numpy as jnp

    from repro.kernels.frontal_cholesky import VMEM_FRONT_MAX
    from repro.kernels.ops import (batched_front_factor, padded_shape,
                                   partial_cholesky)

    max_batch = run.cfg["serve"]["max_batch"]
    with run.timed("warmup_s"):
        classes = sorted({padded_shape(m, nb) for m, nb in run.layer["fronts"]})
        for mp, nbp in classes:
            if mp > VMEM_FRONT_MAX:
                continue
            b = 1
            while b <= max_batch:
                eye = np.broadcast_to(np.eye(mp, dtype=np.float32), (b, mp, mp))
                batched_front_factor(jnp.asarray(eye), nbp).block_until_ready()
                b *= 2
        for m, nb in run.layer["fronts"]:
            if padded_shape(m, nb)[0] > VMEM_FRONT_MAX:
                eye = jnp.eye(m, dtype=jnp.float32)
                [x.block_until_ready() for x in partial_cholesky(eye, nb)]
        warm_k = run.deployment.WARM
        futs = [
            st.clients[0].submit(st.request(run.seed, warm_k + i), tenant=0)
            for i in range(run.traffic["warm_requests"])
        ]
        for f in futs:
            r = f.result(timeout=run.traffic["result_wait_s"] + 300.0)
            if not r.ok:
                raise RuntimeError(f"warm request failed: {r.error}")


def window(run, st: State) -> None:
    tr = run.traffic
    tenants = [t for t, n in enumerate(tr["clients_per_tenant"])
               for _ in range(n)]
    lock = threading.Lock()
    counter = iter(range(1, 1 << 62))

    def client(ci: int) -> None:
        try:
            loop(ci)
        except Exception:  # a client thread's boundary: record, stop
            traceback.print_exc()
            with lock:
                st.lost.append((-1, tenants[ci], "client failed"))

    def loop(ci: int) -> None:
        cl, tenant = st.clients[ci], tenants[ci]
        while run.elapsed() < run.seconds:
            with lock:
                k = next(counter)
            t_sub = time.perf_counter()
            with run.span("bench.request", k=k, tenant=tenant):
                fut = cl.submit(st.request(run.seed, k), tenant=tenant, rid=k)
                # wait past the close for a request in flight, then call
                # it lost
                wait = run.seconds - run.elapsed() + tr["result_wait_s"]
                try:
                    res = fut.result(timeout=max(wait, 0.0))
                except TimeoutError as e:
                    with lock:
                        st.lost.append((k, tenant, str(e)))
                    return
            t_done = time.perf_counter()
            with lock:
                st.done.append((k, tenant, t_sub, t_done, res))

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"bench-client-{i}")
               for i in range(len(st.clients))]
    with run.window():
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    t0 = run.t_window[0]
    ok = [d for d in st.done if d[4].ok]
    t_last = max((d[3] for d in ok), default=run.t_window[1])
    run.e2e["serve_rps"] = len(ok) / (t_last - t0)
    run.layer["latencies"] = [d[3] - d[2] for d in ok]
    run.layer["waits"] = [d[4].wait for d in ok]
    run.layer["requests"] = len(st.done) + len(st.lost)
    run.layer["compiles_in_window"] = run.compiles_in_window()


def measure(run) -> State:
    st = prepare(run)
    try:
        warm(run, st)
        window(run, st)
    finally:
        st.close()
    return st


def verify(run, st: State) -> None:
    """Every request of the window: answered, ok, and its factor checked
    against the reference."""
    ref = run.reference
    symb = st.problem.symb
    layout = ref.SupernodalLayout(
        symb.n, [sn.rows for sn in symb.supernodes],
        [sn.cols for sn in symb.supernodes],
    )
    x = ref.probes(symb.n, run.seed)
    limit = run.cfg["guarantee"]["backward_error_max"]
    errs = []
    for k, _, _, _, res in st.done:
        if not res.ok or res.factor is None:
            errs.append(float("inf"))
            continue
        try:
            errs.append(ref.backward_error(
                layout.l_matrix(res.factor.panels), st.values(run.seed, k), x))
        except ValueError:
            errs.append(float("inf"))
    run.attempted = len(st.done) + len(st.lost)
    run.failed = sum(1 for e in errs if not e <= limit) + len(st.lost)
    run.layer["backward_errors"] = errs
    run.check("backward_error_max", max(errs, default=float("inf")), limit)
    run.check("requests_lost", len(st.lost), 0)
