#!/usr/bin/env python3
"""Smoke run of the multifrontal factorization on a TPU.

Drives the main path once through the entry points a user calls --
``Session(DeviceMesh()).analyze(...).plan(...).execute()`` and
``Session.serve(cluster=...)`` -- at sizes users would call real, in f32,
and checks the backward error ||LL^T - A||_F / ||A||_F of every
factorization.  One process, x64 off, no child processes.

Phases:
  a  device check: platform, kind, count and JAX version; no TPU -> exit 1
  b  executor, many small fronts: 2D 5-point Laplacian on a 255^2 grid
     with nested dissection (65,025 unknowns, 32,383 fronts), greedy
     plan, executed plain and again after ``.optimize()``
  c  large-front path: 3D 7-point Laplacian on a 24^3 grid with
     min_degree (13,824 unknowns, fronts padded up to 1,280; three pass
     VMEM_FRONT_MAX and take the panel + SYRK kernels)
  d  served: LocalCluster(2 workers, inproc) on the chip, 12 numeric
     requests from 3 tenants mixing 2D 63^2 and 3D 12^3 grids

``--chips 4`` runs phase b on a 4-chip DeviceMesh and the same plan on
one chip, and no other phase: the factors must match bit for bit, and
it prints how many dispatches each device ran.

Each phase prints one line ``phase <name> {json}``.  Set-up (ordering,
symbolic analysis, planning, warm-up compiles) is reported apart from
the factor time, which ends with ``block_until_ready``.
``compiles_in_window`` counts executables built (compiled, or loaded
from the persistent cache) inside the timed window.  When every phase
passed, the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
otherwise the script exits 1 and prints no such line.

Usage:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ALPHA = 0.9
EDGE_2D = 255
EDGE_3D = 24
# Backward-stable Cholesky in f32 leaves ||LL^T - A||_F / ||A||_F of a
# few eps_f32 times a slowly growing function of the front order; 100
# eps_f32 (1.2e-5) is ten times above that at these sizes, and a wrong
# entry in L shows up orders of magnitude higher.
TOL = 100 * float(np.finfo(np.float32).eps)
TOL_REASON = "100*eps_f32: f32 fronts, backward-stable partial Cholesky"
# The served phase's scheduler runs on the host and its cost grows with
# the fronts in flight (ROADMAP S7): with the kernels swapped for jnp
# references, this phase took about 3 minutes on a CPU host.
SERVE_TIMEOUT_S = 480.0


class CompileLog:
    """When each executable was built: a JAX monitoring listener on the
    backend-compile event (which also fires on persistent-cache loads)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.times: list = []

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


COMPILES = CompileLog()


def emit(name: str, **fields) -> None:
    print(f"phase {name} {json.dumps(fields)}", flush=True)


def device_info(jax):
    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def dispatches_per_device(report) -> dict:
    """Dispatches each device ran: a dispatch engages the union of its
    fronts' carved groups when it spans several devices, else the
    first device of that union."""
    groups: dict = {}
    for e in report.trace:
        lanes = range(e.device0, e.device0 + max(e.devices_used, 1))
        g = groups.setdefault(e.wave, [set(), e.dispatch_devices])
        g[0].update(lanes)
    out: dict = {}
    for lanes, width in groups.values():
        used = sorted(lanes) if width > 1 else [min(lanes)]
        for d in used:
            out[d] = out.get(d, 0) + 1
    return dict(sorted(out.items()))


def execute(sess, setup: dict) -> tuple:
    """Plan greedily unless ``sess`` already holds a schedule, execute,
    check; returns (run, fields)."""
    if sess.schedule is None:
        t = time.perf_counter()
        sess.plan(policy="greedy")
        setup["plan"] = time.perf_counter() - t
    t = time.perf_counter()
    run = sess.execute()
    wall = time.perf_counter() - t
    rep = run.detail
    factor_s = rep.measured_makespan
    setup["warmup_compile"] = wall - factor_s
    t = time.perf_counter()
    err = run.artifact.backward_error(sess.problem.matrix)
    fields = {
        "n": sess.problem.symb.n,
        "fronts": sess.problem.symb.n_supernodes,
        "tasks": sess.problem.n,
        "dispatches": rep.n_dispatches,
        "devices": rep.n_devices,
        "setup_s": setup,
        "factor_s": factor_s,
        "compiles_in_window": COMPILES.between(
            rep.t_origin, rep.t_origin + factor_s
        ),
        "interpret": rep.interpret,
        "backward_error": err,
        "tol": TOL,
        "check_s": time.perf_counter() - t,
    }
    fields["ok"] = (not rep.interpret) and err <= TOL
    return run, fields


def analyzed(devices, a, ordering, setup: dict):
    from repro.api import DeviceMesh, Session

    t = time.perf_counter()
    sess = Session(DeviceMesh(devices))
    sess.analyze(a, alpha=ALPHA, ordering=ordering)
    setup["symbolic"] = time.perf_counter() - t
    return sess


def grid_2d():
    from repro.sparse import grid_laplacian_2d, nested_dissection_2d

    a = grid_laplacian_2d(EDGE_2D)
    t = time.perf_counter()
    perm = nested_dissection_2d(EDGE_2D)
    return a, perm, time.perf_counter() - t


def phase_b(devices) -> bool:
    a, perm, t_order = grid_2d()
    ok = True
    for label, optimize in (("b-plain", False), ("b-optimized", True)):
        setup = {"ordering": t_order}
        sess = analyzed(devices, a, perm, setup)
        if optimize:
            t = time.perf_counter()
            sess.optimize()
            setup["optimize"] = time.perf_counter() - t
        _, fields = execute(sess, setup)
        emit(label, **fields)
        ok &= fields["ok"]
    return ok


def phase_c(devices) -> bool:
    from repro.kernels.frontal_cholesky import VMEM_FRONT_MAX
    from repro.kernels.ops import padded_shape
    from repro.sparse import grid_laplacian_3d, min_degree

    a = grid_laplacian_3d(EDGE_3D)
    t = time.perf_counter()
    perm = min_degree(a)
    setup = {"ordering": time.perf_counter() - t}
    sess = analyzed(devices, a, perm, setup)
    mps = [padded_shape(s.m, s.nb)[0] for s in sess.problem.symb.supernodes]
    _, fields = execute(sess, setup)
    fields["edge"] = EDGE_3D
    fields["max_padded_front"] = max(mps)
    fields["fronts_above_vmem_max"] = sum(m > VMEM_FRONT_MAX for m in mps)
    fields["ok"] &= fields["fronts_above_vmem_max"] > 0
    emit("c", **fields)
    return fields["ok"]


def phase_d(devices) -> bool:
    from repro.api import DeviceMesh, Problem, Session
    from repro.cluster import LocalCluster
    from repro.kernels.ops import should_interpret
    from repro.sparse import (
        grid_laplacian_2d,
        grid_laplacian_3d,
        min_degree,
        nested_dissection_2d,
    )

    t = time.perf_counter()
    a3 = grid_laplacian_3d(12)
    kinds = [
        Problem.from_matrix(
            grid_laplacian_2d(63), ALPHA, ordering=nested_dissection_2d(63)
        ),
        Problem.from_matrix(a3, ALPHA, ordering=min_degree(a3)),
    ]
    setup = {"ordering_symbolic": time.perf_counter() - t}
    stream = [(kinds[r % 2], 0.0, r % 3) for r in range(12)]
    # compiles run on worker threads; a long heartbeat timeout keeps a
    # compile from reading as a lost worker
    with LocalCluster(
        n_workers=2, scheme="inproc", heartbeat_timeout=120.0
    ) as cl:
        interpret = should_interpret(cl.workers[0].interpret)
        t0 = time.perf_counter()
        run = Session(DeviceMesh(devices)).serve(
            stream, cluster=cl, timeout=SERVE_TIMEOUT_S
        )
        t1 = time.perf_counter()
    results = run.detail["results"]
    t = time.perf_counter()
    errs = [
        r.factor.backward_error(stream[r.rid][0].matrix)
        for r in results
        if r.ok and r.factor is not None
    ]
    n_ok = sum(r.ok for r in results)
    fields = {
        "n": [p.symb.n for p in kinds],
        "fronts": [p.symb.n_supernodes for p in kinds],
        "requests": len(stream),
        "tenants": 3,
        "requests_ok": n_ok,
        "errors": sorted({r.error for r in results if not r.ok}),
        "dispatches": int(run.metrics["n_dispatches"]),
        "setup_s": setup,
        "serve_s": t1 - t0,
        "p50_latency_s": run.metrics.get("p50_latency"),
        "p99_latency_s": run.metrics.get("p99_latency"),
        "compiles_in_window": COMPILES.between(t0, t1),
        "interpret": interpret,
        "backward_error_max": max(errs, default=None),
        "tol": TOL,
        "check_s": time.perf_counter() - t,
    }
    fields["ok"] = (
        not interpret
        and n_ok == len(stream)
        and len(errs) == len(stream)
        and max(errs) <= TOL
    )
    emit("d", **fields)
    return fields["ok"]


def phase_b_four_chips(devices) -> bool:
    """Phase b on a 4-chip mesh, then the same schedule on one chip."""
    from repro.api import DeviceMesh, Session

    a, perm, t_order = grid_2d()
    ok = True
    for label, optimize in (("b4-plain", False), ("b4-optimized", True)):
        setup = {"ordering": t_order}
        sess4 = analyzed(devices[:4], a, perm, setup)
        if optimize:
            sess4.optimize()
        run4, fields = execute(sess4, setup)
        fields["dispatches_per_device"] = dispatches_per_device(run4.detail)
        emit(f"{label}-4chip", **fields)
        ok &= fields["ok"]
        sess1 = Session(DeviceMesh(devices[:1])).load(sess4.problem)
        sess1.schedule = sess4.schedule  # the 4-chip plan, rescaled to one
        run1, fields = execute(sess1, {})
        emit(f"{label}-1chip", **fields)
        ok &= fields["ok"]
        p4, p1 = run4.artifact.panels, run1.artifact.panels
        same = all(np.array_equal(x, y) for x, y in zip(p4, p1))
        diff = max(float(np.max(np.abs(x - y), initial=0.0)) for x, y in zip(p4, p1))
        emit(f"{label}-compare", bit_identical=same, max_abs_diff=diff)
        ok &= same
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: phase b on a 4-chip mesh against one chip, nothing else",
    )
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", False)
    info = device_info(jax)
    print(
        f"device platform={info['platform']} kind={info['kind']} "
        f"count={info['count']} jax={jax.__version__}",
        flush=True,
    )
    if info["platform"] != "tpu":
        print("FAIL: no TPU found; this smoke has no CPU path", file=sys.stderr)
        return 1
    if info["count"] < args.chips:
        print(f"FAIL: {args.chips} chips asked, {info['count']} found",
              file=sys.stderr)
        return 1
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"FAIL: the repro package is not importable: {e}",
              file=sys.stderr)
        return 1

    print(f"tolerance {TOL:.3e} ({TOL_REASON})", flush=True)
    print(f"compile cache {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(COMPILES)

    devices = jax.devices()
    if args.chips == 4:
        phases = [("b4", phase_b_four_chips)]
    else:
        devices = devices[:1]
        phases = [("b", phase_b), ("c", phase_c), ("d", phase_d)]
    ok = True
    for name, fn in phases:
        t = time.perf_counter()
        try:
            passed = fn(devices)
        except Exception:
            traceback.print_exc()
            passed = False
        print(f"phase {name} {'passed' if passed else 'FAILED'} "
              f"in {time.perf_counter() - t:.1f}s", flush=True)
        ok &= passed
    if not ok:
        print("FAIL: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
