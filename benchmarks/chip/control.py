#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's, and the control's.

Runs on a TPU only, at the cell's own size.  In one process it sets the
cell up once, then for each ``--seeds`` runs the cell's window for
``--seconds`` and checks it as a benchmark run does, printing the number
each check compared.  Then it puts the control in the program's place and
does the same for ``--control-seeds``.

The configurations state float32 fronts with the kernels' contractions at
``Precision.HIGHEST``.  The control is the nearest precision below that:
every front factored by a plain JAX partial Cholesky put in the place of
the program's kernels
(``batched_front_factor`` and ``partial_cholesky``), whose Schur
complement update ``A22 - L21 L21^T`` -- the bulk of the flops -- runs at
``Precision.HIGH``, three bf16 passes.  The kernels themselves cannot take
``HIGH``: Mosaic refuses it on a v5e (``NotImplementedError: Unsupported
dot precision: HIGH``).  The control has to come out as not correct; the
limit lies between the two readings (PERF.md).

    python benchmarks/chip/control.py --workload laplace3d-nd.refactor \\
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 51

Each reading is one line ``reading {json}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as bench  # noqa: E402


def reference_factor(a, nb: int, precision):
    """Partial Cholesky of one symmetric front ``a`` (m x m) on its first
    ``nb`` pivots: (L11 over L21, and A22 - L21 L21^T).  The pivot block
    and the triangular solve run at full f32 precision, the update at
    ``precision``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        l11 = jnp.linalg.cholesky(a[:nb, :nb])
        l21 = jax.scipy.linalg.solve_triangular(l11, a[nb:, :nb].T, lower=True).T
    schur = a[nb:, nb:] - jnp.dot(l21, l21.T, precision=precision)
    return jnp.concatenate([l11, l21], axis=0), schur


def install_reference() -> None:
    """Put the reference front factor, its update at ``Precision.HIGH``,
    in the place of the program's kernels, and drop every compiled
    program."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.runtime import executor

    precision = jax.lax.Precision.HIGH

    @partial(jax.jit, static_argnames=("nbp",))
    def batched(fronts, nbp):
        def one(f):
            panel, schur = reference_factor(f, nbp, precision)
            return f.at[:, :nbp].set(panel).at[nbp:, nbp:].set(schur)

        return jax.vmap(one)(fronts)

    @partial(jax.jit, static_argnames=("nb",))
    def large(front, nb):
        return reference_factor(front, nb, precision)

    def batched_front_factor(fronts, nbp, interpret=None, mesh=None):
        return batched(jnp.asarray(fronts), nbp)

    def partial_cholesky(front, nb, interpret=None):
        return large(jnp.asarray(front), nb)

    for mod in (ops, executor):
        mod.batched_front_factor = batched_front_factor
        mod.partial_cholesky = partial_cholesky
    jax.clear_caches()


def readings(run, driver, st, seeds, label: str) -> list:
    out = []
    for seed in seeds:
        run.seed = seed
        run.checks, run.layer["backward_errors"] = [], []
        run.e2e = {}
        st.reset()
        driver.window(run, st)
        driver.verify(run, st)
        row = {
            "label": label,
            "seed": seed,
            "checks": {n: v for n, v, _, _ in run.checks},
            "passed": all(ok for *_, ok in run.checks),
            "errors": run.layer["backward_errors"],
            "e2e": run.e2e,
        }
        print("reading " + json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]

    b = bench.Benchmark()
    run = bench.Run(b, args.workload, (seeds or cseeds)[0], args.seconds, False)
    sys.path.insert(0, str(bench.ROOT / "src"))
    import jax

    run.devices = bench.require_chips(jax, run.chips)
    print(f"compile cache {bench.enable_cache(jax)}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(run.on_compile)
    driver = bench.load_module(b.driver_path(run.traffic), "driver")
    t = time.perf_counter()
    st = driver.prepare(run)
    keep_open = getattr(st, "close", None)
    try:
        driver.warm(run, st)
        print(f"setup {json.dumps(run.setup)} in "
              f"{time.perf_counter() - t:.1f}s", flush=True)
        readings(run, driver, st, seeds, "program")
        if cseeds:
            install_reference()
            t = time.perf_counter()
            driver.warm(run, st)
            print(f"control warm-up {time.perf_counter() - t:.1f}s", flush=True)
            readings(run, driver, st, cseeds, "control-high")
    finally:
        if keep_open is not None:
            keep_open()
    return 0


if __name__ == "__main__":
    sys.exit(main())
