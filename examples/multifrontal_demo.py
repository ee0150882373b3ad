"""The paper's application end-to-end through `repro.api`: matrix →
ordering → symbolic → PM plan → *executed* factorization on a JAX mesh →
‖LLᵀ−A‖ check.

For each matrix: tree stats, PM vs PROPORTIONAL/DIVISIBLE projected
makespans (§7), discretized plan efficiency — all policies resolved from
the same registry.  The first matrix is then actually factorized by the
malleable-plan executor (``Session.execute``): the PM plan's waves of
power-of-two device groups run the Pallas frontal kernels (interpret
mode on CPU), emitting a per-front trace and a measured-vs-projected
makespan report with an empirical α re-fit.  Fronts are f32 (what the
kernels run in on a TPU); the backward error ‖LLᵀ−A‖_F/‖A‖_F must stay
under ``TOL`` or the demo exits non-zero.

Run:  PYTHONPATH=src python examples/multifrontal_demo.py
(Forge a mesh: XLA_FLAGS=--xla_force_host_platform_device_count=8)
"""
import sys
import time

import jax
import numpy as np

from repro.api import DeviceMesh, Session
from repro.sparse import (
    grid_laplacian_2d,
    grid_laplacian_3d,
    min_degree,
    nested_dissection_2d,
    random_spd,
)

ALPHA = 0.9
TOL = 100 * float(np.finfo(np.float32).eps)  # ≈1.2e-5, f32 fronts


def demo(name, a, perm=None, ndev=256, execute=False):
    session = Session(DeviceMesh(plan_devices=ndev))
    t0 = time.time()
    session.analyze(a, alpha=ALPHA, ordering=perm)
    t_sym = time.time() - t0
    symb = session.problem.symb
    mk = {p: session.plan(policy=p).schedule.makespan
          for p in ("pm", "proportional", "divisible")}
    session.plan(policy="greedy")
    plan = session.schedule
    msg = (f"{name:14s} n={symb.n:6d} fronts={symb.n_supernodes:5d} "
           f"maxfront={max(s.m for s in symb.supernodes):4d} "
           f"| PM {mk['pm']:9.3g}"
           f"  PROP +{100*(mk['proportional']/mk['pm']-1):5.1f}%  "
           f"DIV +{100*(mk['divisible']/mk['pm']-1):6.1f}% "
           f"| plan eff {plan.efficiency():.2f} | symbolic {t_sym*1e3:.0f}ms")
    print(msg)
    if execute:
        run = session.execute()
        report = run.detail
        err = run.artifact.backward_error(session.problem.matrix)
        print(f"--- executed {name} (greedy PM plan, "
              f"{len(jax.devices())} device(s))")
        print("\n".join("    " + ln for ln in report.summary().splitlines()))
        print(f"    backward error ‖LLᵀ−A‖_F/‖A‖_F = {err:.2e}"
              f"  ({'OK' if err <= TOL else 'FAIL'}, tol {TOL:.1e})")
        return err <= TOL
    return True


def main() -> int:
    rng = np.random.default_rng(0)
    ok = demo("grid 23x23", grid_laplacian_2d(23), nested_dissection_2d(23),
              execute=True)
    demo("grid 41x41", grid_laplacian_2d(41), nested_dissection_2d(41))
    demo("grid 8x8x8", grid_laplacian_3d(8))
    a = random_spd(400, 5.0, rng)
    demo("rand-spd 400", a, min_degree(a))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
