"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run on the CPU (interpret-mode kernels) at a test size: a 15^2 grid for
the 2D refactorization, 9^2 requests for the served cell and 6^3 for the
3D cell.
The fault is planted in the program's batched front kernel, the path
every factorization of these sizes takes:

* ``unchanged``: the kernel returns its fronts as it got them;
* ``half_batch``: only the first half of a batch's lanes is factored;
* ``altered``: one entry of the first factored front is changed.

The cells run on one chip, so there is no exchange between chips to
leave out.  A sound run of each cell comes out correct.

    python -m pytest benchmarks/chip/test_faults.py
"""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run as bench  # noqa: E402

CELLS = sorted(bench.Benchmark().workloads)
FAULTS = ("unchanged", "half_batch", "altered")


def tiny_run(cell: str) -> "bench.Run":
    r = bench.Run(bench.Benchmark(), cell, 2**31 + 7, 1.0, False)
    if "serve" in r.cfg:
        r.cfg["matrix"]["grid"] = [9, 9]
        r.cfg["serve"]["max_batch"] = 4
    else:
        r.cfg["matrix"]["grid"] = [6, 6, 6] if "3d" in cell else [15, 15]
    return r


def broken(fault: str, real):
    def kernel(fronts, nbp, interpret=None, mesh=None):
        import jax.numpy as jnp

        x = np.asarray(fronts)
        if fault == "unchanged":
            return jnp.asarray(x)
        out = np.array(real(fronts, nbp, interpret, mesh=mesh))
        if fault == "half_batch":
            half = (x.shape[0] + 1) // 2
            out[half:] = x[half:]
        elif fault == "altered":
            out[0, 0, 0] += 1.0
        return jnp.asarray(out)

    return kernel


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = bench.execute(tiny_run(cell), need_chip=False)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_run_is_not_correct(cell, fault, monkeypatch):
    import repro.kernels.ops as ops
    import repro.runtime.executor as executor

    fake = broken(fault, ops.batched_front_factor)
    monkeypatch.setattr(ops, "batched_front_factor", fake)
    monkeypatch.setattr(executor, "batched_front_factor", fake)
    out = bench.execute(tiny_run(cell), need_chip=False)
    assert out["attempted"] > 0
    assert not out["correct"], out["checks"]
    assert out["checks"]["backward_error_max"]["value"] > \
        out["checks"]["backward_error_max"]["limit"]
