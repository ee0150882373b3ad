"""The control comes out not correct (on a TPU only), and reaches every
front (on the CPU).

For each cell, ``control.py`` sets the cell up at its own size, runs and
checks one short window with the program as it is, then puts the
control in the program's place (a plain JAX partial Cholesky whose Schur
update runs at ``Precision.HIGH``, the nearest precision below the
configurations' HIGHEST) and runs and checks one more.  The first has to
pass every check and the second to fail one.  Without a TPU the control
cannot fail (the CPU ignores the precision), so that test skips there;
the CPU test checks that the control's factor reaches every front.

    python -m pytest benchmarks/chip/test_control.py      # on the chip
"""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench  # noqa: E402


@pytest.fixture(scope="module")
def on_tpu():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control runs on a TPU only")
    return True


@pytest.mark.parametrize("cell", sorted(bench.Benchmark().workloads))
def test_control_fails_and_program_passes(cell, on_tpu, capsys):
    import control

    assert control.main(["--workload", cell, "--seeds", "1",
                         "--control-seeds", "2", "--seconds", "0.1"]) == 0
    rows = [json.loads(line.split(" ", 1)[1])
            for line in capsys.readouterr().out.splitlines()
            if line.startswith("reading ")]
    assert [r["label"] for r in rows] == ["program", "control-high"]
    assert rows[0]["passed"], rows[0]
    assert not rows[1]["passed"], rows[1]


@pytest.mark.parametrize("cell", sorted(bench.Benchmark().workloads))
def test_reference_takes_every_front(cell, monkeypatch):
    """On the CPU, where the precision is not honoured, a run with the
    control's reference factor in the program's place comes out correct,
    and the reference took every dispatch: the control replaces the
    kernels on each path the cell takes, in the layout they return."""
    import control
    import test_faults

    import repro.kernels.ops as ops
    import repro.runtime.executor as executor

    for mod in (ops, executor):
        for name in ("batched_front_factor", "partial_cholesky"):
            monkeypatch.setattr(mod, name, getattr(mod, name))
    kernels = (ops.batched_front_factor, ops.partial_cholesky)
    control.install_reference()
    assert (ops.batched_front_factor, ops.partial_cholesky) != kernels
    calls = []
    for mod in (ops, executor):
        for name in ("batched_front_factor", "partial_cholesky"):
            fn = getattr(mod, name)
            monkeypatch.setattr(mod, name, lambda *a, _f=fn, **k:
                                calls.append(1) or _f(*a, **k))
    out = bench.execute(test_faults.tiny_run(cell), need_chip=False)
    assert calls
    assert out["correct"], out["checks"]
