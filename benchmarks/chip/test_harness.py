"""CPU checks of the harness: files found by name, no run without a
TPU, and the benchmark's own work count equal to the program's today.

    python -m pytest benchmarks/chip/test_harness.py
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import run as bench  # noqa: E402
import work  # noqa: E402


def _run(args, cwd=ROOT, **env):
    return subprocess.run(
        [sys.executable, str(Path(cwd) / "benchmarks/chip/run.py"), *args],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


def test_list_resolves_every_entry():
    p = _run(["--list"])
    assert p.returncode == 0, p.stderr
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert f"metrics/{m['name']}.py" in p.stdout
    for w in spec["workloads"]:
        assert f"traffic/{w['traffic']}.json" in p.stdout


def test_list_fails_on_a_missing_file(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (tmp_path / "benchmarks/chip/metrics/plan_s.py").unlink()
    p = _run(["--list"], cwd=tmp_path)
    assert p.returncode == 1
    assert "plan_s" in p.stderr


@pytest.mark.parametrize("cell", sorted(bench.Benchmark().workloads))
def test_no_tpu_no_result(cell):
    p = _run(["--workload", cell, "--seed", "3", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode != 0
    assert "platform=cpu" in p.stdout
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


@pytest.mark.parametrize("m,nb", [(1, 1), (7, 3), (128, 128), (382, 127),
                                  (1190, 600)])
def test_flops_equal_the_programs(m, nb):
    sys.path.insert(0, str(ROOT / "src"))
    from repro.sparse.symbolic import partial_factor_flops

    assert work.partial_factor_flops(m, nb) == partial_factor_flops(m, nb)


def test_roofline_share_by_hand():
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    # one (4, 2) front: flops 4^2+4+1 + 3^2+3+1 = 34, bytes 2*4*16 = 128;
    # twice: 68 flops -> 6.8e-11 s, 256 bytes -> 2.56e-7 s (memory bound)
    share, bound = work.roofline_share([(4, 2)], 2, 2.56e-6, peak)
    assert bound == "memory"
    assert share == pytest.approx(10.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v0 imaginary")
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12
