"""Stage spans of the executor (``obs.stage``) and the bus's default.

Contracts under test:

* every async dispatch records its host stages: ``executor.gather``,
  ``executor.extend_add``, ``executor.pack`` and ``executor.unpack`` on
  the main thread, and one ``executor.dispatch`` holding one
  ``executor.h2d``, ``executor.kernel`` and ``executor.d2h`` on a worker;
* the stages nest inside ``executor.run``, inside ``session.execute``;
* with the default settings the bus and the registry record nothing;
* the factor bits do not depend on the stage spans;
* the stage names reach an active ``jax.profiler`` trace's host plane.
"""
import contextlib
import glob
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import DeviceMesh, Problem, Session, SharedMemory
from repro.runtime import executor as executor_mod
from repro.sparse import grid_laplacian_2d, nested_dissection_2d

ALPHA = 0.9
MAIN_STAGES = (
    "executor.gather", "executor.extend_add", "executor.pack", "executor.unpack"
)
WORKER_STAGES = ("executor.h2d", "executor.kernel", "executor.d2h")


@pytest.fixture(autouse=True)
def bus_off_after():
    """Each test starts from an empty bus and leaves the default (off)."""
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def grid_problem(g: int = 9) -> Problem:
    a = grid_laplacian_2d(g)
    return Problem.from_matrix(
        a, ALPHA, ordering=nested_dissection_2d(g), name=f"grid{g}"
    )


def execute(prob: Problem, optimize: bool = False):
    sess = Session(DeviceMesh(plan_devices=4)).load(prob)
    if optimize:
        sess.optimize()
    return sess.plan("greedy").execute(mode="async", warmup=False)


def stages(name=None):
    return obs.BUS.spans(cat="stage", name=name)


def inside(inner, outer) -> bool:
    return outer.t0 <= inner.t0 and inner.t1 <= outer.t1


@pytest.fixture(scope="module")
def recorded():
    """One async execution with the bus on: its report and stage spans."""
    obs.enable()
    obs.reset()
    rep = execute(grid_problem(9))
    out = {"rep": rep, "spans": stages(), "main": threading.get_native_id()}
    obs.disable()
    return out


def test_stage_is_a_bus_span_only_when_enabled():
    with obs.stage("executor.probe", k=1):
        pass
    assert len(obs.BUS) == 0
    obs.enable()
    with obs.stage("executor.probe", k=1):
        pass
    (sp,) = stages("executor.probe")
    assert sp.key == threading.get_native_id() and sp.attrs == {"k": 1}
    assert sp.t1 >= sp.t0


@pytest.mark.parametrize("name", MAIN_STAGES)
def test_each_dispatch_records_its_main_thread_stage(recorded, name):
    n_disp = recorded["rep"].detail.n_dispatches
    spans = [s for s in recorded["spans"] if s.name == name]
    assert len(spans) == n_disp > 1
    assert {s.key for s in spans} == {recorded["main"]}


def test_each_dispatch_runs_h2d_kernel_d2h_on_a_worker(recorded):
    spans = recorded["spans"]
    dispatches = [s for s in spans if s.name == "executor.dispatch"]
    assert len(dispatches) == recorded["rep"].detail.n_dispatches
    assert recorded["main"] not in {s.key for s in dispatches}
    for name in WORKER_STAGES:
        inner = [s for s in spans if s.name == name]
        assert len(inner) == len(dispatches)
        for d in dispatches:
            held = [s for s in inner if s.key == d.key and inside(s, d)]
            assert len(held) == 1, (name, d)


def test_stages_nest_in_executor_run_in_session_execute(recorded):
    spans = recorded["spans"]
    (sess,) = [s for s in spans if s.name == "session.execute"]
    (run,) = [s for s in spans if s.name == "executor.run"]
    assert inside(run, sess) and run.key == sess.key
    for name in ("executor.prologue", "executor.epilogue"):
        (sp,) = [s for s in spans if s.name == name]
        assert inside(sp, run) and sp.key == run.key
    nested = [s for s in spans if s.name not in ("session.execute", "executor.run")]
    assert nested and all(inside(s, run) for s in nested)
    schedules = [s for s in spans if s.name == "executor.schedule"]
    for name in ("executor.gather", "executor.extend_add", "executor.pack"):
        for s in (s for s in spans if s.name == name):
            assert any(inside(s, sch) for sch in schedules), name


def test_amalgamated_dispatch_stages_run_on_the_worker():
    obs.enable()
    rep = execute(grid_problem(9), optimize=True)
    main = threading.get_native_id()
    dispatches = stages("executor.dispatch")
    assert len(dispatches) == rep.detail.n_dispatches
    for name in (*MAIN_STAGES, *WORKER_STAGES):
        spans = [s for s in stages(name) if s.key != main]
        assert spans, name
        for s in spans:
            assert any(s.key == d.key and inside(s, d) for d in dispatches)
    # the main thread gathers nothing: it lands each finished group once
    on_main = {n: [s for s in stages(n) if s.key == main] for n in MAIN_STAGES}
    assert len(on_main.pop("executor.unpack")) == rep.detail.n_dispatches
    assert not any(on_main.values())


def test_default_execute_records_nothing_on_bus_or_registry():
    """In a fresh process: the bus is off until ``obs.enable()``."""
    code = (
        "from repro import obs\n"
        "from repro.api import DeviceMesh, Problem, Session\n"
        "from repro.sparse import grid_laplacian_2d, nested_dissection_2d\n"
        "assert not obs.enabled()\n"
        "p = Problem.from_matrix(grid_laplacian_2d(7), 0.9,\n"
        "                        ordering=nested_dissection_2d(7))\n"
        "Session(DeviceMesh(plan_devices=4)).load(p).plan('greedy')"
        ".execute(warmup=False)\n"
        "assert len(obs.BUS) == 0, len(obs.BUS)\n"
        "assert obs.get_registry().names() == []\n"
        "print('bus-off-ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr
    assert "bus-off-ok" in out.stdout


def test_serve_dashboard_turns_the_bus_on(rng):
    from repro.core.trees import random_assembly_tree

    assert not obs.enabled()
    sess = Session(SharedMemory(8))
    try:
        sess.serve(
            [(Problem.from_tree(random_assembly_tree(20, rng), ALPHA), 0.0)],
            dashboard_port=0,
        )
        assert obs.enabled()
        assert obs.BUS.spans(cat="tree", name="run")
    finally:
        sess.close()


@pytest.mark.parametrize("optimize", [False, True], ids=["plain", "amalgamated"])
def test_factors_bit_identical_with_and_without_stage_spans(
    monkeypatch, optimize
):
    prob = grid_problem(7)
    obs.enable()
    with_bus = execute(prob, optimize).artifact.to_dense_l()
    obs.disable()
    with_spans = execute(prob, optimize).artifact.to_dense_l()

    class no_stage(contextlib.nullcontext):
        def __init__(self, name, **attrs):
            super().__init__()

    monkeypatch.setattr(executor_mod, "stage", no_stage)
    without = execute(prob, optimize).artifact.to_dense_l()
    waves = (
        Session(DeviceMesh(plan_devices=4)).load(prob).plan("greedy")
        .execute(mode="waves", warmup=False).artifact.to_dense_l()
    )
    for other in (with_bus, without, waves):
        np.testing.assert_array_equal(with_spans, other)


def test_profiler_trace_holds_the_stage_names(tmp_path):
    from jax.profiler import ProfileData

    prob = grid_problem(7)
    execute(prob)  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        execute(prob)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {
        e.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }
    want = {
        "session.execute", "executor.run", "executor.prologue",
        "executor.schedule", "executor.wait", "executor.epilogue",
        "executor.dispatch", *MAIN_STAGES, *WORKER_STAGES,
    }
    assert want <= names, want - names
    assert len(obs.BUS) == 0  # the profiler, not the bus, recorded them
