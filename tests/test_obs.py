"""The unified telemetry layer (``repro.obs``).

Contracts under test:

* the event bus — begin/end spans, orphan ends raise, counter tracks,
  the zero-overhead disable switch;
* the metrics registry — Prometheus trio semantics and both exporters;
* efficiency — p̂(t) folding, the Theorem-6 fluid ratio (== 1.0 within
  1e-9 on the zero-noise single-tree case), L2 deviation, α residuals,
  device utilization;
* the one chrome-trace emitter — both legacy ``to_trace`` wrappers emit
  exactly the canonical slice key set, ``from_bus`` adds lanes/phases/
  counters and stays JSON-serializable;
* executor integration — an async run publishes well-formed spans whose
  aggregates match the ExecutionReport, and ``obs.disable()`` leaves
  the factors bit-identical while recording nothing;
* the dashboard — HTTP routes and the static HTML report.
"""
import json
import math
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.api import DeviceMesh, Problem, Session, SharedMemory
from repro.core.pm import tree_equivalent_lengths
from repro.core.trees import random_assembly_tree
from repro.obs.trace import PHASE_ORDER, SLICE_KEYS
from repro.sparse import (
    grid_laplacian_2d,
    nested_dissection_2d,
)

ALPHA = 0.9


@pytest.fixture(autouse=True)
def fresh_obs():
    """The bus is off by default; every test here records with it on,
    and leaves the default behind."""
    obs.enable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def grid_problem(g: int = 9) -> Problem:
    a = grid_laplacian_2d(g)
    return Problem.from_matrix(
        a, ALPHA, ordering=nested_dissection_2d(g), name=f"grid{g}"
    )


# ----------------------------------------------------------------------
# Event bus
# ----------------------------------------------------------------------
def test_bus_begin_end_round_trip():
    bus = obs.EventBus()
    sid = bus.begin("run", cat="front", key=3, device=2, t=1.0, flops=5.0)
    assert bus.open_spans() == [sid]
    sp = bus.end(sid, t=2.5, batched=2)
    assert bus.open_spans() == []
    assert (sp.name, sp.cat, sp.key, sp.device) == ("run", "front", 3, 2)
    assert sp.t0 == 1.0 and sp.t1 == 2.5 and sp.duration == 1.5
    assert sp.attrs == {"flops": 5.0, "batched": 2}
    assert bus.spans(cat="front", name="run") == [sp]


def test_bus_orphan_end_raises():
    bus = obs.EventBus()
    with pytest.raises(KeyError):
        bus.end(999)


def test_bus_disabled_publishes_nothing():
    bus = obs.EventBus()
    obs.disable()
    try:
        sid = bus.begin("run")
        assert sid == -1
        assert bus.end(sid) is None  # the disabled handshake is silent
        bus.span("run", 0.0, 1.0)
        bus.point("queue_depth", 4.0)
        assert len(bus) == 0 and bus.open_spans() == []
    finally:
        obs.enable()


def test_bus_counter_tracks_sorted_by_time():
    bus = obs.EventBus()
    bus.point("queue_depth", 2.0, t=5.0)
    bus.point("queue_depth", 3.0, t=1.0)
    bus.point("marker", t=2.0)  # value-less: not a counter sample
    tracks = bus.counter_tracks()
    assert tracks == {"queue_depth": [(1.0, 3.0), (5.0, 2.0)]}


def test_bus_subscribe_streams_and_unsubscribes():
    bus = obs.EventBus()
    seen = []
    unsub = bus.subscribe(seen.append)
    bus.span("run", 0.0, 1.0)
    bus.point("capacity", 8.0, t=0.5)
    assert [type(x).__name__ for x in seen] == ["Span", "Event"]
    unsub()
    bus.span("run", 1.0, 2.0)
    assert len(seen) == 2


def test_bus_mixed_clocks_are_tagged():
    bus = obs.EventBus()
    bus.span("run", 0.0, 1.0, clock=obs.VIRTUAL)
    bus.span("run", 0.0, 1.0, clock=obs.WALL)
    clocks = {s.clock for s in bus.spans()}
    assert clocks == {obs.VIRTUAL, obs.WALL}


# ----------------------------------------------------------------------
# Metrics registry
# ----------------------------------------------------------------------
def test_counter_labels_and_monotonicity():
    reg = obs.Registry()
    c = reg.counter("repro_requests_total", "requests", unit="1")
    c.inc()
    c.inc(2.0, tenant=3)
    c.inc(1.0, tenant=3)
    assert c.value == 1.0
    assert c.value_of(tenant=3) == 3.0
    with pytest.raises(ValueError):
        c.inc(-1.0)
    text = reg.prometheus()
    assert "# TYPE repro_requests_total counter" in text
    assert 'repro_requests_total{tenant="3"} 3' in text


def test_gauge_track_series():
    reg = obs.Registry()
    g = reg.gauge("repro_queue_depth", "depth", track=True)
    g.set(2.0, t=0.5)
    g.set(5.0, t=1.5)
    assert g.value == 5.0
    assert g.track() == [(0.5, 2.0), (1.5, 5.0)]


def test_histogram_prometheus_semantics():
    reg = obs.Registry()
    h = reg.histogram("repro_lat", "latency", unit="s", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, float("nan")):
        h.observe(v)
    assert h.count == 4  # NaN observations are dropped
    assert h.mean() == pytest.approx((0.05 + 0.5 + 0.5 + 5.0) / 4)
    assert h.quantile(0.5) == 1.0  # bucket-resolved upper bound
    lines = h.prometheus()
    # cumulative bucket counts, then sum and count
    assert 'repro_lat_bucket{le="0.1"} 1' in lines
    assert 'repro_lat_bucket{le="1"} 3' in lines
    assert 'repro_lat_bucket{le="+Inf"} 4' in lines
    assert any(l.startswith("repro_lat_sum ") for l in lines)
    assert "repro_lat_count 4" in lines


def test_registry_kind_conflict_and_snapshot():
    reg = obs.Registry()
    reg.counter("repro_x", "a counter").inc()
    with pytest.raises(TypeError):
        reg.gauge("repro_x")
    snap = reg.snapshot()
    json.dumps(snap)  # JSON-safe by contract
    assert snap["repro_x"]["values"]["total"] == 1.0


def test_disabled_registry_records_nothing():
    reg = obs.Registry()
    obs.disable()
    try:
        reg.counter("repro_c").inc()
        reg.gauge("repro_g", track=True).set(3.0)
        reg.histogram("repro_h").observe(1.0)
        assert reg.counter("repro_c").value == 0.0
        assert reg.gauge("repro_g").value == 0.0
        assert reg.histogram("repro_h").count == 0
    finally:
        obs.enable()


# ----------------------------------------------------------------------
# Efficiency: p̂(t), the fluid bound, α residuals, utilization
# ----------------------------------------------------------------------
def test_fold_share_timeline():
    steps = obs.fold_share_timeline(
        [(0.0, 2.0, 4.0), (1.0, 3.0, 2.0), (5.0, 5.0, 9.0)]
    )
    assert steps == [(0.0, 4.0), (1.0, 6.0), (2.0, 2.0), (3.0, 0.0)]


def test_l2_deviation_zero_iff_identical():
    ref = obs.pm_reference_timeline(8.0, 10.0)
    assert obs.l2_share_deviation(ref, ref) == 0.0
    half = [(0.0, 4.0), (20.0, 0.0)]  # half the share, twice as long
    dev = obs.l2_share_deviation(half, ref)
    assert dev > 0.3


def test_schedule_l2_deviation_fluid_pm_is_zero(rng):
    tree = random_assembly_tree(60, rng)
    sched = Session(SharedMemory(16)).load(tree, ALPHA).plan("pm").schedule
    # the fluid PM schedule engages the full pool until its own fluid
    # makespan — exactly the Theorem-6 reference profile
    assert obs.schedule_l2_deviation(sched) == pytest.approx(0.0, abs=1e-6)


def test_fluid_ratio_zero_noise_single_tree(rng):
    """Acceptance: fluid_ratio == 1.0 within 1e-9 on the zero-noise
    single-tree case (the online PM loop *is* the fluid optimum)."""
    tree = random_assembly_tree(80, rng)
    rep = Session(SharedMemory(24)).load(tree, ALPHA).simulate(policy="pm")
    assert abs(obs.fluid_ratio(rep) - 1.0) < 1e-9
    assert abs(rep.metrics["fluid_ratio"] - 1.0) < 1e-9
    fluid = tree_equivalent_lengths(tree, ALPHA)[tree.root] / 24**ALPHA
    assert obs.fluid_ratio(rep.makespan, fluid) == pytest.approx(1.0, abs=1e-9)


def test_alpha_residuals_recover_perfect_model():
    pts = [
        ("64x32", g, 3.0 * g**ALPHA)
        for g in (1, 2, 4, 8)
    ] + [("128x64", g, 7.0 * g**ALPHA) for g in (2, 8)]
    out = obs.alpha_residuals(pts, ALPHA)
    for bucket in ("64x32", "128x64"):
        assert out[bucket]["rms"] == pytest.approx(0.0, abs=1e-12)
        assert out[bucket]["alpha_fit"] == pytest.approx(ALPHA, abs=1e-12)


def test_device_utilization_merges_overlaps():
    mk = lambda sid, t0, t1, dev, used: obs.Span(
        sid, "run", "front", sid, dev, t0, t1, attrs={"devices_used": used}
    )
    spans = [
        mk(0, 0.0, 1.0, 0, 2),  # lanes 0,1
        mk(1, 0.5, 1.0, 0, 2),  # batched twin: same lanes, overlap merged
        mk(2, 1.0, 2.0, 2, 1),  # lane 2
    ]
    u = obs.device_utilization(spans, 4, horizon=2.0)
    assert u["per_device"] == pytest.approx([0.5, 0.5, 0.5, 0.0])
    assert u["occupancy"] == pytest.approx(0.375)
    assert u["horizon"] == 2.0


# ----------------------------------------------------------------------
# One trace vocabulary: both legacy emitters, plus the bus view
# ----------------------------------------------------------------------
def test_schedule_trace_key_set_regression():
    prob = grid_problem(9)
    sched = Session(SharedMemory(8)).load(prob).plan("greedy").schedule
    trace = sched.to_trace()
    assert trace
    for ev in trace:
        assert set(ev) == SLICE_KEYS
        assert ev["ph"] == "X"


@pytest.fixture(scope="module")
def async_run():
    """One instrumented async execution, captured before any reset."""
    obs.enable()
    obs.reset()
    rep = (
        Session(DeviceMesh(plan_devices=8))
        .load(grid_problem(9))
        .plan("greedy")
        .execute(mode="async", warmup=False)
    )
    reg = obs.get_registry()
    return {
        "rep": rep,
        "spans": obs.BUS.spans(),
        "open": obs.BUS.open_spans(),
        "tracks": obs.BUS.counter_tracks(),
        "snapshot": reg.snapshot(),
        "bus_trace": obs.from_bus(obs.BUS),
        "report_trace": rep.detail.to_trace(),
    }


def test_execution_trace_key_set_regression(async_run):
    trace = async_run["report_trace"]
    assert trace
    for ev in trace:
        assert set(ev) == SLICE_KEYS
        assert ev["ph"] == "X"


def test_async_run_spans_well_formed(async_run):
    spans = async_run["spans"]
    assert async_run["open"] == []  # every begin() was matched
    fronts = [s for s in spans if s.cat == "front"]
    assert fronts and {s.name for s in fronts} <= set(PHASE_ORDER)
    by_key = {}
    for s in fronts:
        by_key.setdefault(s.key, {})[s.name] = s
    n_run = 0
    for key, phases in by_key.items():
        run = phases.get("run")
        assert run is not None, f"front {key} has no run span"
        n_run += 1
        assert math.isfinite(run.t0) and run.t1 >= run.t0 >= 0.0
        assert run.attrs["devices_used"] >= 1
        if "submit" in phases:  # submit ends where the run starts
            assert phases["submit"].t1 == pytest.approx(run.t0, abs=1e-9)
        if "ready" in phases:  # ready ends at (or before) dispatch
            assert phases["ready"].t1 <= run.t0 + 1e-9
    rep = async_run["rep"]
    assert n_run == len(rep.detail.trace)


def test_async_run_counters_match_report(async_run):
    rep, snap = async_run["rep"], async_run["snapshot"]
    trace = rep.detail.trace
    assert snap["repro_fronts_completed_total"]["values"]["total"] == len(trace)
    assert (
        snap["repro_dispatches_total"]["values"]["total"]
        == rep.detail.n_dispatches
    )
    n_ready = sum(1 for e in trace if not math.isnan(e.t_ready))
    assert snap["repro_ready_latency_seconds"]["count"] == n_ready
    # batch widths: one sample per dispatch interval, fronts sum to trace
    widths = snap["repro_batch_width"]
    assert widths["sum"] == len(trace)
    assert snap["repro_peak_resident_bytes"]["values"]["value"] == (
        rep.detail.measured_peak_bytes
    )


def test_async_run_live_counter_tracks(async_run):
    tracks = async_run["tracks"]
    for name in ("queue_depth", "resident_bytes"):
        assert name in tracks and tracks[name]
        ts = [t for t, _ in tracks[name]]
        assert ts == sorted(ts)
    assert all(v >= 0 for _, v in tracks["resident_bytes"])


def test_bus_trace_has_lanes_phases_and_counters(async_run):
    events = async_run["bus_trace"]
    json.dumps(events)  # perfetto-loadable JSON
    phs = {e["ph"] for e in events}
    assert phs == {"M", "X", "C"}
    # metadata first, naming host + device lanes
    metas = [e for e in events if e["ph"] == "M"]
    assert events[: len(metas)] == metas
    names = {e["args"]["process_name"] for e in metas}
    assert "host" in names
    assert any(n.startswith("device") for n in names)
    for e in events:
        if e["ph"] == "X":
            assert set(e) == SLICE_KEYS and e["dur"] > 0
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert "queue_depth" in counters and "resident_bytes" in counters


def test_run_report_metrics_have_no_null_values(async_run):
    for k, v in async_run["rep"].metrics.items():
        assert v is not None, k
        assert not (isinstance(v, float) and math.isnan(v)), k


def test_utilization_and_efficiency_from_bus(async_run):
    spans = async_run["spans"]
    u = obs.device_utilization(
        [s for s in spans if s.cat == "front"], 8
    )
    assert 0.0 < u["occupancy"] <= 1.0
    assert len(u["per_device"]) == 8
    summary = obs.efficiency_summary(async_run["rep"])
    assert summary["fluid_ratio"] >= 0.0
    json.dumps(summary)


# ----------------------------------------------------------------------
# Zero-overhead disable: bit-identical factors, silent instruments
# ----------------------------------------------------------------------
def test_disable_leaves_factors_bit_identical():
    prob = grid_problem(7)

    def run():
        obs.reset()
        rep = (
            Session(DeviceMesh(plan_devices=4))
            .load(prob)
            .plan("greedy")
            .execute(mode="async", warmup=False)
        )
        return rep.artifact.to_dense_l()

    on = run()
    obs.disable()
    try:
        off = run()
        assert len(obs.BUS) == 0
        assert obs.get_registry().names() == []
    finally:
        obs.enable()
    np.testing.assert_allclose(on, off, rtol=0, atol=0)


# ----------------------------------------------------------------------
# Online / serve integration: virtual-clock spans
# ----------------------------------------------------------------------
def test_serve_publishes_virtual_spans_and_admission_metrics(rng):
    t1 = random_assembly_tree(30, rng)
    t2 = random_assembly_tree(40, rng)
    p1 = Problem.from_tree(t1, ALPHA, name="t1")
    p2 = Problem.from_tree(t2, ALPHA, name="t2")
    rep = Session(SharedMemory(8)).serve(
        [(p1, 0.0, 0), (p2, 0.1, 1)], admission="fair", max_concurrent=1
    )
    trees = obs.BUS.spans(cat="tree", name="run")
    assert len(trees) == 2
    assert all(s.clock == obs.VIRTUAL for s in trees)
    tasks = obs.BUS.spans(cat="task", name="run")
    assert len(tasks) == t1.n + t2.n
    reg = obs.get_registry()
    admit = reg.counter("repro_admission_requests_total")
    assert admit.value_of(tenant=0) == 1.0
    assert admit.value_of(tenant=1) == 1.0
    assert reg.histogram("repro_admission_wait_seconds").count == 2
    assert 0.0 < reg.gauge("repro_online_utilization").value <= 1.0
    # virtual-clock capacity samples ride next to the wall-clock ones
    assert "capacity" in obs.BUS.counter_tracks()
    assert rep.metrics["fluid_ratio"] >= 1.0 - 1e-12


def test_elastic_run_publishes_plan_segments():
    from repro.core.trees import balanced_tree
    from repro.runtime.elastic import ElasticEvent, run_elastic_schedule

    tree = balanced_tree(depth=4, arity=2)
    mk, plans = run_elastic_schedule(
        tree, ALPHA, 8, [ElasticEvent(time=0.05, devices=4)]
    )
    segs = obs.BUS.spans(cat="plan", name="run")
    assert len(segs) == len(plans)
    assert all(s.clock == obs.VIRTUAL for s in segs)
    assert segs[-1].t1 == pytest.approx(mk)
    reg = obs.get_registry()
    assert reg.counter("repro_elastic_replans_total").value == len(plans)


# ----------------------------------------------------------------------
# Dashboard: HTTP routes, static HTML, trace file
# ----------------------------------------------------------------------
def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=10) as resp:
        assert resp.status == 200
        return resp.read()


def test_dashboard_routes(rng):
    tree = random_assembly_tree(40, rng)
    Session(SharedMemory(8)).load(tree, ALPHA).simulate(policy="pm")
    dash = obs.Dashboard(0, context={"subtitle": "test run"})
    try:
        page = _get(dash.url).decode()
        assert "<html" in page and "test run" in page
        prom = _get(dash.url + "metrics").decode()
        assert "# TYPE" in prom
        snap = json.loads(_get(dash.url + "metrics.json"))
        assert isinstance(snap, dict)
        trace = json.loads(_get(dash.url + "trace.json"))
        assert trace["traceEvents"]
        with pytest.raises(urllib.error.HTTPError):
            _get(dash.url + "nope")
    finally:
        dash.stop()


def test_serve_dashboard_port_lifecycle(rng):
    tree = random_assembly_tree(30, rng)
    sess = Session(SharedMemory(8))
    rep = sess.serve(
        [(Problem.from_tree(tree, ALPHA), 0.0)], dashboard_port=0
    )
    assert sess.dashboard is not None
    try:
        page = _get(sess.dashboard.url).decode()
        assert "<html" in page
        # post-run context carries the run's makespan
        assert sess.dashboard.context["makespan"] == rep.makespan
    finally:
        sess.dashboard.stop()


def test_save_html_and_trace_files(tmp_path, rng):
    tree = random_assembly_tree(40, rng)
    rep = Session(SharedMemory(8)).load(tree, ALPHA).simulate(policy="pm")
    html_path = rep.save_html(tmp_path / "run.html")
    doc = open(html_path).read()
    assert "<html" in doc and "repro" in doc
    trace_path = tmp_path / "run.trace.json"
    obs.save_trace(obs.from_bus(obs.BUS), trace_path)
    loaded = json.loads(open(trace_path).read())
    assert loaded["displayTimeUnit"] == "ms"
    assert loaded["traceEvents"]
