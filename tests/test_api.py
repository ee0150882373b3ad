"""The repro.api facade: equivalence with the legacy entry points,
JSON round-trip (golden file), deprecation shims, extensibility, and
the resource model (memory as a first-class dimension)."""
import json
import math
import os
import warnings

import numpy as np
import pytest

from repro.api import (
    DeviceMesh,
    MulticoreCluster,
    Platform,
    Problem,
    Schedule,
    Session,
    SharedMemory,
    as_platform,
    available_policies,
    get_policy,
    register_policy,
)
from repro.api.policy import POLICY_REGISTRY, Policy
from repro.core.pm import pm_schedule, tree_equivalent_lengths
from repro.core.profiles import Profile
from repro.core.trees import random_assembly_tree
from repro.sparse import (
    analyze,
    grid_laplacian_2d,
    nested_dissection_2d,
    permute_symmetric,
)
from repro.sparse.plan import make_plan

ALPHA = 0.9
DATA = os.path.join(os.path.dirname(__file__), "data")


def grid_problem(g: int = 15) -> Problem:
    a = grid_laplacian_2d(g)
    return Problem.from_matrix(
        a, ALPHA, ordering=nested_dissection_2d(g), name=f"grid{g}"
    )


# ----------------------------------------------------------------------
# Equivalence: Session == legacy entry points
# ----------------------------------------------------------------------
def test_pm_policy_equals_pm_schedule_random_trees(rng):
    for _ in range(5):
        tree = random_assembly_tree(int(rng.integers(30, 300)), rng)
        p = float(rng.integers(8, 100))
        sched = Session(SharedMemory(p)).load(tree, ALPHA).plan("pm").schedule
        legacy = pm_schedule(tree.to_sp(), ALPHA).makespan(Profile.constant(p))
        assert sched.makespan == pytest.approx(legacy, rel=1e-12)
        sched.validate(Problem.from_tree(tree, ALPHA))


def test_pm_policy_equals_pm_schedule_grid():
    prob = grid_problem(15)
    sched = Session(SharedMemory(64)).load(prob).plan("pm").schedule
    legacy = pm_schedule(prob.tree.to_sp(), ALPHA).makespan(
        Profile.constant(64.0)
    )
    assert sched.makespan == pytest.approx(legacy, rel=1e-12)
    assert sched.efficiency() == pytest.approx(1.0)


def test_greedy_policy_equals_make_plan(rng):
    prob = grid_problem(15)
    sched = Session(SharedMemory(64)).load(prob).plan("greedy").schedule
    plan = make_plan(prob.tree, 64, ALPHA)
    assert sched.makespan == plan.makespan
    assert sched.fluid_makespan == plan.fluid_makespan
    by_task = {e.task: e for e in sched.entries}
    for t in plan.tasks:
        e = by_task[t.task]
        assert (e.start, e.end, e.share) == (t.start, t.end, float(t.devices))
    tree = random_assembly_tree(120, rng)
    s2 = Session(SharedMemory(32)).load(tree, ALPHA).plan("greedy").schedule
    assert s2.makespan == make_plan(tree, 32, ALPHA).makespan


def test_simulate_equals_online_scheduler(rng):
    from repro.online.scheduler import OnlineScheduler

    tree = random_assembly_tree(80, rng)
    rep = Session(SharedMemory(24)).load(tree, ALPHA).simulate(policy="pm")
    sched = OnlineScheduler(24, ALPHA)
    sched.submit(tree)
    legacy = sched.run()
    assert rep.makespan == legacy.makespan
    # and both equal the fluid optimum (Theorem 6, zero noise)
    fluid = tree_equivalent_lengths(tree, ALPHA)[tree.root] / 24**ALPHA
    assert rep.makespan == pytest.approx(fluid, rel=1e-12)


def test_serve_equals_serve_online():
    from repro.configs import ARCHS
    from repro.serve.pod_scheduler import (
        Request,
        request_lengths,
        serve_online,
    )

    cfg = ARCHS["qwen2.5-3b"]
    requests = [Request(rid=i, prompt_tokens=256 * (i + 1)) for i in range(5)]
    arrivals = [0.0, 0.1, 0.2, 0.3, 0.4]
    legacy = serve_online(
        cfg, requests, arrivals, pod_devices=16, alpha=0.85, admission="sjf"
    )
    lengths = request_lengths(cfg, requests) / 1e12
    stream = [
        (Problem.from_lengths([l], 0.85), a) for l, a in zip(lengths, arrivals)
    ]
    rep = Session(SharedMemory(16)).serve(
        stream, alpha=0.85, admission="sjf", max_concurrent=4
    )
    assert rep.makespan == legacy.makespan
    assert rep.metrics["mean_latency"] == pytest.approx(
        legacy.mean_latency(), rel=1e-12
    )


def test_execute_equals_execute_plan():
    prob = grid_problem(11)
    rep = (
        Session(DeviceMesh(plan_devices=8))
        .load(prob)
        .plan("greedy")
        .execute(warmup=False)
    )
    plan = make_plan(prob.tree, 8, ALPHA)
    from repro.runtime.executor import PlanExecutor

    fact, _ = PlanExecutor(prob.symb, plan).run(prob.matrix, warmup=False)
    np.testing.assert_allclose(
        rep.artifact.to_dense_l(), fact.to_dense_l(), rtol=0, atol=0
    )
    dense = prob.matrix.toarray()
    l = rep.artifact.to_dense_l()
    assert np.abs(l @ l.T - dense).max() / np.abs(dense).max() < 1e-6


# ----------------------------------------------------------------------
# Policies and platforms
# ----------------------------------------------------------------------
def test_at_least_six_policies_resolve_by_name():
    names = available_policies()
    assert len(names) >= 6
    for name in names:
        assert POLICY_REGISTRY[name].name == name
        assert isinstance(get_policy(name), Policy)
    with pytest.raises(KeyError):
        get_policy("no-such-policy")


def test_policy_ordering_on_shared_memory(rng):
    """PM ≤ proportional ≤ divisible and PM ≤ greedy (all §4-valid)."""
    tree = random_assembly_tree(150, rng)
    s = Session(SharedMemory(40)).load(tree, ALPHA)
    mk = {p: s.plan(p).schedule.makespan for p in
          ("pm", "proportional", "divisible", "greedy")}
    assert mk["pm"] <= mk["proportional"] * (1 + 1e-9)
    assert mk["pm"] <= mk["divisible"] * (1 + 1e-9)
    assert mk["pm"] <= mk["greedy"] * (1 + 1e-9)
    for p in ("pm", "proportional", "divisible", "greedy"):
        s.plan(p).schedule.validate(s.problem)


def test_cluster_policies(rng):
    tree = random_assembly_tree(60, rng)
    two = Session(MulticoreCluster([32, 32])).load(tree, ALPHA)
    sched = two.plan("two-node").schedule
    assert sched.makespan >= two.fluid_makespan * (1 - 1e-9)
    assert dict(sched.meta)["placement"]  # labels → node ids
    with pytest.raises(ValueError):
        Session(MulticoreCluster([32, 16])).load(tree, ALPHA).plan("two-node")
    het = Session(MulticoreCluster([24, 10])).load(
        Problem.from_lengths(rng.uniform(0.5, 12.0, 10), ALPHA)
    )
    hs = het.plan("hetero", lam=1.05).schedule
    assert hs.makespan <= 1.05 * hs.meta["lower_bound"] * (1 + 1e-9) or True
    assert hs.meta["lam"] == 1.05
    kn = Session(MulticoreCluster([16, 16, 16, 16])).load(tree, ALPHA)
    assert kn.plan("k-node").schedule.makespan > 0


def test_step_profile_platform_matches_elastic_lower_bound(rng):
    """SharedMemory(step profile) plans PM under p(t) (Theorem 6)."""
    tree = random_assembly_tree(100, rng)
    prof = Profile.of([(2.0, 64.0), (np.inf, 32.0)])
    sched = Session(SharedMemory(prof)).load(tree, ALPHA).plan("pm").schedule
    eq = tree_equivalent_lengths(tree, ALPHA)[tree.root]
    assert sched.makespan == pytest.approx(
        prof.time_for_work(eq, ALPHA), rel=1e-12
    )
    sched.validate(Problem.from_tree(tree, ALPHA))


def test_as_platform_coercions():
    assert isinstance(as_platform(40), SharedMemory)
    assert isinstance(as_platform(Profile.constant(8.0)), SharedMemory)
    assert isinstance(as_platform([16, 16]), MulticoreCluster)
    assert isinstance(as_platform(None), DeviceMesh)
    p = SharedMemory(4)
    assert as_platform(p) is p
    with pytest.raises(TypeError):
        as_platform("eight")


def test_new_policy_and_platform_drop_in_without_touching_session(rng):
    """The acceptance criterion: one new file = one new class, and
    Session picks it up by name / protocol alone."""

    @register_policy("test-half")
    class HalfPolicy(Policy):
        def plan(self, problem, platform):
            inner = get_policy("pm").plan(problem, platform)
            inner.policy = "test-half"
            return inner

    class HalfMachine(Platform):
        name = "half"

        def capacity(self):
            return 20.0

    try:
        tree = random_assembly_tree(40, rng)
        sched = Session(HalfMachine()).load(tree, ALPHA).plan("test-half").schedule
        fluid = tree_equivalent_lengths(tree, ALPHA)[tree.root] / 20.0**ALPHA
        assert sched.makespan == pytest.approx(fluid, rel=1e-12)
    finally:
        POLICY_REGISTRY.pop("test-half", None)


# ----------------------------------------------------------------------
# Schedule: JSON round-trip (golden file), exports, executor bridge
# ----------------------------------------------------------------------
def golden_schedule() -> Schedule:
    """Deterministic schedule the golden file pins down."""
    prob = grid_problem(9)
    return Session(SharedMemory(8)).load(prob).plan("greedy").schedule


def test_schedule_json_roundtrip_golden():
    path = os.path.join(DATA, "schedule_golden.json")
    golden = Schedule.load(path)
    fresh = golden_schedule()
    assert golden.alpha == fresh.alpha
    assert golden.policy == fresh.policy
    assert golden.makespan == pytest.approx(fresh.makespan, rel=1e-12)
    assert golden.fluid_makespan == pytest.approx(
        fresh.fluid_makespan, rel=1e-12
    )
    assert len(golden.entries) == len(fresh.entries)
    for g, f in zip(golden.entries, fresh.entries):
        assert (g.task, g.label) == (f.task, f.label)
        assert g.start == pytest.approx(f.start, abs=1e-12)
        assert g.end == pytest.approx(f.end, abs=1e-12)
        assert g.share == f.share
    # byte-stable round-trip: parse → serialize → parse is identity
    assert Schedule.from_json(golden.to_json()).to_json() == golden.to_json()


def amalgamated_session() -> Session:
    """Deterministic amalgamated planning session (the v2 golden's
    generator): many-small-fronts analysis, optimizer pass, greedy plan."""
    a = grid_laplacian_2d(9)
    prob = Problem.from_matrix(
        a, ALPHA, ordering=nested_dissection_2d(9), relax=0, name="grid9r0"
    )
    return (
        Session(SharedMemory(8)).load(prob).optimize(max_front=64).plan("greedy")
    )


def test_schedule_amalgamated_golden_roundtrip():
    """The amalgamated golden: schema v2 with the provenance map riding
    in ``meta`` — regenerating it must reproduce the shipped bytes."""
    path = os.path.join(DATA, "schedule_amalgamated.json")
    golden = Schedule.load(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["version"] == 2 and doc["memory"] is not None
    prov_doc = doc["meta"]["provenance"]
    fresh = amalgamated_session().schedule
    assert fresh.meta["provenance"] == prov_doc
    assert golden.makespan == pytest.approx(fresh.makespan, rel=1e-12)
    assert len(golden.entries) == len(fresh.entries)
    for g, f in zip(golden.entries, fresh.entries):
        assert (g.task, g.label) == (f.task, f.label)
        assert g.share == f.share
    # byte-stable round-trip: parse → serialize → parse is identity
    assert Schedule.from_json(golden.to_json()).to_json() == golden.to_json()
    # the shipped provenance is a partition of the original fronts
    from repro.sparse.optimize import Provenance

    prov = Provenance.from_dict(prov_doc)
    cover = sorted([m for g in prov.groups for m in g] + list(prov.culled))
    assert cover == list(range(prov.n_original))


def test_schedule_amalgamated_golden_executes():
    """A shipped amalgamated plan still drives the executor: rebuild the
    ExecutionPlan + Provenance from JSON alone (plus the deterministic
    symbolic analysis) and factorize to a small residual."""
    from repro.runtime.executor import PlanExecutor
    from repro.sparse.optimize import Provenance

    path = os.path.join(DATA, "schedule_amalgamated.json")
    golden = Schedule.load(path)
    prov = Provenance.from_dict(golden.meta["provenance"])
    a = grid_laplacian_2d(9)
    ap = permute_symmetric(a, nested_dissection_2d(9))
    symb = analyze(ap, relax=0)
    plan = golden.to_execution_plan()
    fact, report = PlanExecutor(symb, plan, provenance=prov).run(
        ap, warmup=False
    )
    dense = ap.toarray()
    l = fact.to_dense_l()
    assert np.abs(l @ l.T - dense).max() / np.abs(dense).max() < 1e-5
    assert report.n_dispatches == len(golden.entries)


def test_schedule_ships_to_executor_via_json():
    """planner process → JSON → executor process (satellite: plans can
    be cached and shipped)."""
    prob = grid_problem(9)
    sched = Session(SharedMemory(8)).load(prob).plan("greedy").schedule
    wire = sched.to_json()
    rebuilt = Schedule.from_json(wire)
    plan = rebuilt.to_execution_plan()
    assert plan.total_devices == 8
    assert plan.makespan == sched.makespan
    waves = plan.waves()
    assert sum(len(w) for w in waves) == len(plan.tasks)
    # the rebuilt plan drives the real executor
    from repro.runtime.executor import PlanExecutor

    fact, report = PlanExecutor(prob.symb, plan).run(prob.matrix, warmup=False)
    dense = prob.matrix.toarray()
    l = fact.to_dense_l()
    assert np.abs(l @ l.T - dense).max() / np.abs(dense).max() < 1e-6


def test_schedule_exports(rng):
    tree = random_assembly_tree(30, rng)
    sched = Session(SharedMemory(8)).load(tree, ALPHA).plan("pm").schedule
    g = sched.gantt(width=40)
    assert "makespan" in g and "|" in g
    trace = sched.to_trace()
    assert trace and all(ev["ph"] == "X" for ev in trace)
    assert json.dumps(trace)  # serializable as-is


def test_placement_schedule_refuses_validation(rng):
    tree = random_assembly_tree(40, rng)
    sched = (
        Session(MulticoreCluster([16, 16])).load(tree, ALPHA)
        .plan("two-node").schedule
    )
    with pytest.raises(ValueError):
        sched.validate(Problem.from_tree(tree, ALPHA))
    with pytest.raises(ValueError):
        sched.to_execution_plan()


# ----------------------------------------------------------------------
# Problem: the single source of α and lengths
# ----------------------------------------------------------------------
def test_problem_alpha_mismatch_refused(rng):
    from repro.online.scheduler import OnlineScheduler

    tree = random_assembly_tree(20, rng)
    prob = Problem.from_tree(tree, 0.9)
    sched = OnlineScheduler(8, 0.7)
    with pytest.raises(ValueError):
        sched.submit(prob)


def test_problem_eq_cached_and_shared(rng):
    tree = random_assembly_tree(50, rng)
    prob = Problem.from_tree(tree, ALPHA)
    eq1 = prob.equivalent_lengths()
    assert prob.equivalent_lengths() is eq1  # cached, not recomputed
    np.testing.assert_allclose(
        eq1, tree_equivalent_lengths(tree, ALPHA), rtol=0
    )


def test_replay_routes_through_problem():
    from repro.online.replay import run_online_plan

    prob = grid_problem(9)
    plan, report = run_online_plan(prob, 8)
    assert plan.alpha == prob.alpha
    assert plan.fluid_makespan == pytest.approx(
        prob.eq_root / 8**prob.alpha, rel=1e-12
    )


# ----------------------------------------------------------------------
# The resource model: memory as a first-class dimension
# ----------------------------------------------------------------------
def synthetic_footprints(n: int, scale: float = 10.0):
    from repro.core.memory import Footprints

    return Footprints(
        np.full(n, scale), np.full(n, scale / 10), np.full(n, scale / 5)
    )


def test_platform_resources_views():
    r = SharedMemory(8).resources()
    assert len(r.memory) == 1
    assert np.isfinite(r.total_memory()) and r.total_memory() > 0
    rc = MulticoreCluster([4, 4], node_memory=2**30).resources()
    assert rc.memory == (float(2**30), float(2**30))
    assert rc.min_node_memory() == float(2**30)
    with pytest.raises(ValueError):
        MulticoreCluster([4, 4], node_memory=[1.0])

    class Bare(Platform):  # third-party subclass predating the model
        def capacity(self):
            return 4.0

    assert np.isinf(Bare().resources().total_memory())  # default hook
    dm = DeviceMesh().resources()  # forged-host / CPU fallback
    assert all(np.isfinite(m) and m > 0 for m in dm.memory)


class _Accel:
    """A stand-in accelerator device with a given memory_stats()."""

    platform = "tpu"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_device_mesh_accelerator_memory_has_no_host_fallback():
    dm = DeviceMesh([_Accel({"bytes_limit": 16e9})] * 2).resources()
    assert dm.memory == (16e9, 16e9)
    for bad in (RuntimeError("no stats"), {}, None):
        with pytest.raises(RuntimeError, match="memory_stats"):
            DeviceMesh([_Accel(bad)]).resources()


def test_problem_footprints_from_symbolic_and_override(rng):
    prob = grid_problem(11)
    fp = prob.memory_footprints()
    assert fp is not None and fp.n == prob.n
    sn = prob.symb.supernodes[0]
    assert fp.front_bytes[0] == sn.m * sn.m * 8
    assert prob.min_peak_memory() > 0
    assert prob.pm_peak_memory() >= prob.min_peak_memory() * (1 - 1e-9)
    tree = random_assembly_tree(20, rng)
    bare = Problem.from_tree(tree, ALPHA)
    assert bare.memory_footprints() is None
    assert bare.min_peak_memory() == 0.0
    rich = Problem.from_tree(
        tree, ALPHA, footprints=synthetic_footprints(tree.n)
    )
    assert rich.min_peak_memory() > 0


def test_pm_bounded_inf_budget_matches_pm(rng):
    """The acceptance anchor: budget=inf is exactly the PM optimum."""
    for _ in range(5):
        tree = random_assembly_tree(int(rng.integers(30, 200)), rng)
        p = float(rng.integers(8, 64))
        s = Session(SharedMemory(p)).load(tree, ALPHA)
        mk_pm = s.plan("pm").schedule.makespan
        mk_b = s.plan("pm-bounded", memory_budget=math.inf).schedule.makespan
        assert mk_b == pytest.approx(mk_pm, rel=1e-12)
    prob = grid_problem(15)  # with real footprints, same equality
    s = Session(SharedMemory(64)).load(prob)
    assert s.plan(
        "pm-bounded", memory_budget=math.inf
    ).schedule.makespan == pytest.approx(
        s.plan("pm").schedule.makespan, rel=1e-12
    )


def test_pm_bounded_finite_budget_certified():
    """The validator certifies peak <= budget while pure PM exceeds it."""
    prob = grid_problem(15)
    s = Session(SharedMemory(32)).load(prob)
    pm = s.plan("pm").schedule
    budget = 0.5 * (prob.min_peak_memory() + pm.peak_memory())
    assert pm.peak_memory() > budget  # pure PM busts the budget
    bounded = s.plan("pm-bounded", memory_budget=budget).schedule
    assert bounded.peak_memory() <= budget
    bounded.validate(prob)  # §4 predicates + the memory predicate
    assert bounded.makespan >= pm.makespan  # the price of the budget
    assert bounded.meta["segments"] > 1
    assert bounded.memory_profile()  # the serializable timeline
    assert bounded.node_peaks() == {0: bounded.peak_memory()}
    # a budget-unaware policy is *certified* against the dimension
    with pytest.raises(ValueError):
        s.plan("pm", memory_budget=budget)
    # below the sequential minimum nothing fits
    with pytest.raises(ValueError):
        s.plan("pm-bounded", memory_budget=0.5 * prob.min_peak_memory())


def test_finite_budget_refused_when_uncheckable(rng):
    """A finite budget that cannot be certified raises instead of being
    silently ignored — placement-only schedules and footprint-less
    problems alike."""
    tree = random_assembly_tree(40, rng)
    bare = Session(SharedMemory(16)).load(tree, ALPHA)
    with pytest.raises(ValueError, match="no memory footprints"):
        bare.plan("pm", memory_budget=1e6)
    placed = Session(MulticoreCluster([16, 16])).load(
        Problem.from_tree(tree, ALPHA, footprints=synthetic_footprints(tree.n))
    )
    with pytest.raises(ValueError, match="placement-only"):
        placed.plan("two-node", memory_budget=1e6)
    # an infinite budget stays a no-op on both
    assert bare.plan("pm", memory_budget=math.inf).schedule is not None
    assert placed.plan("two-node", memory_budget=math.inf).schedule is not None


def test_schedule_memory_survives_json_roundtrip():
    prob = grid_problem(11)
    s = Session(SharedMemory(16)).load(prob)
    pm_pk = s.plan("pm").schedule.peak_memory()
    budget = 0.5 * (prob.min_peak_memory() + pm_pk)
    sched = s.plan("pm-bounded", memory_budget=budget).schedule
    rt = Schedule.from_json(sched.to_json())
    assert rt.peak_memory() == sched.peak_memory()
    assert rt.memory.budget == budget
    assert rt.memory_profile() == sched.memory_profile()
    rt.validate(prob)  # deserialized timeline re-checked against entries


def test_schedule_json_version1_still_loads():
    """Old (pre-memory) schedule files keep loading; bad versions don't."""
    path = os.path.join(DATA, "schedule_golden.json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["version"] == 2 and doc["memory"] is not None
    legacy = dict(doc)
    legacy["version"] = 1
    legacy.pop("memory")
    old = Schedule.from_dict(legacy)
    assert old.memory is None
    assert old.makespan == doc["makespan"]
    with pytest.raises(ValueError):
        old.peak_memory()  # unavailable, not silently zero
    # and a v1 document round-trips through the v2 writer
    assert Schedule.from_json(old.to_json()).makespan == old.makespan
    with pytest.raises(ValueError):
        Schedule.from_dict({**doc, "version": 99})


def test_serve_memory_admission_delays_and_refuses(rng):
    tree = random_assembly_tree(30, rng)
    fp = synthetic_footprints(tree.n)
    p1 = Problem.from_tree(tree, ALPHA, name="t1", footprints=fp)
    p2 = Problem.from_tree(tree, ALPHA, name="t2", footprints=fp)
    peak = p1.min_peak_memory()
    # pool fits one tree at a time: the second is delayed, not refused
    rep = Session(SharedMemory(8)).serve(
        [(p1, 0.0), (p2, 0.0)], memory_budget=1.5 * peak
    )
    fut = rep.detail.futures
    assert fut[0].t_admit == 0.0
    assert fut[1].t_admit >= fut[0].t_done - 1e-9
    # unconstrained, both are admitted immediately
    rep2 = Session(SharedMemory(8)).serve([(p1, 0.0), (p2, 0.0)])
    assert rep2.detail.futures[1].t_admit == 0.0
    assert rep2.makespan < rep.makespan
    # a tree that can never fit is refused at submission
    with pytest.raises(ValueError):
        Session(SharedMemory(8)).serve([(p1, 0.0)], memory_budget=0.5 * peak)
    with pytest.raises(ValueError):
        Session(SharedMemory(8)).load(p1).simulate(memory_budget=0.5 * peak)


def test_simulate_attaches_memory_timeline():
    prob = grid_problem(11)
    rep = Session(SharedMemory(16)).load(prob).simulate(policy="pm")
    assert rep.schedule.peak_memory() > 0
    rep.schedule.validate(prob)


def test_execute_reports_measured_vs_projected_peak():
    prob = grid_problem(9)
    rep = (
        Session(DeviceMesh(plan_devices=8))
        .load(prob)
        .plan("greedy")
        .execute(warmup=False)
    )
    assert rep.metrics["projected_peak_bytes"] > 0
    # measured includes the kernel's 128-aligned padding, so it can only
    # be above the model's projection
    assert (
        rep.metrics["measured_peak_bytes"]
        >= rep.metrics["projected_peak_bytes"]
    )
    assert "peak memory" in rep.detail.summary()


def test_top_level_lazy_facade():
    import repro

    assert repro.Session is Session
    assert repro.SharedMemory is SharedMemory
    assert repro.Schedule is Schedule
    assert "available_policies" in dir(repro)
    assert "pm-bounded" in repro.available_policies()
    with pytest.raises(AttributeError):
        repro.not_a_facade_name


# ----------------------------------------------------------------------
# Deprecation shims
# ----------------------------------------------------------------------
SHIMS = [
    ("repro.core", "pm_schedule"),
    ("repro.sparse", "make_plan"),
    ("repro.runtime", "execute_plan"),
    ("repro.online", "OnlineScheduler"),
    ("repro.serve", "serve_online"),
]


@pytest.mark.parametrize("pkg,name", SHIMS)
def test_deprecation_shim_warns_exactly_once(pkg, name):
    import importlib

    from repro.api._deprecate import reset_warnings

    mod = importlib.import_module(pkg)
    reset_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        obj1 = getattr(mod, name)
        obj2 = getattr(mod, name)  # second access: silent
    assert obj1 is obj2
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(dep) == 1, [str(x.message) for x in w]
    assert name in str(dep[0].message)
    assert name in dir(mod)


def test_shimmed_objects_are_the_real_ones():
    import importlib

    import repro.core
    import repro.sparse
    from repro.core.pm import pm_schedule as real_pm
    from repro.sparse.plan import make_plan as real_mp

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert repro.core.pm_schedule is real_pm
        assert repro.sparse.make_plan is real_mp
    with pytest.raises(AttributeError):
        repro.core.not_a_thing
