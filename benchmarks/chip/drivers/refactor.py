"""Refactorization of a fixed pattern: whole factorizations back to back.

The user is a simulation (Newton steps, time stepping) that factors the
same sparsity pattern again and again with new values.  Set-up does what
such a user does once: generate the matrix, order it, analyze it and plan
(``Session.analyze`` / ``.plan``), then one warm factorization that
compiles every shape of the pattern.  The window then runs whole numeric
factorizations through ``Session.execute()`` -- the async plan executor,
assembly, extend-add and the Pallas front kernels -- each on new link
weights drawn from ``(seed, k)``, until ``--seconds`` have passed, and
finishes the one in flight.  Each ends when the executor has copied every
factored front back to the host.

``factor_s`` is the window's elapsed time over the factorizations it
completed.  Every one of them is checked afterwards against the reference.
"""
from __future__ import annotations


class State:
    """The session and what the window produced."""

    def __init__(self, mats, sess) -> None:
        self.mats = mats  # deployment.Matrices
        self.sess = sess
        self.results: list = []  # (k, panels)

    def values(self, seed: int, k: int):
        return self.mats.values(seed, k)

    def reset(self) -> None:
        """Forget the window's results (a new window on the same set-up)."""
        self.results = []


def prepare(run) -> State:
    """Generate, order, analyze and plan (host set-up)."""
    from repro.api import DeviceMesh, Session

    cfg = run.cfg
    mats = run.deployment.Matrices(run.family, cfg)
    with run.timed("generate_s"):
        a = mats.first(run.seed)
    with run.timed("ordering_s"):
        perm = mats.order(a)
    with run.timed("analyze_s"):
        sess = Session(DeviceMesh(run.devices)).analyze(
            a, alpha=cfg["alpha"], ordering=perm, relax=cfg["symbolic"]["relax"]
        )
    with run.timed("plan_s"):
        sess.plan(policy=cfg["plan"]["policy"])
    st = State(mats, sess)
    run.layer["fronts"] = [(sn.m, sn.nb) for sn in sess.problem.symb.supernodes]
    return st


def warm(run, st: State) -> None:
    """Compile every shape of the pattern: warm factorizations, outside
    the window."""
    with run.timed("warmup_s"):
        for k in range(run.traffic["warm_factorizations"]):
            st.sess.problem.matrix = st.values(run.seed, run.deployment.WARM + k)
            st.sess.execute()


def window(run, st: State) -> None:
    """Factor back to back for ``run.seconds``; finish the one in flight."""
    reports = []
    with run.window():
        k = 0
        while True:
            k += 1
            st.sess.problem.matrix = st.values(run.seed, k)
            with run.span("bench.factorize", k=k):
                rep = st.sess.execute(warmup=False)
            st.results.append((k, rep.artifact.panels))
            reports.append(rep.detail)
            if run.elapsed() >= run.seconds:
                break
    t0, t1 = run.t_window
    run.e2e["factor_s"] = (t1 - t0) / k
    run.layer["factorizations"] = k
    run.layer["dispatches"] = [r.n_dispatches for r in reports]
    run.layer["compiles_in_window"] = run.compiles_in_window()
    run.layer["front_paths"] = front_paths(run.layer["fronts"])


def front_paths(fronts) -> dict:
    """Which kernel path each front takes, by the program's own rule
    (padded order above VMEM_FRONT_MAX takes the panel + SYRK path)."""
    from repro.kernels.frontal_cholesky import VMEM_FRONT_MAX
    from repro.kernels.ops import padded_shape

    out = {"front_factor_vmem": [], "large_front": []}
    for m, nb in fronts:
        large = padded_shape(m, nb)[0] > VMEM_FRONT_MAX
        out["large_front" if large else "front_factor_vmem"].append((m, nb))
    return out


def measure(run) -> State:
    st = prepare(run)
    warm(run, st)
    window(run, st)
    return st


def verify(run, st: State) -> None:
    """Every factorization of the window against the reference."""
    ref = run.reference
    symb = st.sess.problem.symb
    layout = ref.SupernodalLayout(
        symb.n, [sn.rows for sn in symb.supernodes],
        [sn.cols for sn in symb.supernodes],
    )
    x = ref.probes(symb.n, run.seed)
    errs = []
    for k, panels in st.results:
        try:
            errs.append(ref.backward_error(layout.l_matrix(panels),
                                           st.values(run.seed, k), x))
        except ValueError:  # a malformed answer
            errs.append(float("inf"))
    limit = run.cfg["guarantee"]["backward_error_max"]
    run.attempted = len(errs)
    run.failed = sum(1 for e in errs if not e <= limit)
    run.layer["backward_errors"] = errs
    run.check("backward_error_max", max(errs, default=float("inf")), limit)
