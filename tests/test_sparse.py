"""Multifrontal substrate: symbolic + numeric factorization, PM planning."""
import jax
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core.pm import tree_equivalent_lengths
from repro.sparse import (
    analyze,
    etree,
    factorize,
    grid_laplacian_2d,
    grid_laplacian_3d,
    make_plan,
    min_degree,
    nested_dissection_2d,
    partial_factor_flops,
    permute_symmetric,
    random_spd,
    replan_elastic,
    solve,
)


@pytest.fixture(autouse=True)
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def test_etree_known_example():
    """Arrow matrix: every column hangs off the last one."""
    n = 5
    a = sp.lil_matrix((n, n))
    a.setdiag(10.0)
    a[n - 1, :] = 1.0
    a[:, n - 1] = 1.0
    par = etree(a.tocsr())
    assert all(par[i] == n - 1 for i in range(n - 1))
    assert par[n - 1] == -1


@pytest.mark.parametrize("relax", [0, 2])
def test_grid_2d_factorization(relax):
    a = grid_laplacian_2d(9, 9)
    perm = nested_dissection_2d(9, 9)
    ap = permute_symmetric(a, perm)
    symb = analyze(ap, relax=relax)
    fact = factorize(ap, symb)
    l = fact.to_dense_l()
    assert np.abs(l @ l.T - ap.toarray()).max() < 1e-10
    b = np.arange(symb.n, dtype=float)
    x = solve(fact, b)
    assert np.abs(ap @ x - b).max() < 1e-8


def test_grid_3d_factorization():
    a = grid_laplacian_3d(4)
    symb = analyze(a, relax=1)
    fact = factorize(a, symb)
    l = fact.to_dense_l()
    assert np.abs(l @ l.T - a.toarray()).max() < 1e-10


@pytest.mark.parametrize("relax", [0, 2])
def test_sparse_l_and_backward_error_match_dense(relax):
    """The sparse export holds exactly the dense factor's entries, and
    the sparse ‖LLᵀ−A‖_F/‖A‖_F equals the dense computation — also for
    a factor perturbed far from exact."""
    a = grid_laplacian_2d(11, 9)
    ap = permute_symmetric(a, nested_dissection_2d(11, 9))
    symb = analyze(ap, relax=relax)
    fact = factorize(ap, symb)
    l = fact.to_dense_l()
    np.testing.assert_array_equal(fact.to_sparse_l().toarray(), l)
    dense = ap.toarray()

    def dense_err(l):
        return np.linalg.norm(l @ l.T - dense) / np.linalg.norm(dense)

    assert fact.backward_error(ap) == pytest.approx(dense_err(l), abs=1e-15)
    assert fact.backward_error(ap) < 1e-14
    fact.panels[0] = fact.panels[0] * 1.01
    l = fact.to_dense_l()
    assert fact.backward_error(ap) == pytest.approx(dense_err(l), rel=1e-12)
    assert fact.backward_error(ap) > 1e-4


def test_random_spd_min_degree(rng):
    a = random_spd(50, 4.0, rng)
    p = min_degree(a)
    assert sorted(p) == list(range(50))
    ap = permute_symmetric(a, p)
    symb = analyze(ap, relax=1)
    fact = factorize(ap, symb)
    l = fact.to_dense_l()
    assert np.abs(l @ l.T - ap.toarray()).max() < 1e-8


def test_flops_formula():
    # full Cholesky of dense m×m: ~ m³/3
    m = 64
    f = partial_factor_flops(m, m)
    assert f == pytest.approx(m**3 / 3, rel=0.1)


def test_task_tree_and_plan():
    a = grid_laplacian_2d(15, 15)
    perm = nested_dissection_2d(15, 15)
    ap = permute_symmetric(a, perm)
    symb = analyze(ap, relax=1)
    tree = symb.task_tree()
    assert tree.lengths.sum() > 0
    plan = make_plan(tree, 64, alpha=0.9)
    # precedence: every task starts after its children end
    by_task = {t.task: t for t in plan.tasks}
    for i in range(tree.n):
        p = int(tree.parent[i])
        if p >= 0:
            assert by_task[p].start >= by_task[i].end - 1e-9
    # capacity: at any start event, running device groups fit the mesh
    events = sorted({t.start for t in plan.tasks})
    for ev in events:
        used = sum(
            t.devices for t in plan.tasks if t.start <= ev < t.end
        )
        assert used <= 64
    # plan is never better than the fluid optimum
    assert plan.makespan >= plan.fluid_makespan - 1e-9


def test_wave_order_factorization_matches():
    a = grid_laplacian_2d(11, 11)
    perm = nested_dissection_2d(11, 11)
    ap = permute_symmetric(a, perm)
    symb = analyze(ap)
    tree = symb.task_tree()
    plan = make_plan(tree, 16, alpha=0.85)
    order = [t.label for w in plan.waves() for t in w if t.label >= 0]
    fact = factorize(ap, symb, order=order)
    l = fact.to_dense_l()
    assert np.abs(l @ l.T - ap.toarray()).max() < 1e-10


def test_elastic_replan_work_conservation():
    a = grid_laplacian_2d(13, 13)
    perm = nested_dissection_2d(13, 13)
    symb = analyze(permute_symmetric(a, perm), relax=1)
    tree = symb.task_tree()
    plan = make_plan(tree, 64, alpha=0.9)
    t_evt = plan.makespan * 0.4
    plan2 = replan_elastic(tree, plan, t_evt, 32, 0.9)
    # residual work is at most the original and the new plan is feasible
    assert plan2.makespan > 0
    done_before = sum(
        min(1.0, max(0.0, (t_evt - t.start) / max(t.end - t.start, 1e-12)))
        * tree.lengths[t.task]
        for t in plan.tasks
    )
    assert done_before > 0
    eq_before = tree_equivalent_lengths(tree, 0.9)[tree.root]
    assert plan2.fluid_makespan <= eq_before / 32**0.9 + 1e-9
