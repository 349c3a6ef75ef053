"""Vectorized DELTA-Fast engine: batch-op equivalence with the scalar
forms, feasibility invariants of the whole-population Alg. 5/6 ops, and
no-regression guarantees against the serial per-genome engine it
replaced (its makespans, and its port trimmer as the reference below)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import gpt7b_job, random_comm_dags
from repro.core.dag import CommDAG
from repro.core.des import DESProblem, simulate
from repro.core.ga import GAOptions, TopologySpace, delta_fast, trim_ports
from repro.core.schedule import build_comm_dag


@pytest.fixture(scope="module")
def dag():
    return build_comm_dag(gpt7b_job(4))


def serial_trim_ports(dag: CommDAG, x: np.ndarray, rel_tol: float = 1e-6
                      ) -> np.ndarray:
    """Reference greedy port trimmer: sweep the pairs in order, dropping
    one circuit at a time while the exact DES makespan stays within
    budget, until a whole sweep drops nothing."""
    problem = DESProblem(dag)
    base = simulate(problem, x).makespan
    if not np.isfinite(base):
        return x
    x = x.copy()
    budget = base * (1 + rel_tol)
    improved = True
    while improved:
        improved = False
        for i, j in dag.undirected_pairs():
            if x[i, j] <= 1:
                continue
            x[i, j] -= 1
            x[j, i] -= 1
            if simulate(problem, x).makespan <= budget:
                improved = True
            else:
                x[i, j] += 1
                x[j, i] += 1
    return x


# ----------------------------------------------------------- batch <-> scalar
def test_to_matrix_batch_matches_scalar(dag):
    space = TopologySpace(dag)
    rng = np.random.default_rng(0)
    G = space.random_init_batch(rng, 16)
    X = space.to_matrix_batch(G)
    assert X.shape == (16, space.P, space.P)
    for g, x in zip(G, X):
        ref = np.zeros((space.P, space.P), dtype=np.int64)
        for e, (i, j) in enumerate(space.edges):
            ref[i, j] = ref[j, i] = g[e]
        assert (x == ref).all()
        assert (x == x.T).all()


def test_port_usage_batch_matches_scalar(dag):
    space = TopologySpace(dag)
    rng = np.random.default_rng(1)
    G = space.random_init_batch(rng, 8)
    U = space.port_usage_batch(G)
    for g, u in zip(G, U):
        ref = np.zeros(space.P, dtype=np.int64)
        for e, (i, j) in enumerate(space.edges):
            ref[i] += g[e]
            ref[j] += g[e]
        assert (u == ref).all()


def test_genome_of_roundtrip(dag):
    space = TopologySpace(dag)
    rng = np.random.default_rng(2)
    g = space.feasible_random_init(rng)
    assert (space.genome_of(space.to_matrix(g)) == g).all()


# ------------------------------------------------------ feasibility invariants
def test_random_init_batch_always_feasible(dag):
    space = TopologySpace(dag)
    rng = np.random.default_rng(0)
    G = space.random_init_batch(rng, 256)
    assert space.is_feasible_batch(G).all()


@settings(max_examples=30, deadline=None)
@given(random_comm_dags(), st.integers(0, 2**31 - 1))
def test_property_repair_batch_restores_feasibility(dag, seed):
    """Alg. 6 on whole populations: every repaired genome satisfies
    1 <= g <= X̄ and the per-pod port budgets (== TopologySpace.is_feasible
    row-wise)."""
    space = TopologySpace(dag)
    rng = np.random.default_rng(seed)
    wild = rng.integers(-3, 9, size=(32, space.E))
    repaired, ok = space.repair_batch(wild, rng)
    assert ok.all()     # constructor guarantees all-ones is within budget
    assert space.is_feasible_batch(repaired).all()
    assert (repaired >= 1).all() and (repaired <= space.xbar).all()
    assert (space.port_usage_batch(repaired) <= space.U).all()


@settings(max_examples=20, deadline=None)
@given(random_comm_dags(), st.integers(0, 2**31 - 1))
def test_property_init_batch_feasible(dag, seed):
    space = TopologySpace(dag)
    rng = np.random.default_rng(seed)
    G = space.random_init_batch(rng, 16)
    assert space.is_feasible_batch(G).all()


# -------------------------------------------------- quality: no regression
# Makespans of the serial per-genome engine the vectorized one replaced, at
# the same seeds and options (both backends gave the same value; each run
# ended on patience, not on its time limit).
LEGACY_MAKESPAN_MB4 = 1.1119895697066666
LEGACY_MAKESPAN_MB6 = 1.4198606583466669


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_vectorized_no_worse_than_legacy(dag, backend):
    """Seeded runs of the vectorized engine must match or beat the
    pre-refactor engine's makespan on the small workloads."""
    kw = dict(seed=3, patience=20, time_limit=40, backend=backend)
    new = delta_fast(dag, GAOptions(**kw))
    assert new.feasible
    assert new.makespan <= LEGACY_MAKESPAN_MB4 * (1 + 1e-9)


def test_vectorized_no_worse_than_legacy_mb6():
    dag6 = build_comm_dag(gpt7b_job(6))
    kw = dict(seed=0, patience=15, time_limit=40)
    new = delta_fast(dag6, GAOptions(**kw))
    assert new.feasible
    assert new.makespan <= LEGACY_MAKESPAN_MB6 * (1 + 1e-9)


# --------------------------------------------------------------- trim_ports
@pytest.mark.parametrize("backend", ["auto", "jax", "numpy"])
def test_trim_ports_identical_to_legacy(dag, backend):
    """Batched trimming must reproduce the serial greedy sweep exactly:
    same accepted drops, same port count, same makespan."""
    space = TopologySpace(dag)
    g_fat, ok = space.repair(space.xbar.copy(), np.random.default_rng(0))
    assert ok
    x_fat = space.to_matrix(g_fat)
    got = trim_ports(dag, x_fat, backend=backend)
    want = serial_trim_ports(dag, x_fat)
    assert (got == want).all()
    problem = DESProblem(dag)
    assert simulate(problem, got).makespan == \
        pytest.approx(simulate(problem, want).makespan, rel=1e-12)
    assert int(got.sum()) == int(want.sum())


def test_trim_ports_keeps_makespan(dag):
    ga = delta_fast(dag, GAOptions(seed=1, patience=10, time_limit=20))
    trimmed = trim_ports(dag, ga.x)
    problem = DESProblem(dag)
    assert trimmed.sum() <= ga.x.sum()
    assert simulate(problem, trimmed).makespan <= \
        ga.makespan * (1 + 1e-5)


# --------------------------------------------------- fused genome evaluation
def test_batch_genome_makespan_matches_matrix_batch(dag):
    from repro.core.des_jax import JaxDES
    space = TopologySpace(dag)
    rng = np.random.default_rng(4)
    G = space.random_init_batch(rng, 12)
    jd = JaxDES(DESProblem(dag))
    ms_g, feas_g = jd.batch_genome_makespan(G, space.edge_u, space.edge_v)
    ms_x, feas_x = jd.batch_makespan(space.to_matrix_batch(G))
    assert (feas_g == feas_x).all()
    assert np.allclose(ms_g[feas_g], ms_x[feas_x], rtol=1e-6)


def test_dedup_cache_only_evaluates_unique(dag):
    from repro.core.ga import BatchedFitness
    space = TopologySpace(dag)
    opts = GAOptions(pop_size=8)
    fit = BatchedFitness(dag, space, opts)
    rng = np.random.default_rng(5)
    G = space.random_init_batch(rng, 4)
    pop = np.concatenate([G, G, G])      # 12 rows, 4 unique
    f1 = fit(pop)
    assert fit.evaluations <= 4
    f2 = fit(pop)                        # all hits: no new evaluations
    assert fit.evaluations <= 4
    assert (f1 == f2).all()
    assert (f1[:4] == f1[4:8]).all() and (f1[:4] == f1[8:]).all()
