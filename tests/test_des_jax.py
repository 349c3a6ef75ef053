"""JAX DES must match the numpy engine (f32 tolerance)."""
import numpy as np
import pytest
from hypothesis import given, settings

from conftest import gpt7b_job, one_circuit_topology, random_comm_dags
from repro.core.des import DESProblem, simulate
from repro.core.des_jax import JaxDES
from repro.core.schedule import build_comm_dag

RTOL = 5e-5  # jax runs in f32 by default


@settings(max_examples=15, deadline=None)
@given(random_comm_dags(max_pods=3, max_tasks=8))
def test_property_matches_numpy(dag):
    prob = DESProblem(dag)
    jd = JaxDES(prob)
    x = one_circuit_topology(dag)
    r = simulate(prob, x)
    ms, feas, start, finish = jd.simulate(x)
    assert feas == r.feasible
    if r.feasible:
        assert ms == pytest.approx(r.makespan, rel=RTOL)
        np.testing.assert_allclose(start, r.start, rtol=RTOL, atol=1e-9)
        np.testing.assert_allclose(finish, r.finish, rtol=RTOL, atol=1e-9)


def test_gpt7b_grid_matches_numpy():
    dag = build_comm_dag(gpt7b_job(4))
    prob = DESProblem(dag)
    jd = JaxDES(prob)
    rng = np.random.default_rng(0)
    P = dag.cluster.num_pods
    for _ in range(6):
        x = np.zeros((P, P), dtype=int)
        for i, j in dag.undirected_pairs():
            x[i, j] = x[j, i] = rng.integers(1, 3)
        r = simulate(prob, x)
        ms, feas, *_ = jd.simulate(x)
        assert feas == r.feasible
        assert ms == pytest.approx(r.makespan, rel=RTOL)


def test_batched_equals_single():
    dag = build_comm_dag(gpt7b_job(3))
    prob = DESProblem(dag)
    jd = JaxDES(prob)
    rng = np.random.default_rng(1)
    P = dag.cluster.num_pods
    xs = []
    for _ in range(8):
        x = np.zeros((P, P), dtype=int)
        for i, j in dag.undirected_pairs():
            x[i, j] = x[j, i] = rng.integers(1, 4)
        xs.append(x)
    xs = np.stack(xs)
    ms_b, feas_b = jd.batch_makespan(xs)
    for i in range(len(xs)):
        ms, feas, *_ = jd.simulate(xs[i])
        assert feas == bool(feas_b[i])
        assert ms == pytest.approx(float(ms_b[i]), rel=1e-6)


def test_ideal_mode():
    dag = build_comm_dag(gpt7b_job(3))
    prob = DESProblem(dag)
    jd = JaxDES(prob)
    x = one_circuit_topology(dag)
    ideal_np = simulate(prob, x, ideal=True).makespan
    ideal_jx = jd.makespan(x, ideal=True)
    assert ideal_jx == pytest.approx(ideal_np, rel=RTOL)
