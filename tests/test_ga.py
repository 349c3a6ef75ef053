"""DELTA GA: exactness on small instances, Alg. 5/6 properties, and the
pinned results of the four DELTA engines."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.core.ga as ga
from conftest import gpt7b_job, random_comm_dags
from repro.core.cluster import ClusterSpec
from repro.core.dag import CommDAG, DagEnsemble, make_virtual
from repro.core.ga import (GAOptions, TopologySpace, delta_fast,
                           exhaustive_search)
from repro.core.schedule import build_comm_dag


@pytest.fixture(scope="module")
def dag():
    return build_comm_dag(gpt7b_job(4))


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_ga_finds_exhaustive_optimum(dag, backend):
    x, best_ms, count = exhaustive_search(dag)
    ga = delta_fast(dag, GAOptions(seed=3, patience=20, time_limit=40,
                                   backend=backend))
    assert ga.feasible
    assert ga.makespan == pytest.approx(best_ms, rel=1e-9)


def test_ga_monotone_history(dag):
    ga = delta_fast(dag, GAOptions(seed=1, patience=10, time_limit=20))
    h = ga.history
    assert all(h[i + 1] <= h[i] + 1e-12 for i in range(len(h) - 1))


def test_feasible_random_init_always_feasible(dag):
    space = TopologySpace(dag)
    rng = np.random.default_rng(0)
    for _ in range(200):
        g = space.feasible_random_init(rng)
        assert space.is_feasible(g), g


@settings(max_examples=30, deadline=None)
@given(random_comm_dags(), st.integers(0, 2**31 - 1))
def test_property_repair_restores_feasibility(dag, seed):
    space = TopologySpace(dag)
    rng = np.random.default_rng(seed)
    wild = rng.integers(-2, 8, size=space.E)
    repaired, ok = space.repair(wild, rng)
    if ok:
        assert space.is_feasible(repaired)
    else:
        # repair only fails when reducible edges ran out: every still-over-
        # budget pod's incident edges are at the connectivity minimum
        over = space.port_usage(repaired) > space.U
        assert over.any()
        for p in np.nonzero(over)[0]:
            assert (repaired[space.inc[p].astype(bool)] == 1).all()


def test_seeding_with_baseline(dag):
    from repro.core.baselines import prop_alloc
    seed_x = prop_alloc(dag)
    ga = delta_fast(dag, GAOptions(seed=0, patience=10, time_limit=20),
                    seeds=[seed_x])
    assert ga.feasible


def test_infeasible_placement_raises():
    job = gpt7b_job(2, tp=2, gpus_per_pod_per_replica=2)
    dag_bad = build_comm_dag(job)
    with pytest.raises(ValueError, match="infeasible"):
        TopologySpace(dag_bad)


# ------------------------------------------------- pinned engine results
# Generation-bounded runs of every DELTA engine on the small gpt-7b DAGs
# (4 pods).  The literals pin the whole search: the topology, the exact
# makespans, the generation and evaluation counts, and how many exact
# numpy simulations (`repro.core.ga.simulate`) the engine runs -- the
# re-rank scores its k best distinct genomes and then the winner once more.
_PIN_OPTS = GAOptions(seed=7, pop_size=12, max_generations=6,
                      patience=10**9, time_limit=1e9)
_PINNED = {
    "delta_fast": dict(
        x=[[0, 2, 2, 0], [2, 0, 0, 1], [2, 0, 0, 0], [0, 1, 0, 0]],
        makespans=[1.1119895697066666], generations=6, evaluations=12,
        simulate_calls=9),
    # two delta_fast reference runs (9 each), then 8 + 1 genomes x 2 members
    "delta_robust": dict(
        x=[[0, 2, 2, 0], [2, 0, 0, 1], [2, 0, 0, 0], [0, 1, 0, 0]],
        makespans=[1.1119895697066666, 0.8040811983644446], generations=6,
        evaluations=12, simulate_calls=36, objective_value=1.0),
    # 8 + 1 genomes x 4 scenarios (healthy + one per active pair)
    "delta_failsafe": dict(
        x=[[0, 1, 3, 0], [1, 0, 0, 1], [3, 0, 0, 0], [0, 1, 0, 0]],
        makespans=[0.8044167426844445, 0.8048641351111111,
                   0.8044167426844445, 0.8044167426844445],
        generations=6, evaluations=10, simulate_calls=36,
        objective_value=0.8048641351111111),
    # the base delta_robust (9 x 2), then the spare lane's re-rank
    "delta_planes": dict(
        x=[[0, 1, 3, 0], [1, 0, 0, 2], [3, 0, 0, 0], [0, 2, 0, 0]],
        makespans=[1.1129962026666667, 0.8044167426844445],
        dark_makespans=[[1.1183649117866665, 0.8071010972444445],
                        [1.1129962026666667, 0.8044167426844445],
                        [1.1129962026666667, 0.8044167426844445]],
        generations=6, evaluations=5, simulate_calls=52,
        objective_value=1.0048236544807043),
}


@pytest.mark.parametrize("engine", sorted(_PINNED))
def test_engine_results_pinned(engine, small_dag, tiny_dag, monkeypatch):
    calls = []
    simulate = ga.simulate

    def counted(*args, **kw):
        calls.append(0)
        return simulate(*args, **kw)

    monkeypatch.setattr(ga, "simulate", counted)
    ens = DagEnsemble([small_dag, tiny_dag])
    res = {"delta_fast": lambda: ga.delta_fast(small_dag, _PIN_OPTS),
           "delta_robust": lambda: ga.delta_robust(ens, _PIN_OPTS),
           "delta_failsafe": lambda: ga.delta_failsafe(tiny_dag, _PIN_OPTS),
           "delta_planes": lambda: ga.delta_planes(ens, _PIN_OPTS,
                                                   num_planes=3)}[engine]()
    want = _PINNED[engine]
    assert res.x.tolist() == want["x"]
    got_ms = [res.makespan] if engine == "delta_fast" else res.makespans
    assert list(got_ms) == pytest.approx(want["makespans"], rel=1e-12)
    if "dark_makespans" in want:
        assert res.dark_makespans.ravel().tolist() == pytest.approx(
            np.ravel(want["dark_makespans"]).tolist(), rel=1e-12)
    if "objective_value" in want:
        assert res.objective_value == pytest.approx(
            want["objective_value"], rel=1e-12)
    assert res.generations == want["generations"]
    assert res.evaluations == want["evaluations"]
    assert len(calls) == want["simulate_calls"]


@pytest.mark.parametrize("engine", ["delta_fast", "delta_robust",
                                    "delta_failsafe"])
def test_engines_on_empty_pair_space(engine):
    """No inter-pod traffic: every engine skips the search and returns the
    empty topology after one exact evaluation."""
    dag = CommDAG(tasks=[make_virtual()], deps=[],
                  cluster=ClusterSpec(num_pods=2, port_limits=(2, 2),
                                      nic_bandwidth=50e9))
    res = {"delta_fast": lambda: ga.delta_fast(dag, _PIN_OPTS),
           "delta_robust": lambda: ga.delta_robust(
               DagEnsemble([dag]), _PIN_OPTS, refs=[1.0]),
           "delta_failsafe": lambda: ga.delta_failsafe(dag, _PIN_OPTS)
           }[engine]()
    assert res.x.tolist() == [[0, 0], [0, 0]]
    assert res.feasible
    assert (res.generations, res.evaluations) == (0, 1)
    assert len(res.history) == 1
