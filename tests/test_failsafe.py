"""DELTA-Failsafe: degraded-mode DES masks, ledger port failures, priced
repair decisions, the solver fallback chain, and journal crash recovery."""
from __future__ import annotations

import json

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import gpt7b_job, one_circuit_topology
from repro.core.des import DESProblem, simulate
from repro.core.ga import (INF, GAOptions, GAResult, delta_failsafe,
                           failure_scenarios)
from repro.core.milp import (MILPOptions, result_from_topology,
                             solve_delta_milp, solve_resilient,
                             validate_solution)
from repro.fleet import (FabricHealth, FaultInjector, FleetPlanner,
                         FleetSpec, JobArrival, LedgerError, LinkFailure,
                         LinkRecovery, PlanCache, PlaneFailure,
                         PlaneRecovery, PortFailure, PortLedger,
                         PortRecovery, fault_events_from_trace,
                         shrink_to_limits, step_failure_trace)
from repro.obs import FleetJournal
from repro.obs.journal import _json_default

GA = GAOptions(pop_size=12, max_generations=25, patience=8, time_limit=5.0,
               seed=0)

# one cache across planners: chaos traces re-solve the same tenant DAGs
_SHARED_CACHE = PlanCache()


def _job(name="j", pp=4, mb=4):
    return gpt7b_job(mb, name=name, pp=pp, stage_params=(1.75e9,) * pp)


def make_planner(pods=6, ports=16, **kw) -> FleetPlanner:
    kw.setdefault("cache", _SHARED_CACHE)
    return FleetPlanner(FleetSpec(num_pods=pods, ports_per_pod=ports),
                        ga_options=GA, seed=0, **kw)


def _history_json(planner: FleetPlanner) -> str:
    return json.dumps(planner.history, default=_json_default)


# ---------------------------------------------------------- degraded DES
def test_jax_mask_matches_numpy_oracle(small_dag):
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.core.des_jax import JaxDES
    prob = DESProblem(small_dag)
    des = JaxDES(prob)
    P = small_dag.cluster.num_pods
    x = 2 * one_circuit_topology(small_dag)
    rng = np.random.default_rng(0)
    for _ in range(4):
        mask = np.ones((P, P))
        for (i, j) in small_dag.undirected_pairs():
            if rng.random() < 0.6:
                f = float(rng.uniform(0.25, 1.0))
                mask[i, j] = mask[j, i] = f
        got = des.makespan(x, mask=mask)
        want = simulate(prob, x.astype(np.float64) * mask).makespan
        assert got == pytest.approx(want, rel=1e-4)


def test_jax_dead_link_is_inf_in_both_engines(small_dag):
    pytest.importorskip("jax")
    from repro.core.des_jax import JaxDES
    prob = DESProblem(small_dag)
    des = JaxDES(prob)
    P = small_dag.cluster.num_pods
    x = one_circuit_topology(small_dag)
    i, j = small_dag.undirected_pairs()[0]
    mask = np.ones((P, P))
    mask[i, j] = mask[j, i] = 0.0
    assert not np.isfinite(des.makespan(x, mask=mask))
    assert not np.isfinite(
        simulate(prob, x.astype(np.float64) * mask).makespan)


def test_mask_is_traced_not_recompiled(small_dag):
    pytest.importorskip("jax")
    from repro.core.des_jax import JaxDES, des_cache_stats
    prob = DESProblem(small_dag)
    des = JaxDES(prob)
    P = small_dag.cluster.num_pods
    x = one_circuit_topology(small_dag)
    des.makespan(x)                      # warm the compile bucket
    before = des_cache_stats()["misses"]
    rng = np.random.default_rng(1)
    for _ in range(5):
        mask = rng.uniform(0.3, 1.0, size=(P, P))
        mask = (mask + mask.T) / 2
        des.makespan(x, mask=mask)
    assert des_cache_stats()["misses"] == before


def test_ensemble_per_member_masks(small_dag, tiny_dag):
    pytest.importorskip("jax")
    from repro.core.des_jax import EnsembleJaxDES
    members = [small_dag, tiny_dag]
    des = EnsembleJaxDES([DESProblem(d) for d in members])
    P = small_dag.cluster.num_pods
    x = 2 * one_circuit_topology(small_dag)
    masks = np.stack([np.ones((P, P)), np.full((P, P), 0.5)])
    ms, feas = des.makespans(x, masks=masks)
    assert feas.all()
    for m, (dag, mask) in zip(ms, zip(members, masks)):
        want = simulate(DESProblem(dag),
                        x.astype(np.float64) * mask).makespan
        assert m == pytest.approx(want, rel=1e-4)


# ------------------------------------------------------- ledger failures
def test_ledger_fail_ports_escalation_and_conservation():
    led = PortLedger([8, 8])
    led.admit("a", [4, 0])
    led.commit("a", [3, 0])
    led.admit("b", [2, 2])
    led.commit("b", [2, 2])
    # pool at pod 0 is 2; failing 3 eats the pool then seizes a's surplus
    assert led.fail_ports(0, 3) == []
    led.check()
    assert led.failed[0] == 3
    assert led.account("a").seized[0] == 1
    # failing 3 more must strand someone (only allocated ports remain)
    stranded = led.fail_ports(0, 3)
    assert stranded
    # stranded tenants wire more than their reduced limits: check() fails
    # until the caller re-commits a smaller plan (what replan_reduced does)
    with pytest.raises(LedgerError):
        led.check()
    for name in stranded:
        acct = led.account(name)
        assert (acct.allocated > acct.limits).any()
        led.commit(name, np.minimum(acct.allocated, acct.limits))
    led.check()
    # restoration makes seized accounts whole first, then refills the pool
    led.restore_ports(0, 6)
    led.check()
    assert led.failed[0] == 0
    assert led.account("a").seized.sum() == 0
    assert led.account("b").seized.sum() == 0


def test_ledger_fail_ports_clamps_and_snapshot_roundtrip():
    led = PortLedger([4, 4])
    led.admit("a", [2, 1])
    led.commit("a", [1, 1])
    stranded = led.fail_ports(0, 99)   # clamped to capacity
    assert led.failed[0] == 4
    assert stranded == ["a"]
    acct = led.account("a")
    led.commit("a", np.minimum(acct.allocated, acct.limits))
    led.check()
    clone = PortLedger.from_snapshot(led.snapshot())
    assert (clone.failed == led.failed).all()
    acct, acct2 = led.account("a"), clone.account("a")
    for f in ("entitled", "donated", "granted", "allocated", "seized"):
        assert (getattr(acct, f) == getattr(acct2, f)).all()
    with pytest.raises(LedgerError):
        led.fail_ports(0, -1)


def test_shrink_to_limits_fits_and_is_deterministic():
    x = np.array([[0, 3, 2], [3, 0, 1], [2, 1, 0]], dtype=np.int64)
    limits = np.array([3, 2, 2])
    y = shrink_to_limits(x, limits)
    assert (y.sum(axis=1) <= limits).all()
    assert (y == y.T).all() and (y >= 0).all()
    assert (shrink_to_limits(x, limits) == y).all()


# -------------------------------------------------------- fault modeling
def test_fabric_health_masks_and_snapshot():
    h = FabricHealth(num_pods=3, num_planes=4)
    assert h.healthy and h.mask().min() == 1.0
    h.fail_link((0, 1), 0.5)
    h.fail_link((0, 1), 0.25)          # cumulative
    assert h.mask()[0, 1] == pytest.approx(0.25)
    h.fail_plane(2)
    assert h.plane_factor == pytest.approx(0.75)
    assert h.mask()[1, 2] == pytest.approx(0.75)
    assert h.degraded_pairs() == [(0, 1), (0, 2), (1, 2)]
    assert h.affects([1, 2])
    h2 = FabricHealth.from_snapshot(h.snapshot())
    assert np.allclose(h2.mask(), h.mask())
    h.recover_plane(2)
    h.recover_link((0, 1))
    assert h.healthy


def test_fault_injector_is_seeded_and_shared_format():
    t1 = FaultInjector(num_pods=4, seed=7).trace(20)
    t2 = FaultInjector(num_pods=4, seed=7).trace(20)
    assert t1 == t2
    assert t1 != FaultInjector(num_pods=4, seed=8).trace(20)
    steps = [ev["step"] for ev in t1]
    assert steps == sorted(steps)
    events = fault_events_from_trace(t1)
    assert len(events) == len(t1)
    # step failures ride the same trace format but go to the training loop
    from repro.distributed.fault_tolerance import FailureInjector
    mixed = t1 + step_failure_trace([3, 9])
    inj = FailureInjector.from_trace(mixed)
    assert inj.fail_at == (3, 9)
    assert len(fault_events_from_trace(mixed)) == len(t1)
    assert inj.to_trace() == step_failure_trace([3, 9])
    with pytest.raises(ValueError):
        fault_events_from_trace([{"step": 0, "kind": "nope"}])


# ------------------------------------------------------- delta_failsafe
def test_delta_failsafe_worst_case(tiny_dag):
    scen = failure_scenarios(tiny_dag, num_planes=4, k=1)
    assert len(scen) == len(tiny_dag.undirected_pairs()) + 1
    res = delta_failsafe(tiny_dag, GA, scenarios=scen)
    assert res.feasible
    assert len(res.makespans) == len(scen)
    # scenario 0 is the healthy fabric; every degraded scenario is at
    # least as slow, and the reported makespans are exact (numpy) values
    prob = DESProblem(tiny_dag)
    for m, ms in zip(scen, res.makespans):
        assert ms == pytest.approx(
            simulate(prob, res.x.astype(np.float64) * m).makespan, rel=1e-9)
        assert ms >= res.makespans[0] - 1e-9
    with pytest.raises(ValueError):
        delta_failsafe(tiny_dag, GA, objective="nope")


# ------------------------------------------------- solver fallback chain
def _force_milp_timeout(monkeypatch):
    """scipy.optimize.milp returning time-limit with NO incumbent."""
    class FakeRes:
        status = 1
        x = None
        mip_gap = None
        message = "time limit reached (no incumbent)"

    monkeypatch.setattr("repro.core.milp.milp",
                        lambda *a, **kw: FakeRes())


def test_milp_time_limit_without_incumbent_is_infeasible(tiny_dag,
                                                         monkeypatch):
    _force_milp_timeout(monkeypatch)
    res = solve_delta_milp(tiny_dag, MILPOptions(time_limit=1.0))
    assert res.status == "time_limit"
    assert not np.isfinite(res.makespan)
    assert not res.feasible          # the clean fallback trigger


def test_solve_resilient_milp_timeout_falls_back_to_ga(tiny_dag,
                                                       monkeypatch):
    _force_milp_timeout(monkeypatch)
    res = solve_resilient(tiny_dag, MILPOptions(time_limit=1.0),
                          budget_s=5.0, ga_options=GA)
    assert res.feasible and res.degraded and res.fallback_stage == "ga"
    assert validate_solution(tiny_dag, res) == []


def test_solve_resilient_solver_exception_falls_back(tiny_dag, monkeypatch):
    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        raise RuntimeError("solver crashed")

    monkeypatch.setattr("repro.core.milp.milp", boom)
    res = solve_resilient(tiny_dag, MILPOptions(time_limit=1.0),
                          budget_s=5.0, retries=1, backoff_s=0.0,
                          ga_options=GA)
    assert calls["n"] >= 2           # retried before falling back
    assert res.feasible and res.degraded and res.fallback_stage == "ga"
    assert validate_solution(tiny_dag, res) == []


def test_solve_resilient_last_resort_current_plan(tiny_dag, monkeypatch):
    _force_milp_timeout(monkeypatch)

    def ga_infeasible(dag, opts=None, **kw):
        P = dag.cluster.num_pods
        return GAResult(x=np.zeros((P, P), dtype=np.int64), makespan=INF,
                        generations=0, evaluations=0, elapsed=0.0,
                        feasible=False)

    monkeypatch.setattr("repro.core.ga.delta_fast", ga_infeasible)
    P = tiny_dag.cluster.num_pods
    mask = np.full((P, P), 0.5)
    cur = 2 * one_circuit_topology(tiny_dag)
    res = solve_resilient(tiny_dag, MILPOptions(time_limit=1.0),
                          budget_s=5.0, current_x=cur, mask=mask)
    assert res.feasible and res.degraded and res.fallback_stage == "current"
    assert (res.x == cur).all()
    # masked capacities only shrink, so the DES schedule still satisfies
    # the nominal Eq. 9 link caps of the integer topology
    assert validate_solution(tiny_dag, res) == []
    # and the masked makespan really is the degraded one
    want = simulate(DESProblem(tiny_dag),
                    cur.astype(np.float64) * mask).makespan
    assert res.makespan == pytest.approx(want, rel=1e-9)


def test_result_from_topology_is_validate_clean(tiny_dag):
    x = one_circuit_topology(tiny_dag)
    res = result_from_topology(tiny_dag, x)
    assert res.feasible
    assert validate_solution(tiny_dag, res) == []
    # an all-dead mask partitions the job: priced honestly as infeasible
    P = tiny_dag.cluster.num_pods
    dead = result_from_topology(tiny_dag, x, mask=np.zeros((P, P)))
    assert dead.status == "infeasible" and not dead.feasible


# --------------------------------------------------------- fleet repairs
def test_plane_failure_keeps_topology_uniform_haircut():
    pl = make_planner()
    pl.handle(JobArrival(name="a", job=_job("ja")))
    ms0 = pl.tenants["a"].plan.makespan
    rec = pl.handle(PlaneFailure(plane=0))
    (dec,) = rec["repairs"]
    # a dark plane scales every pair by 3/4: no rewiring can help, so the
    # priced decision keeps the topology and inflates the makespan ~4/3
    assert dec["option"] in ("keep", "rewire")
    assert pl.tenants["a"].plan.makespan >= ms0
    assert "a" in pl._degraded
    rec = pl.handle(PlaneRecovery(plane=0))
    (dec,) = rec["repairs"]
    assert dec["option"] == "healthy"
    assert pl._degraded == set()
    assert pl.tenants["a"].plan.makespan == pytest.approx(ms0, rel=1e-9)
    pl.ledger.check()


def test_dead_pair_is_priced_as_partition():
    pl = make_planner()
    pl.handle(JobArrival(name="a", job=_job("ja")))
    pair = tuple(pl.tenants["a"].dag.undirected_pairs()[0])
    rec = pl.handle(LinkFailure(pair=pair, fraction=1.0))
    (dec,) = rec["repairs"]
    # every option routes pair traffic over zero surviving capacity
    assert not np.isfinite(dec["makespan"])
    assert not np.isfinite(pl.tenants["a"].plan.makespan)
    rec = pl.handle(LinkRecovery(pair=pair))
    assert rec["repairs"][0]["option"] == "healthy"
    assert np.isfinite(pl.tenants["a"].plan.makespan)


def test_partial_link_failure_prices_all_options():
    pl = make_planner(replan_threshold=0.0)   # always price the full replan
    pl.handle(JobArrival(name="a", job=_job("ja")))
    dag = pl.tenants["a"].dag
    vol = dag.traffic_matrix()
    pair = max(dag.undirected_pairs(),
               key=lambda e: vol[e[0], e[1]] + vol[e[1], e[0]])
    rec = pl.handle(LinkFailure(pair=pair, fraction=0.75))
    (dec,) = rec["repairs"]
    assert set(dec["options"]) >= {"keep", "rewire", "replan"}
    assert dec["options"]["keep"]["delay_s"] == 0.0
    costs = {n: o["cost_s"] for n, o in dec["options"].items()}
    assert dec["cost_s"] == min(costs.values())
    # the committed plan carries the winner's exact masked pricing
    mask = pl.health.local_mask(pl.tenants["a"].pods)
    want = simulate(DESProblem(pl.tenants["a"].dag),
                    pl.tenants["a"].plan.x.astype(np.float64) * mask)
    assert pl.tenants["a"].plan.makespan == pytest.approx(want.makespan,
                                                          rel=1e-9)
    pl.ledger.check()


def test_port_failure_strands_and_recovers_through_replan():
    pl = make_planner(pods=4, ports=8)
    pl.handle(JobArrival(name="a", job=_job("ja")))
    x_before = pl.tenants["a"].plan.x.copy()
    pod = int(pl.tenants["a"].pods[0])
    rec = pl.handle(PortFailure(pod=pod, count=8))
    assert rec["stranded"] == ["a"]
    assert rec["replans"] and rec["replans"][0]["tenant"] == "a"
    limits = pl.ledger.limits("a")
    assert (pl.tenants["a"].fleet_usage(pl.fleet.num_pods) <= limits).all()
    assert "a" in pl._shrunk
    pl.ledger.check()
    rec = pl.handle(PortRecovery(pod=pod, count=8))
    assert pl.ledger.account("a").seized.sum() == 0
    assert "a" not in pl._shrunk
    # full budget back -> the cached original plan returns
    assert (pl.tenants["a"].plan.x == x_before).all()
    pl.ledger.check()


# -------------------------------------------------------- crash recovery
def _scripted_events():
    return [
        JobArrival(name="a", job=_job("ja")),
        JobArrival(name="b", job=_job("jb", pp=2), port_min=True),
        LinkFailure(pair=(0, 1), fraction=0.5),
        PlaneFailure(plane=0),
        PortFailure(pod=0, count=10),
        PortRecovery(pod=0, count=10),
        LinkRecovery(pair=(0, 1)),
        PlaneRecovery(plane=0),
    ]


def test_snapshot_journal_recovery_is_bit_identical(tmp_path):
    path = tmp_path / "journal.jsonl"
    pl = make_planner(snapshot_every=3, journal=FleetJournal(path))
    for ev in _scripted_events():
        pl.handle(ev)
    pl.journal.close()
    assert sum(1 for e in FleetJournal.load(path)
               if e["kind"] == "fleet_snapshot") >= 2

    pl2 = FleetPlanner.recover(str(path), pl.fleet, ga_options=GA, seed=0,
                               cache=PlanCache(), snapshot_every=3)
    assert _history_json(pl) == _history_json(pl2)
    assert pl.rng.bit_generator.state == pl2.rng.bit_generator.state
    assert pl.ledger.snapshot() == pl2.ledger.snapshot()
    for name, t in pl.tenants.items():
        t2 = pl2.tenants[name]
        assert (t.plan.x == t2.plan.x).all()
        assert t.plan.makespan == t2.plan.makespan
        assert t.plan.nct == t2.plan.nct


def test_recovery_without_snapshot_replays_whole_journal(tmp_path):
    path = tmp_path / "journal.jsonl"
    # both sides must start with a cold cache: a full replay re-plans the
    # arrivals, and a warm cache on one side would skip the planning work
    # (and its rng draws) that the other side performs
    pl = make_planner(journal=FleetJournal(path), cache=PlanCache())
    for ev in _scripted_events()[:4]:
        pl.handle(ev)
    pl.journal.close()
    pl2 = FleetPlanner.recover(str(path), pl.fleet, ga_options=GA, seed=0,
                               cache=PlanCache())
    assert _history_json(pl) == _history_json(pl2)


# ------------------------------------------------------------ chaos test
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_chaos_traces_preserve_invariants(seed):
    """Property: any seeded failure trace through a loaded planner keeps
    ledger conservation after every event, raises nothing, and replays
    from the journal to identical decisions."""
    pl = make_planner(snapshot_every=4)
    pl.handle(JobArrival(name="a", job=_job("ja")))
    pl.handle(JobArrival(name="b", job=_job("jb", pp=2), port_min=True))
    inj = FaultInjector(num_pods=pl.fleet.num_pods, seed=seed,
                        max_fraction=0.9)
    for ev in fault_events_from_trace(inj.trace(8)):
        pl.handle(ev)            # handle() runs ledger.check() each event
        for name in pl.tenants:
            acct = pl.ledger.account(name)
            assert (acct.allocated + acct.surplus == acct.limits).all()
    pl2 = FleetPlanner.recover(pl.journal.entries, pl.fleet, ga_options=GA,
                               seed=0, cache=_SHARED_CACHE,
                               snapshot_every=4)
    assert _history_json(pl) == _history_json(pl2)
