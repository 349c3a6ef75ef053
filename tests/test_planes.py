"""DELTA-Planes: k-plane decomposition, staggered SLO-guarded rewires,
plane-event serde, fault-injector collision-freedom, and the fleet loop's
transition plumbing + bit-identical journal replay."""
from __future__ import annotations

import json

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import gpt7b_job, one_circuit_topology
from repro.core.cluster import ClusterSpec, split_port_budgets
from repro.core.dag import DagEnsemble
from repro.core.des import DESProblem, simulate
from repro.core.des_jax import plane_state_genomes
from repro.core.ga import GAOptions, delta_planes, split_across_planes
from repro.core.schedule import build_comm_dag
from repro.fleet import (FabricHealth, FaultInjector, FleetPlanner,
                         FleetSpec, JobArrival, PlanCache, PlaneBook,
                         PlaneFailure, PlaneRewireStep,
                         PlaneTransitionSummary, StaggeredTransition,
                         TenantLane, TrafficChange, effective_topology,
                         rebuild_event, serialize_event, split_plan)
from repro.obs import FleetJournal, plane_rewire_timeline, validate_trace
from repro.obs.journal import _json_default

GA = GAOptions(pop_size=12, max_generations=25, patience=8, time_limit=5.0,
               seed=0)


def _job(name="j", mb=4, **kw):
    return gpt7b_job(mb, name=name, **kw)


def make_planner(pods=4, ports=8, **kw) -> FleetPlanner:
    return FleetPlanner(FleetSpec(num_pods=pods, ports_per_pod=ports,
                                  nic_gbps=100.0), ga_options=GA, seed=0,
                        **kw)


# -------------------------------------------------------- budget splitting
def test_split_port_budgets_balanced_and_deterministic():
    budgets = split_port_budgets((10, 7, 4), 3)
    assert np.asarray(budgets).sum(axis=0).tolist() == [10, 7, 4]
    # remainder lands on the LOW plane ids (replay bit-identity contract)
    assert budgets == ((4, 3, 2), (3, 2, 1), (3, 2, 1))
    assert split_port_budgets((10, 7, 4), 3) == budgets
    cluster = ClusterSpec.uniform(num_pods=3, ports_per_pod=8,
                                  nic_bandwidth=50e9)
    assert np.asarray(cluster.plane_port_limits(4)).sum(axis=0).tolist() \
        == [8, 8, 8]


def test_split_across_planes_sums_budgets_and_balance():
    x = np.zeros((3, 3), dtype=np.int64)
    x[0, 1] = x[1, 0] = 7
    x[1, 2] = x[2, 1] = 3
    budgets = np.asarray(split_port_budgets((16, 16, 16), 4))
    planes = split_across_planes(x, budgets)
    assert planes.shape == (4, 3, 3)
    assert np.array_equal(planes.sum(axis=0), x)
    for p in range(4):
        assert np.array_equal(planes[p], planes[p].T)
        usage = np.triu(planes[p], k=1).sum(axis=0) \
            + np.triu(planes[p], k=1).sum(axis=1)
        assert (usage <= budgets[p]).all()
        # balanced: no plane hoards a pair (share <= ceil(c/k))
        assert planes[p][0, 1] <= -(-7 // 4)
        assert planes[p][1, 2] <= -(-3 // 4)


def test_split_across_planes_integral_infeasibility():
    """Integrality can make the per-plane split infeasible even though x
    fits the summed budgets: `split_plan` degrades to None (the fleet
    then falls back to an atomic swap)."""
    x = np.zeros((3, 3), dtype=np.int64)
    x[0, 1] = x[1, 0] = 9
    x[0, 2] = x[2, 0] = 5
    x[1, 2] = x[2, 1] = 2
    budgets = np.asarray(split_port_budgets((16, 11, 7), 4))
    with pytest.raises(ValueError):
        split_across_planes(x, budgets)
    assert split_plan(x, budgets) is None
    # generous budgets always decompose
    wide = np.asarray(split_port_budgets((64, 64, 64), 4))
    planes = split_plan(x, wide)
    assert planes is not None and np.array_equal(planes.sum(axis=0), x)


# ------------------------------------------------------- state conventions
def test_plane_state_genomes_trickle_and_blackout():
    lanes = np.array([[2.0, 0.0, 1.0],
                      [2.0, 0.0, 0.0],
                      [0.0, 0.0, 0.0]])
    states = plane_state_genomes(lanes)
    assert states.shape == (4, 3)
    total = states[0]
    assert total.tolist() == [4.0, 0.0, 1.0]
    # plane 2 carries nothing: its dark state is the full topology
    assert np.array_equal(states[3], total)
    # plane 0 dark: pair 2 is fully carried by it -> x/k trickle
    assert states[1].tolist() == [2.0, 0.0, 1.0 / 3.0]
    # an empty pair stays empty in every state
    assert all(s[1] == 0.0 for s in states)


def test_effective_topology_matches_state_conventions():
    planes = np.zeros((3, 2, 2), dtype=np.int64)
    planes[0, 0, 1] = planes[0, 1, 0] = 3
    planes[1, 0, 1] = planes[1, 1, 0] = 1
    x = planes.sum(axis=0)
    assert np.array_equal(effective_topology(planes, set()), x)
    eff0 = effective_topology(planes, {0})
    assert eff0[0, 1] == 1.0
    # planes 0+1 dark -> the pair is fully dark but plane 2 is lit: trickle
    eff01 = effective_topology(planes, {0, 1})
    assert eff01[0, 1] == pytest.approx(4.0 / 3.0)
    # ALL planes dark: true blackout, capacity 0
    assert (effective_topology(planes, {0, 1, 2}) == 0).all()


# ------------------------------------------------------------ delta_planes
def test_delta_planes_decomposition_and_dark_certification(tiny_dag):
    ens = DagEnsemble.singleton(tiny_dag)
    opts = GAOptions(pop_size=10, max_generations=8, patience=4,
                     time_limit=5.0, seed=0)
    res = delta_planes(ens, opts, num_planes=4)
    assert res.num_planes == 4
    assert np.array_equal(res.planes.sum(axis=0), res.x)
    budgets = np.asarray(res.plane_port_limits, dtype=np.int64)
    for p in range(4):
        usage = np.triu(res.planes[p], k=1).sum(axis=0) \
            + np.triu(res.planes[p], k=1).sum(axis=1)
        assert (usage <= budgets[p]).all()
    # any single plane dark keeps every member finite + bounded regret
    assert np.isfinite(res.dark_makespans).all()
    assert res.feasible and res.worst_dark_regret >= 1.0
    assert np.isfinite(res.objective_value)
    # the lane genomes ARE the planes, on the union pair list
    eu = np.asarray([e[0] for e in res.edges])
    ev = np.asarray([e[1] for e in res.edges])
    for p in range(4):
        assert np.array_equal(res.planes[p][eu, ev], res.lane_genomes[p])
    # the exact dark makespans agree with the numpy oracle on the
    # effective (trickle-convention) topology of each one-dark state
    prob = DESProblem(tiny_dag)
    for p in range(4):
        eff = effective_topology(res.planes, {p})
        assert simulate(prob, eff).makespan == res.dark_makespans[p, 0]


# ----------------------------------------------------- staggered scheduler
def _lane_fixture(dag, shrink_pairs=2):
    """A committed plan A and a shrink-style target B (always wireable),
    split across 4 planes under generous budgets."""
    P = dag.cluster.num_pods
    x_a = one_circuit_topology(dag) * 4
    x_b = x_a.copy()
    pairs = dag.undirected_pairs()[:shrink_pairs]
    for i, j in pairs:
        x_b[i, j] = x_b[j, i] = x_a[i, j] - 2
    budgets = np.asarray(split_port_budgets((64,) * P, 4))
    lane = TenantLane(name="a", dag=dag, pods=tuple(range(P)),
                      planes_a=split_plan(x_a, budgets),
                      planes_b=split_plan(x_b, budgets))
    return lane, x_a, x_b


def test_transition_commits_and_certifies_each_step(tiny_dag):
    lane, x_a, x_b = _lane_fixture(tiny_dag)
    health = FabricHealth(tiny_dag.cluster.num_pods, 4)
    tr = StaggeredTransition([lane], health, slo=3.0, transition_id="tx")
    res = tr.run()
    assert res.committed and res.status == "committed"
    assert np.array_equal(tr.mixed_planes(lane), lane.planes_b)
    assert np.array_equal(tr.mixed_planes(lane).sum(axis=0), x_b)
    # every step's recorded peak inflation is the ORACLE number: recompute
    # it from scratch from the step sequence and match bit-exactly
    prob = DESProblem(tiny_dag)
    done: list[int] = []
    for s in res.steps:
        assert s.direction == "forward" and s.transition == "tx"
        mixed = lane.planes_a.copy()
        for p in done:
            mixed[p] = lane.planes_b[p]
        ref = simulate(prob, effective_topology(mixed, set())).makespan
        ms = simulate(prob, effective_topology(mixed, {s.plane})).makespan
        assert s.peak_inflation == max(ms / ref, 1.0)
        assert s.changed_circuits > 0 and s.delay_s > 0
        done.append(s.plane)
    assert res.summary.outcome == "committed"
    assert res.summary.peak_inflation == max(
        s.peak_inflation for s in res.steps)


def test_transition_slo_breach_rolls_back_to_plan_a(tiny_dag):
    lane, x_a, _ = _lane_fixture(tiny_dag)
    health = FabricHealth(tiny_dag.cluster.num_pods, 4)
    # slo below the 1.0 inflation floor: every candidate breaches
    tr = StaggeredTransition([lane], health, slo=0.5, transition_id="tr")
    res = tr.run()
    assert res.status == "rolled_back" and not res.committed
    # the fleet is back on plan A exactly -- never stranded between plans
    assert np.array_equal(tr.mixed_planes(lane), lane.planes_a)
    assert np.array_equal(tr.mixed_planes(lane).sum(axis=0), x_a)
    assert all(s.direction == "rollback" for s in res.steps
               if s.seq >= len(res.steps) - len(tr.done))


def test_transition_reprices_against_midstream_plane_failure(tiny_dag):
    """A PlaneFailure on a not-yet-rewired plane mid-transition enters the
    next round's live pricing; the engine continues or rolls back but
    always lands on exactly plan A or plan B."""
    lane, x_a, x_b = _lane_fixture(tiny_dag)
    health = FabricHealth(tiny_dag.cluster.num_pods, 4)
    tr = StaggeredTransition([lane], health, slo=5.0)
    first = tr.step()
    assert first is not None
    victim = tr.pending[0]
    health.fail_plane(victim)
    status = "committed"
    while tr.pending:
        if tr.step() is None:
            tr.rollback()
            status = "rolled_back"
            break
    final = tr.mixed_planes(lane)
    target = lane.planes_b if status == "committed" else lane.planes_a
    assert np.array_equal(final, target)
    # doubly-dark pricing really happened: steps after the fault price the
    # candidate plane ON TOP of the failed one (peak vs the damaged ref)
    assert all(np.isfinite(s.peak_inflation) for s in tr.steps)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_random_transitions_one_plane_dark_invariant(seed):
    """Property (ISSUE S3): for random A->B plan pairs, every intermediate
    state darkens at most ONE plane beyond the fabric's own damage --
    each pair keeps >= its total minus one balanced plane share (and a
    trickle > 0 whenever it carries anything) -- and the final state
    equals plan B exactly."""
    rng = np.random.default_rng(seed)
    dag = build_comm_dag(gpt7b_job(2), 400.0)
    P = dag.cluster.num_pods
    k = 3
    budgets = np.asarray(split_port_budgets((64,) * P, k))
    base = one_circuit_topology(dag)

    def rand_x():
        x = np.zeros_like(base)
        for i, j in dag.undirected_pairs():
            c = int(rng.integers(1, 5))
            x[i, j] = x[j, i] = c
        return x

    x_a, x_b = rand_x(), rand_x()
    lane = TenantLane(name="t", dag=dag, pods=tuple(range(P)),
                      planes_a=split_plan(x_a, budgets),
                      planes_b=split_plan(x_b, budgets))
    health = FabricHealth(P, k)
    tr = StaggeredTransition([lane], health, slo=float("inf"))
    res = tr.run()
    assert res.committed
    done: list[int] = []
    for s in res.steps:
        mixed = lane.planes_a.copy()
        for p in done:
            mixed[p] = lane.planes_b[p]
        eff = effective_topology(mixed, {s.plane})
        x_mid = mixed.sum(axis=0)
        carried = x_mid > 0
        assert (eff[carried] > 0).all()              # never a blackout
        # at most one plane dark: each pair keeps total - its share
        share = mixed[s.plane]
        assert (eff[carried] >= np.minimum(
            x_mid - share, x_mid / k)[carried] - 1e-12).all()
        done.append(s.plane)
    assert np.array_equal(tr.mixed_planes(lane), lane.planes_b)
    assert sorted(done) == sorted({s.plane for s in res.steps})


# ------------------------------------------------- fault injector (S1)
def test_plane_failure_draws_are_collision_free():
    """A plane_failure is never drawn for an already-dark plane (its
    matching recovery would be ambiguous); with every plane dark the
    injector degrades the draw to a link fault instead of stalling."""
    inj = FaultInjector(num_pods=4, num_planes=2, seed=11, link_rate=0.05,
                        port_rate=0.05, plane_rate=0.9, flap_rate=0.3)
    for _ in range(3):              # trace() must reset the dark set
        dark: set[int] = set()
        saw_fallback = False
        for ev in inj.trace(40):
            if ev["kind"] == "plane_failure":
                assert ev["plane"] not in dark
                dark.add(ev["plane"])
            elif ev["kind"] == "plane_recovery":
                dark.discard(ev["plane"])
            elif len(dark) >= 2:
                saw_fallback = True
        assert saw_fallback     # both planes dark -> non-plane kinds only


# --------------------------------------------- health round-trip (S2)
@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_health_snapshot_roundtrip_under_plane_churn(seed):
    rng = np.random.default_rng(seed)
    h = FabricHealth(num_pods=5, num_planes=4)
    for _ in range(15):
        op = int(rng.integers(4))
        if op == 0:
            h.fail_plane(int(rng.integers(4)))
        elif op == 1:
            h.recover_plane(int(rng.integers(4)))
        else:
            i = int(rng.integers(5))
            j = (i + 1 + int(rng.integers(4))) % 5
            if op == 2:
                h.fail_link((i, j), float(rng.uniform(0.1, 0.8)))
            else:
                h.recover_link((i, j))
        snap = json.loads(json.dumps(h.snapshot()))    # full JSON trip
        h2 = FabricHealth.from_snapshot(snap)
        assert h2.availability() == h.availability()
        assert np.array_equal(h2.link_frac, h.link_frac)
        assert h2.dark_planes == h.dark_planes
        assert h2.plane_factor == h.plane_factor


def test_plane_event_serde_roundtrip_and_backcompat():
    step = PlaneRewireStep(transition="t3", plane=2, seq=5,
                           direction="rollback", peak_inflation=1.25,
                           delay_s=0.04, changed_circuits=4,
                           tenants=("a", "b"))
    summ = PlaneTransitionSummary(transition="t3", outcome="rolled_back",
                                  steps=6, peak_inflation=1.25,
                                  total_delay_s=0.2, tenants=("a",),
                                  planes=(0, 1, 2))
    for ev in (step, summ):
        data = json.loads(json.dumps(serialize_event(ev)))
        assert data["v"] == 3
        assert rebuild_event(data) == ev
    # fields absent from older entries take their dataclass defaults
    old = {"kind": "plane_rewire", "transition": "t0", "plane": 1, "seq": 0}
    back = rebuild_event(old)
    assert back.direction == "forward" and back.peak_inflation == 1.0
    assert rebuild_event({"kind": "plane_transition", "transition": "t0",
                          "outcome": "committed"}).planes == ()


def test_plane_book_snapshot_roundtrip():
    book = PlaneBook(3)
    planes = np.arange(12, dtype=np.int64).reshape(3, 2, 2)
    book.assign("a", planes)
    snap = json.loads(json.dumps(book.snapshot()))
    book2 = PlaneBook.from_snapshot(snap)
    assert book2.num_planes == 3
    assert np.array_equal(book2.get("a"), planes)
    assert np.array_equal(book2.total("a"), planes.sum(axis=0))
    with pytest.raises(ValueError):
        book.assign("bad", np.zeros((2, 2, 2)))


# -------------------------------------------------------------- timeline
def test_plane_rewire_timeline_is_valid_trace(tiny_dag):
    lane, _, _ = _lane_fixture(tiny_dag)
    health = FabricHealth(tiny_dag.cluster.num_pods, 4)
    res = StaggeredTransition([lane], health, slo=3.0).run()
    trace = plane_rewire_timeline(res.steps, res.summary)
    assert validate_trace(trace) == []
    assert trace["otherData"]["outcome"] == "committed"
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == len(res.steps)
    assert any(e["ph"] == "C" for e in trace["traceEvents"])
    with pytest.raises(ValueError):
        plane_rewire_timeline([])


# ------------------------------------------------------ fleet integration
def test_fleet_traffic_change_staggers_and_replays_bit_identical():
    journal = FleetJournal()
    pl = make_planner(journal=journal, cache=PlanCache())
    pl.handle(JobArrival(name="a", job=_job()))
    assert np.array_equal(pl.planes.total("a"), pl.tenants["a"].plan.x)
    rec = pl.handle(TrafficChange(
        name="a", job=_job(mb=8, micro_tokens=8192)))
    tr = rec.get("transition")
    assert tr is not None and tr["status"] == "committed"
    assert tr["reason"] == "traffic_change" and tr["steps"] > 0
    assert np.array_equal(pl.planes.total("a"), pl.tenants["a"].plan.x)
    # plane events are journaled as decision outputs (v3 schema)
    plane_records = [e for e in journal.entries
                     if e.get("kind") == "plane_event"]
    assert plane_records
    kinds = {e["event"]["kind"] for e in plane_records}
    assert kinds == {"plane_rewire", "plane_transition"}
    assert all(e["event"]["v"] == 3 for e in plane_records)
    # replay the journal on a fresh planner: bit-identical plane state
    pl2 = FleetPlanner.recover(journal.entries, pl.fleet, ga_options=GA,
                               seed=0, cache=PlanCache())
    assert pl2.planes.snapshot() == pl.planes.snapshot()
    assert json.dumps(pl2.transitions, default=_json_default) \
        == json.dumps(pl.transitions, default=_json_default)
    assert json.dumps(pl2.history, default=_json_default) \
        == json.dumps(pl.history, default=_json_default)


def test_fleet_slo_breach_reverts_to_old_topology():
    """plane_slo below any possible inflation forces every transition to
    roll back: the tenant keeps its OLD circuits (priced on the new dag)
    and the rollback is recorded."""
    pl = make_planner(plane_slo=0.5, cache=PlanCache())
    pl.handle(JobArrival(name="a", job=_job()))
    x_before = pl.tenants["a"].plan.x.copy()
    rec = pl.handle(TrafficChange(
        name="a", job=_job(mb=8, micro_tokens=8192)))
    tr = rec.get("transition")
    if tr is None:       # replan converged to the identical topology
        pytest.skip("replan kept the incumbent topology; nothing to roll")
    assert tr["status"] == "rolled_back"
    assert np.array_equal(pl.tenants["a"].plan.x, x_before)
    # the reverted plan is re-certified on the NEW dag
    prob = DESProblem(pl.tenants["a"].dag)
    assert pl.tenants["a"].plan.makespan \
        == simulate(prob, x_before).makespan
    pl.ledger.check()
    assert pl.report()["planes"]["rolled_back"] >= 1


def test_fleet_snapshot_restore_carries_plane_book():
    pl = make_planner(cache=PlanCache())
    pl.handle(JobArrival(name="a", job=_job()))
    snap = pl.snapshot()
    assert "planes" in snap and snap["transition_seq"] == \
        pl._transition_seq
    pl2 = FleetPlanner.restore(snap, pl.fleet, ga_options=GA, seed=0,
                               cache=PlanCache())
    assert pl2.planes.snapshot() == pl.planes.snapshot()
    assert pl2._transition_seq == pl._transition_seq
    # pre-v3 snapshots (no plane book) restore to an empty book that
    # `_sync_planes` rebuilds deterministically on the next event
    legacy = {k: v for k, v in snap.items()
              if k not in ("planes", "transition_seq", "transitions")}
    pl3 = FleetPlanner.restore(legacy, pl.fleet, ga_options=GA, seed=0,
                               cache=PlanCache())
    assert pl3.planes.snapshot()["lanes"] == {}
    pl3.handle(PlaneFailure(plane=2))
    assert np.array_equal(pl3.planes.total("a"), pl3.tenants["a"].plan.x)
