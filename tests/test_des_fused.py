"""Kernel-fused DES: waterfill-backend parity (segment / ref / pallas
interpret) against a pure-numpy max-min reference, bucket-padding
equivalence, the module-level compile cache, batched ensemble trimming,
and the dense max-plus dependency readiness."""
import logging

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp
from conftest import gpt7b_job, one_circuit_topology
from repro.core import des_jax
from repro.core.cluster import ClusterSpec
from repro.core.dag import CommDAG, CommTask, DagEnsemble, Dep, make_virtual
from repro.core.des import DESProblem, simulate
from repro.core.des_jax import (DESArrays, DESOptions, EnsembleJaxDES,
                                JaxDES, PadSpec, _maxmin, des_cache_clear,
                                des_cache_stats)
from repro.core.ga import trim_ports_ensemble
from repro.core.schedule import build_comm_dag

RTOL = 5e-5  # jax runs in f32 by default


# ------------------------------------------------- numpy max-min reference
def maxmin_numpy_ref(n, C, con_task, con_id, con_w, flows, active, caps):
    """Pure-numpy weighted max-min fair-share oracle (progressive filling,
    float64): the semantics every `_maxmin` backend must reproduce."""
    phi = np.zeros(n)
    unfrozen = active.copy()
    for _ in range(C + 1):
        if not unfrozen.any():
            break
        used = np.zeros(C)
        denom = np.zeros(C)
        np.add.at(used, con_id,
                  np.where(active[con_task], con_w, 0.0) * phi[con_task])
        np.add.at(denom, con_id, np.where(unfrozen[con_task], con_w, 0.0))
        slack = caps - used
        with np.errstate(divide="ignore", invalid="ignore"):
            alpha_c = np.where(denom > 0,
                               slack / np.maximum(denom, 1e-300), np.inf)
        alpha = max(float(alpha_c.min()), 0.0)
        if not np.isfinite(alpha):
            break
        phi[unfrozen] += alpha
        sat = np.isfinite(alpha_c) & (alpha_c <= alpha * (1 + 1e-9) + 1e-18)
        task_sat = np.zeros(n, dtype=bool)
        task_sat[con_task[sat[con_id]]] = True
        unfrozen = unfrozen & ~task_sat
    return flows * phi * active


def _synthetic_arrays(n, C, con_task, con_id, con_w, flows) -> DESArrays:
    """DESArrays carrying only the fields `_maxmin` consumes."""
    z = np.zeros(1, dtype=np.int32)
    return DESArrays(
        volume=jnp.ones(n), flows=jnp.asarray(flows),
        dep_pre=jnp.asarray(z), dep_succ=jnp.asarray(z),
        dep_delta=jnp.zeros(1),
        con_task=jnp.asarray(con_task, dtype=jnp.int32),
        con_id=jnp.asarray(con_id, dtype=jnp.int32),
        con_w=jnp.asarray(con_w), link_pair_a=jnp.asarray(z),
        link_pair_b=jnp.asarray(z), task_valid=jnp.ones(n, dtype=bool),
        num_cons=C, num_link_cons=0, nic_bandwidth=1.0, n=n)


@st.composite
def maxmin_instances(draw):
    """Random active-flow / capacity instances where every task belongs to
    at least one finite-capacity constraint (so filling always saturates)."""
    n = draw(st.integers(1, 12))
    C = draw(st.integers(1, 6))
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    # guarantee coverage: task m is a member of constraint m % C, plus
    # random extra memberships
    pairs = {(m % C, m) for m in range(n)}
    for _ in range(int(rng.integers(0, 2 * n + 1))):
        pairs.add((int(rng.integers(0, C)), int(rng.integers(0, n))))
    con_id, con_task = map(np.asarray, zip(*sorted(pairs)))
    con_w = rng.uniform(0.1, 3.0, size=len(con_id))
    flows = rng.uniform(1.0, 4.0, size=n)
    caps = rng.uniform(0.1, 5.0, size=C)
    active = rng.random(n) < 0.8
    return n, C, con_task, con_id, con_w, flows, active, caps


@pytest.mark.parametrize("backend", ["segment", "ref", "pallas"])
@settings(max_examples=25, deadline=None)
@given(maxmin_instances())
def test_property_maxmin_matches_numpy(backend, instance):
    n, C, con_task, con_id, con_w, flows, active, caps = instance
    arr = _synthetic_arrays(n, C, con_task, con_id, con_w, flows)
    got = np.asarray(_maxmin(arr, jnp.asarray(active), jnp.asarray(caps),
                             backend=backend, interpret=True))
    want = maxmin_numpy_ref(n, C, con_task, con_id, con_w, flows, active,
                            caps)
    # f32 vs f64 can flip a freeze decision on a near-tie, so compare with
    # a tolerance wide enough for one filling level of drift...
    assert np.allclose(got, want, rtol=5e-3, atol=1e-4)
    # ...and check the defining invariants exactly: no rate on inactive
    # tasks, non-negative rates, and no constraint over capacity
    assert (got[~active] == 0).all()
    assert (got >= 0).all()
    used = np.zeros(C)
    np.add.at(used, con_id, con_w * (got / flows)[con_task])
    assert (used <= caps * (1 + 1e-3) + 1e-4).all()


def test_maxmin_single_link_fair_share():
    """Three 1-flow tasks on one cap-2 link: each gets 2/3."""
    arr = _synthetic_arrays(3, 1, np.arange(3), np.zeros(3, dtype=int),
                            np.ones(3), np.ones(3))
    for backend in ("segment", "ref", "pallas"):
        got = np.asarray(_maxmin(arr, jnp.ones(3, dtype=bool),
                                 jnp.asarray([2.0]), backend=backend,
                                 interpret=True))
        assert np.allclose(got, 2.0 / 3.0, rtol=1e-6)


# ------------------------------------------------ engine parity on real DAGs
@pytest.fixture(scope="module")
def dag():
    return build_comm_dag(gpt7b_job(2))


def test_backends_match_numpy_end_to_end(dag):
    """Every kernel backend reproduces the numpy DES makespan through the
    full event loop (the pallas path runs in interpret mode off-TPU, so CI
    exercises the kernel body on every run)."""
    prob = DESProblem(dag)
    x = one_circuit_topology(dag)
    want = simulate(prob, x)
    x2 = x * 2
    want2 = simulate(prob, x2)
    for backend in ("segment", "ref", "pallas"):
        jd = JaxDES(prob, options=DESOptions(backend=backend,
                                             interpret=True))
        ms, feas, *_ = jd.simulate(x)
        assert feas == want.feasible
        assert ms == pytest.approx(want.makespan, rel=RTOL), backend
        # the batched (vmap) path wraps the same kernel loop
        ms_b, feas_b = jd.batch_makespan(np.stack([x, x2]))
        assert feas_b.all() == (want.feasible and want2.feasible)
        assert ms_b[0] == pytest.approx(want.makespan, rel=RTOL), backend
        assert ms_b[1] == pytest.approx(want2.makespan, rel=RTOL), backend


def test_bucket_padding_is_exact(dag):
    """Bucket-padded simulation equals the exact-shape one bit-for-bit
    (ghost tasks contribute zero to every reduction) and strips the ghost
    tasks from start/finish."""
    prob = DESProblem(dag)
    x = one_circuit_topology(dag)
    opts = dict(backend="ref")
    jd_b = JaxDES(prob, options=DESOptions(bucket=True, **opts))
    jd_e = JaxDES(prob, options=DESOptions(bucket=False, **opts))
    assert jd_b.pad.n > prob.n >= jd_e.pad.n
    ms_b, feas_b, start_b, finish_b = jd_b.simulate(x)
    ms_e, feas_e, start_e, finish_e = jd_e.simulate(x)
    assert ms_b == ms_e and feas_b == feas_e
    assert start_b.shape == (prob.n,) and finish_b.shape == (prob.n,)
    np.testing.assert_array_equal(start_b, start_e)
    np.testing.assert_array_equal(finish_b, finish_e)


def test_pad_spec_quantization():
    spec = PadSpec(n=17, d=40, e=48, links=6, cons=22)
    b = spec.bucketed(DESOptions(bucket_quantum=64,
                                 bucket_quantum_cons=8).resolve())
    assert b == PadSpec(n=64, d=64, e=64, links=8, cons=24)
    # already-aligned sizes stay put
    assert b.bucketed(DESOptions(bucket_quantum=64,
                                 bucket_quantum_cons=8).resolve()) == b


# --------------------------------------------------------- compile cache
def test_compile_cache_shared_across_instances(dag):
    des_cache_clear()
    prob = DESProblem(dag)
    opts = DESOptions(backend="ref", bucket=True)
    JaxDES(prob, options=opts)
    stats = des_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 0
    JaxDES(prob, options=opts)           # same bucket: no recompile
    JaxDES(DESProblem(dag), options=opts)
    stats = des_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 2
    assert stats["entries"] == 1


def test_compile_cache_miss_warns(dag, caplog):
    des_cache_clear()
    prob = DESProblem(dag)
    with caplog.at_level(logging.WARNING, logger="repro.des_jax"):
        JaxDES(prob, options=DESOptions(backend="ref",
                                        warn_on_miss=True))
    assert any("compile-cache miss" in r.message for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="repro.des_jax"):
        JaxDES(prob, options=DESOptions(backend="ref",
                                        warn_on_miss=True))
    assert not caplog.records           # hit: silent


def test_ensemble_bucket_shares_member_shapes(dag):
    """Two ensembles whose members land in the same bucket share one
    compiled entry."""
    des_cache_clear()
    p2 = DESProblem(dag)
    p3 = DESProblem(build_comm_dag(gpt7b_job(3)))
    opts = DESOptions(backend="ref", bucket=True)
    EnsembleJaxDES([p2, p3], options=opts)
    EnsembleJaxDES([p3, p2], options=opts)
    stats = des_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 1


# ------------------------------------------------- batched ensemble trimming
def _wide_member(cluster, volumes) -> CommDAG:
    tasks = [make_virtual()]
    deps = []
    tid = 0
    gid = 0
    P = cluster.num_pods
    for i in range(P):
        for j in range(i + 1, P):
            tid += 1
            v = float(volumes[(i * P + j) % len(volumes)])
            tasks.append(CommTask(tid, i, j, 2, v, (gid, gid + 1),
                                  (gid + 500, gid + 501), kind="wide"))
            gid += 2
            deps.append(Dep(0, tid, 0.0))
    return CommDAG(tasks=tasks, deps=deps, cluster=cluster)


@pytest.fixture(scope="module")
def wide_ensemble():
    P = 7                                # 21 undirected pairs (>= 16)
    cluster = ClusterSpec(num_pods=P, port_limits=(40,) * P,
                          nic_bandwidth=50e9)
    rng = np.random.default_rng(7)
    a = _wide_member(cluster, rng.uniform(0.5, 2.0, 21) * 1e9)
    b = _wide_member(cluster, rng.uniform(0.5, 2.0, 21) * 1e9)
    return DagEnsemble([a, b], names=["a", "b"])


def test_trim_ports_ensemble_batched_matches_serial(wide_ensemble):
    """The batched candidates-x-members sweep reproduces the serial
    member-by-member sweep exactly on a wide fabric."""
    pairs = wide_ensemble.undirected_pairs()
    P = wide_ensemble.cluster.num_pods
    x = np.zeros((P, P), dtype=np.int64)
    for i, j in pairs:
        x[i, j] = x[j, i] = 3
    got = trim_ports_ensemble(wide_ensemble, x, backend="jax")
    want = trim_ports_ensemble(wide_ensemble, x, backend="numpy")
    assert (got == want).all()
    assert got.sum() < x.sum()           # the sweep had real work to do
    # budgets hold for every member
    base = [simulate(DESProblem(m), x).makespan
            for m in wide_ensemble.members]
    for m, b in zip(wide_ensemble.members, base):
        assert simulate(DESProblem(m), got).makespan <= b * (1 + 1e-6)


def test_trim_ports_ensemble_off_pair_circuits_stay_serial():
    """Circuits outside the union pairs are invisible to the genome
    scatter: the batched path must refuse and fall back to the serial
    sweep (identical result, off-pair circuits preserved)."""
    P = 7
    cluster = ClusterSpec(num_pods=P, port_limits=(40,) * P,
                          nic_bandwidth=50e9)
    # members only touch pods 1..6, so pair (0, 1) is outside the union
    rng = np.random.default_rng(3)

    def member(volumes):
        tasks, deps = [make_virtual()], []
        tid = gid = 0
        for i in range(1, P):
            for j in range(i + 1, P):
                tid += 1
                v = float(volumes[tid % len(volumes)])
                tasks.append(CommTask(tid, i, j, 2, v, (gid, gid + 1),
                                      (gid + 500, gid + 501), kind="wide"))
                gid += 2
                deps.append(Dep(0, tid, 0.0))
        return CommDAG(tasks=tasks, deps=deps, cluster=cluster)

    ens = DagEnsemble([member(rng.uniform(0.5, 2.0, 15) * 1e9),
                       member(rng.uniform(0.5, 2.0, 15) * 1e9)])
    x = np.zeros((P, P), dtype=np.int64)
    for i, j in ens.undirected_pairs():
        x[i, j] = x[j, i] = 3
    x[0, 1] = x[1, 0] = 2                # off-union circuits
    got = trim_ports_ensemble(ens, x, backend="jax")
    want = trim_ports_ensemble(ens, x, backend="numpy")
    assert (got == want).all()
    assert got[0, 1] == 2 and got[1, 0] == 2


# --------------------------------------------------------- fleet ref cache
def test_fleet_robust_refs_come_from_plan_cache():
    """plan_robust's max-regret reference runs are the members' single-DAG
    plans: they must be served by the fleet PlanCache, not re-solved."""
    from repro.core.ga import GAOptions
    from repro.fleet import FleetPlanner, FleetSpec, JobArrival, TrafficChange

    opts = GAOptions(seed=0, pop_size=12, max_generations=4, patience=10**9,
                     time_limit=30.0)
    fp = FleetPlanner(FleetSpec(num_pods=4, ports_per_pod=8),
                      ga_options=opts, robust_replan=True)
    fp.handle(JobArrival(name="j", job=gpt7b_job(2)))
    rec = fp.handle(TrafficChange(name="j",
                                  job=gpt7b_job(2, micro_tokens=16384)))
    assert rec["robust"] and rec["robust_members"] == 2
    details = fp.tenants["j"].plan.details
    # the incumbent phase's ref was already in the cache from admission
    assert details["ref_cache_hits"] >= 1
    # flipping back re-solves only the robust plan (the primary DAG hash
    # changed) -- BOTH member refs come from the cache
    misses_before = fp.cache.misses
    rec2 = fp.handle(TrafficChange(name="j", job=gpt7b_job(2)))
    assert rec2["robust"]
    assert fp.cache.misses == misses_before + 1
    assert fp.tenants["j"].plan.details["ref_cache_hits"] == 2


# ----------------------------------------- dense dependency readiness
def _dark_first_pair(dag):
    P = dag.cluster.num_pods
    mask = np.ones((P, P), dtype=np.float32)
    i, j = dag.undirected_pairs()[0]
    mask[i, j] = mask[j, i] = 0.0
    return mask


def _assert_matches_oracle(prob, x, ms, feas, start, finish, ideal=False):
    """The device loop against the float64 numpy DES: feasibility, the
    makespan and every task's start and finish."""
    want = simulate(prob, x, ideal=ideal)
    assert bool(feas) == want.feasible
    if want.feasible:
        assert float(ms) == pytest.approx(want.makespan, rel=RTOL)
        np.testing.assert_allclose(start, want.start, rtol=RTOL, atol=1e-9)
        np.testing.assert_allclose(finish, want.finish, rtol=RTOL,
                                   atol=1e-9)


READINESS_CASES = {
    # bucket padding: ghost tasks and ghost deps in the lag matrix
    "bucketed": dict(mb=2),
    "exact-shape": dict(mb=3, bucket=False),
    "ideal": dict(mb=2, ideal=True),
    "doubled-circuits": dict(mb=4, scale=2),
    "dark-link": dict(mb=2, dark=True),
}


@pytest.mark.parametrize("case", list(READINESS_CASES))
def test_dense_readiness_matches_the_float64_oracle(case):
    """The event loop with the dense max-plus readiness pass reproduces the
    numpy DES: makespan, feasibility, start and finish; a dark link on a
    pair that carries traffic comes out infeasible."""
    c = READINESS_CASES[case]
    dag = build_comm_dag(gpt7b_job(c["mb"]))
    prob = DESProblem(dag)
    x = one_circuit_topology(dag) * c.get("scale", 1)
    ideal = c.get("ideal", False)
    jd = JaxDES(prob, options=DESOptions(backend="ref",
                                         bucket=c.get("bucket", True)))
    if c.get("bucket", True):
        assert jd.pad.n > prob.n and jd.pad.d > len(prob.dep_pre)
    mask = _dark_first_pair(dag) if c.get("dark") else None
    ms, feas, start, finish, trips = [
        np.asarray(o) for o in jd._compiled.single(
            jd._leaves, jnp.asarray(x), jnp.asarray(ideal), jd._mask(mask))]
    assert trips > 0
    if c.get("dark"):
        assert not feas and ms == np.inf
        return
    n = prob.n
    _assert_matches_oracle(prob, x, ms, feas, start[:n], finish[:n],
                           ideal=ideal)


def test_dense_readiness_on_ensemble_genomes(dag):
    """One members x genomes call (two stacked DAGs of different sizes,
    one of them with a dark link) matches the oracle member by member."""
    probs = [DESProblem(dag), DESProblem(build_comm_dag(gpt7b_job(3)))]
    pairs = sorted(set(dag.undirected_pairs())
                   | set(probs[1].dag.undirected_pairs()))
    eu = np.array([p[0] for p in pairs], dtype=np.int32)
    ev = np.array([p[1] for p in pairs], dtype=np.int32)
    genomes = np.random.default_rng(4).integers(1, 4, (5, len(pairs)))
    P = dag.cluster.num_pods
    masks = np.ones((2, P, P), dtype=np.float32)
    masks[1] = _dark_first_pair(probs[1].dag)
    ens = EnsembleJaxDES(probs, options=DESOptions(backend="ref"))
    ms, feas, trips = [np.asarray(o) for o in ens._compiled.ensemble_genomes(
        ens._leaves, jnp.asarray(genomes), jnp.asarray(eu), jnp.asarray(ev),
        jnp.asarray(masks))]
    assert ms.shape == (5, 2) and feas[:, 0].all() and not feas[:, 1].any()
    assert (trips > 0).all()
    for g, genome in enumerate(genomes):
        x = np.zeros((P, P), dtype=np.int64)
        x[eu, ev] = x[ev, eu] = genome
        want = simulate(probs[0], x).makespan
        assert ms[g, 0] == pytest.approx(want, rel=RTOL)


def _scatter_ready(pre, succ, delta, finish):
    """Float32 reference readiness, one dependency at a time."""
    ready = np.zeros(len(finish), dtype=np.float32)
    np.maximum.at(ready, succ, (finish[pre] + delta).astype(np.float32))
    return ready


def test_dense_readiness_takes_the_largest_lag_of_duplicate_deps():
    """Duplicate (pre, succ) pairs and ghost deps (0 -> 0, delta 0) land in
    one cell of the lag matrix, which keeps the largest delta; readiness
    equals the per-dependency float32 max bit for bit."""
    pre = np.array([0, 1, 1, 1, 2, 0, 0], dtype=np.int32)
    succ = np.array([1, 3, 3, 2, 3, 2, 0], dtype=np.int32)
    delta = np.array([0.5, 0.25, 2.0, 1.0, 0.125, 3.0, 0.0],
                     dtype=np.float32)
    arr = _synthetic_arrays(4, 1, np.zeros(1), np.zeros(1), np.ones(1),
                            np.ones(4))._replace(
        dep_pre=jnp.asarray(pre), dep_succ=jnp.asarray(succ),
        dep_delta=jnp.asarray(delta))
    ready_at = jax.jit(des_jax._ready_fn(arr))
    for finish in ([0.0, 1.5, np.inf, np.inf], [0.0, 1.5, 4.0, np.inf],
                   [0.0, 1.0 / 3.0, 7.1, 9.0]):
        finish = np.asarray(finish, dtype=np.float32)
        np.testing.assert_array_equal(np.asarray(ready_at(finish)),
                                      _scatter_ready(pre, succ, delta,
                                                     finish))
    got = np.asarray(ready_at(np.array([0.0, 1.5, 4.0, np.inf],
                                       dtype=np.float32)))
    assert got[1] == 0.5 and got[2] == 3.0 and got[3] == np.float32(4.125)


def test_finite_ready_time_is_the_missing_count_gate():
    """With no `missing` counter, a task counts as having every
    predecessor done when its ready time is finite.  Replay a reference
    run: at each of its completion times the done set is the tasks that
    have finished by then, and at every such state the dense readiness
    equals the per-dependency float32 max bit for bit, and `ready < INF`
    equals `missing == 0` for every task."""
    dag = build_comm_dag(gpt7b_job(4))
    jd = JaxDES(DESProblem(dag), options=DESOptions(backend="ref"))
    arr = jd.arrays
    x = one_circuit_topology(dag)
    _, feas, _, finish, trips = [np.asarray(o) for o in jd._compiled.single(
        jd._leaves, jnp.asarray(x), jnp.asarray(False), jd._mask(None))]
    assert feas and trips > 0 and np.isfinite(finish).all()
    pre, succ = np.asarray(arr.dep_pre), np.asarray(arr.dep_succ)
    delta = np.asarray(arr.dep_delta)
    indeg = np.bincount(succ, minlength=arr.n)
    ready_at = jax.jit(des_jax._ready_fn(arr))
    times = np.unique(finish)
    assert len(times) > 10
    for t in times:
        done = finish <= t
        missing = indeg - np.bincount(succ[done[pre]], minlength=arr.n)
        fin_t = np.where(done, finish, np.inf).astype(np.float32)
        ready = np.asarray(ready_at(fin_t))
        np.testing.assert_array_equal(ready,
                                      _scatter_ready(pre, succ, delta, fin_t))
        np.testing.assert_array_equal(ready < np.inf, missing == 0)
