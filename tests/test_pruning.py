"""Algs. 1/2/4: windows, bounds, closures."""
import numpy as np
import pytest
from hypothesis import given, settings

from conftest import gpt7b_job, one_circuit_topology, random_comm_dags
from repro.core.cluster import ClusterSpec
from repro.core.dag import CommDAG, CommTask, Dep, make_virtual
from repro.core.des import DESProblem, simulate
from repro.core.pruning import (cal_task_time_windows, estimate_t_up,
                                profile_anchors, task_time_index_pruning)
from repro.core.schedule import build_comm_dag
from repro.core.xbound import (mwis, reachability_bitset,
                               reachability_kernel, x_upper_bound)


@pytest.fixture(scope="module")
def dag():
    return build_comm_dag(gpt7b_job(4))


def test_est_lct_windows_are_consistent(dag):
    prob = DESProblem(dag)
    t_up = estimate_t_up(prob)
    est, lct = cal_task_time_windows(dag, t_up)
    assert (est[1:] <= lct[1:] + 1e-9).all()
    # the baseline schedule fits inside the windows
    res = simulate(prob, one_circuit_topology(dag))
    for t in dag.real_tasks():
        assert res.start[t.tid] >= est[t.tid] - 1e-9
        assert res.finish[t.tid] <= lct[t.tid] + 1e-9


def test_index_windows_contain_baseline(dag):
    prob = DESProblem(dag)
    res, anchors, K = profile_anchors(prob)
    w = task_time_index_pruning(dag, K, anchors)
    ti = res.task_interval
    for m in range(1, dag.num_tasks):
        assert w.k_min[m] <= ti[m, 0] <= ti[m, 1] <= w.k_max[m]


def test_pruning_reduces_search_space(dag):
    prob = DESProblem(dag)
    _, anchors, K = profile_anchors(prob)
    w = task_time_index_pruning(dag, K, anchors)
    dense = dag.num_real_tasks * K
    assert w.num_task_intervals() < 0.3 * dense


def test_empty_windows_raise_instead_of_silent_repair():
    """A rigid-delta chain needing 3 intervals with K=2 is infeasible; the
    old order (clip into [1, K] *then* check) silently repaired k_max < 1
    / k_min > K into [1, 1] / [K, K] instead of raising."""
    tasks = [make_virtual(),
             CommTask(1, 0, 1, 1, 1e9, (0,), (100,), kind="rand"),
             CommTask(2, 1, 0, 1, 1e9, (101,), (1,), kind="rand")]
    deps = [Dep(0, 1, 0.0), Dep(1, 2, 0.01)]  # delta > 0 -> index bump 2
    cluster = ClusterSpec(num_pods=2, port_limits=(2, 2),
                          nic_bandwidth=50e9)
    dag = CommDAG(tasks=tasks, deps=deps, cluster=cluster)
    with pytest.raises(ValueError, match="empty index windows"):
        task_time_index_pruning(dag, K=2)
    w = task_time_index_pruning(dag, K=3)  # K=3 is genuinely feasible
    assert (w.k_min[1:] <= w.k_max[1:]).all()


@settings(max_examples=20, deadline=None)
@given(random_comm_dags(max_tasks=9))
def test_property_closure_backends_agree(dag):
    assert (reachability_bitset(dag) == reachability_kernel(dag)).all()


def test_mwis_exact_small():
    # path graph a-b-c with weights 2,3,2 -> {a,c}=4 > {b}=3
    w = np.array([2.0, 3.0, 2.0])
    adj = np.array([[0, 1, 0], [1, 0, 1], [0, 1, 0]], dtype=bool)
    assert mwis(w, adj) == pytest.approx(4.0)
    # triangle: best single vertex
    adj2 = ~np.eye(3, dtype=bool)
    assert mwis(w, adj2) == pytest.approx(3.0)
    # empty graph: everything
    assert mwis(w, np.zeros((3, 3), bool)) == pytest.approx(7.0)


def _mwis_brute(w, adj):
    k = len(w)
    best = 0.0
    for mask in range(1 << k):
        chosen = [i for i in range(k) if mask >> i & 1]
        if not adj[np.ix_(chosen, chosen)].any():
            best = max(best, float(w[chosen].sum()))
    return best


def test_mwis_floor_is_max_of_floor_and_exact_weight():
    rng = np.random.default_rng(7)
    for _ in range(40):
        k = int(rng.integers(1, 9))
        w = rng.integers(1, 5, k).astype(float)
        upper = np.triu(rng.random((k, k)) < 0.4, 1)
        adj = upper | upper.T
        exact = _mwis_brute(w, adj)
        assert mwis(w, adj) == exact
        for floor in (0.0, exact - 1, exact, exact + 2):
            assert mwis(w, adj, floor=max(floor, 0.0)) == max(floor, exact)


def _xbound_every_interval(dag, exact_limit):
    """Alg. 2 as written: solve every co-window interval of every pair."""
    from repro.core.xbound import reachability
    P = dag.cluster.num_pods
    xbar = np.zeros((P, P), dtype=np.int64)
    est, lct = cal_task_time_windows(dag, estimate_t_up(DESProblem(dag)))
    reach = reachability(dag)
    excl = reach | reach.T
    for (u, v), tids in dag.tasks_on_pair().items():
        tids = np.asarray(tids)
        bounds = np.unique(np.concatenate([est[tids], lct[tids]]))
        flows = dag.flows()[tids]
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            mid = 0.5 * (lo + hi)
            sel = (est[tids] <= mid) & (mid < lct[tids])
            if sel.any():
                sub = excl[np.ix_(tids[sel], tids[sel])]
                cmax = mwis(flows[sel], sub, exact_limit=exact_limit)
                xbar[u, v] = max(xbar[u, v], int(np.ceil(cmax)))
    xbar = np.maximum(xbar, xbar.T)
    U = np.asarray(dag.cluster.port_limits)
    for i, j in dag.undirected_pairs():
        xbar[i, j] = xbar[j, i] = max(1, min(xbar[i, j], min(U[i], U[j])))
    return xbar


def _sparse_dag(rng):
    """Tasks on a few pods with few dependencies, so many co-windowed
    tasks are independent and the port cap is often reached."""
    pods = int(rng.integers(2, 4))
    n = int(rng.integers(4, 25))
    tasks = [make_virtual()]
    for tid in range(1, n + 1):
        src = int(rng.integers(0, pods))
        dst = int((src + 1 + rng.integers(0, pods - 1)) % pods)
        f = int(rng.integers(1, 4))
        g = tuple(range(10 * tid, 10 * tid + f))
        tasks.append(CommTask(tid, src, dst, f,
                              float(rng.uniform(0.5, 4.0) * 1e9), g,
                              tuple(x + 5000 for x in g), kind="rand"))
    deps = [Dep(0, tid, float(rng.uniform(0, 0.05)))
            for tid in range(1, n + 1)]
    for tid in range(2, n + 1):
        if rng.random() < 0.2:
            deps.append(Dep(int(rng.integers(1, tid)), tid,
                            float(rng.uniform(0, 0.05))))
    ports = tuple(int(rng.integers(2, 16)) for _ in range(pods))
    return CommDAG(tasks=tasks, deps=deps, cluster=ClusterSpec(
        num_pods=pods, port_limits=ports, nic_bandwidth=50e9))


@pytest.mark.parametrize("exact_limit", [40, 2])
def test_xbound_equals_every_interval_scan(exact_limit):
    """Solving the heaviest intervals first and stopping at the port cap
    gives the bound that solving every interval gives, on the exact and
    on the greedy path."""
    rng = np.random.default_rng(11)
    for _ in range(60):
        d = _sparse_dag(rng)
        assert (x_upper_bound(d, exact_limit=exact_limit)
                == _xbound_every_interval(d, exact_limit)).all()


@pytest.mark.parametrize("exact_limit", [40, 2])
@settings(max_examples=25, deadline=None)
@given(random_comm_dags(max_pods=3, max_tasks=16))
def test_property_xbound_equals_every_interval_scan(exact_limit, dag):
    assert (x_upper_bound(dag, exact_limit=exact_limit)
            == _xbound_every_interval(dag, exact_limit)).all()


def test_xbound_upper_bounds_des_concurrency(dag):
    """Alg. 2's bound must dominate any simultaneous flow weight the DES
    actually achieves on an abundant topology."""
    prob = DESProblem(dag)
    xbar = x_upper_bound(dag)
    x = one_circuit_topology(dag) * 8
    U = np.array(dag.cluster.port_limits)
    res = simulate(prob, np.minimum(x, np.minimum.outer(U, U)),
                   record_rates=True)
    flows = dag.flows()
    for _t0, _t1, rates in res.rate_trace:
        active = rates > 0
        for i, j in dag.pod_pairs():
            tids = [t.tid for t in dag.real_tasks()
                    if t.pair == (i, j) and active[t.tid]]
            conc = sum(flows[m] for m in tids)
            cap = min(U[i], U[j])
            assert min(conc, cap) <= xbar[i, j] + 1e-9


def test_xbound_within_ports(dag):
    xbar = x_upper_bound(dag)
    U = np.array(dag.cluster.port_limits)
    for i, j in dag.undirected_pairs():
        assert 1 <= xbar[i, j] <= min(U[i], U[j])
        assert xbar[i, j] == xbar[j, i]
