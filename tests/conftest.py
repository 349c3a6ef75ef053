"""Shared fixtures and hypothesis strategies.

NOTE: no XLA_FLAGS here -- smoke tests and benches must see the real
device count (1 on this container); only the dry-run forces 512.
"""
from __future__ import annotations

import numpy as np
import pytest

from hypothesis import strategies as st

from repro.core.cluster import ClusterSpec
from repro.core.dag import CommDAG, CommTask, Dep, make_virtual
from repro.core.schedule import build_comm_dag
from repro.core.traffic import JobSpec


def gpt7b_job(mb: int = 4, **kw) -> JobSpec:
    """The paper's Fig.-1 profiling setup (4 pods, 2 stages/pod)."""
    defaults = dict(name="gpt7b", tp=2, pp=4, dp=2, num_microbatches=mb,
                    micro_tokens=4096, d_model=4096,
                    stage_params=(1.75e9,) * 4,
                    gpus_per_pod_per_replica=4)
    defaults.update(kw)
    return JobSpec(**defaults)


@pytest.fixture(scope="session")
def small_dag() -> CommDAG:
    return build_comm_dag(gpt7b_job(4), 400.0)


@pytest.fixture(scope="session")
def tiny_dag() -> CommDAG:
    return build_comm_dag(gpt7b_job(2), 400.0)


# ---------------------------------------------------------------- strategies
@st.composite
def random_comm_dags(draw, max_pods: int = 4, max_tasks: int = 10):
    """Random layered inter-pod DAGs with feasible port budgets."""
    num_pods = draw(st.integers(2, max_pods))
    n = draw(st.integers(1, max_tasks))
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    tasks = [make_virtual()]
    gid = 0
    for tid in range(1, n + 1):
        src = int(rng.integers(0, num_pods))
        dst = int((src + 1 + rng.integers(0, num_pods - 1)) % num_pods)
        flows = int(rng.integers(1, 4))
        volume = float(rng.uniform(0.5, 4.0) * 1e9)
        src_g = tuple(range(gid, gid + flows))
        dst_g = tuple(range(gid + 1000, gid + 1000 + flows))
        gid += flows
        tasks.append(CommTask(tid, src, dst, flows, volume, src_g, dst_g,
                              kind="rand"))
    deps = [Dep(0, tid, float(rng.uniform(0, 0.02))) for tid in range(1, n + 1)
            if rng.random() < 0.7 or tid == 1]
    for tid in range(2, n + 1):
        if rng.random() < 0.6:
            pre = int(rng.integers(1, tid))
            deps.append(Dep(pre, tid, float(rng.uniform(0, 0.05))))
    # ensure every task is reachable from the virtual source
    reached = {0} | {d.succ for d in deps if d.pre == 0}
    for tid in range(1, n + 1):
        if tid not in reached and not any(d.succ == tid for d in deps):
            deps.append(Dep(0, tid, 0.0))
    # port budget: enough for one circuit per incident pair + slack
    pairs_at = [set() for _ in range(num_pods)]
    for t in tasks[1:]:
        key = tuple(sorted((t.src_pod, t.dst_pod)))
        pairs_at[t.src_pod].add(key)
        pairs_at[t.dst_pod].add(key)
    ports = tuple(max(2, len(p) + int(rng.integers(0, 3)))
                  for p in pairs_at)
    cluster = ClusterSpec(num_pods=num_pods, port_limits=ports,
                          nic_bandwidth=50e9)
    return CommDAG(tasks=tasks, deps=deps, cluster=cluster)


def one_circuit_topology(dag: CommDAG) -> np.ndarray:
    P = dag.cluster.num_pods
    x = np.zeros((P, P), dtype=np.int64)
    for i, j in dag.undirected_pairs():
        x[i, j] = x[j, i] = 1
    return x
