"""`correct` comes out false for the control and for each fault the plan
cells can have, planted underneath the timed path of a whole run (the
harness's look for a chip skipped)."""
from __future__ import annotations

import dataclasses
import io
import time

import numpy as np
import pytest

from perfbench.harness import bench


def run(root, control=None, workload="tiny-plan"):
    err = io.StringIO()
    res = bench.run_cell(workload, 7, 1.0, False,
                         t_start=time.perf_counter(), require_chip=False,
                         control=control, root=root, out=io.StringIO(),
                         err=err)
    return res, res["checks"]


def failing(checks) -> set:
    return {k for k, v in checks.items()
            if not (np.isfinite(v["value"]) and v["value"] <= v["limit"])}


def test_sound_run_is_correct(tiny_root):
    res, checks = run(tiny_root)
    assert res["correct"] and not failing(checks)


def test_control_one_precision_below_fails_both_gaps(tiny_root):
    res, checks = run(tiny_root, control="lower")
    assert not res["correct"]
    assert failing(checks) == {"des_gap", "oracle_gap"}


def _device(monkeypatch, broken):
    """Plants `broken(ms, feas, state)` under JaxDES.batch_genome_makespan
    before set-up, so the harness records what the broken path returns."""
    from repro.core.des_jax import JaxDES
    orig = JaxDES.batch_genome_makespan
    state = {}

    def patched(self, genomes, edge_u, edge_v, mask=None):
        ms, feas = orig(self, genomes, edge_u, edge_v, mask=mask)
        return broken(np.array(ms), np.array(feas), state)

    monkeypatch.setattr(JaxDES, "batch_genome_makespan", patched)


def answer_altered(ms, feas, state):
    return ms * (1 + 1e-3), feas


def half_batch_left_out(ms, feas, state):
    h = (len(ms) + 1) // 2
    ms[h:] = ms[0]
    return ms, feas


def state_unchanged(ms, feas, state):
    """Each call hands back what the call before it returned."""
    prev = state.get("prev")
    state["prev"] = (ms.copy(), feas.copy())
    if prev is not None and len(prev[0]) == len(ms):
        return prev
    return np.zeros_like(ms), feas


@pytest.mark.parametrize("broken", [answer_altered, half_batch_left_out,
                                    state_unchanged])
def test_broken_device_answers_are_not_correct(tiny_root, monkeypatch,
                                               broken):
    _device(monkeypatch, broken)
    res, checks = run(tiny_root)
    assert not res["correct"]
    assert "des_gap" in failing(checks)


def test_altered_host_oracle_is_not_correct(tiny_root, monkeypatch):
    import repro.core.api as api
    orig = api.simulate

    def simulate(problem, x, **kw):
        r = orig(problem, x, **kw)
        return dataclasses.replace(r, makespan=r.makespan * (1 + 1e-7))

    monkeypatch.setattr(api, "simulate", simulate)
    res, checks = run(tiny_root)
    assert not res["correct"]
    assert failing(checks) == {"oracle_gap"}


def test_plan_over_a_port_budget_is_not_correct(tiny_root, monkeypatch):
    import repro.core.api as api
    orig = api._from_des

    def from_des(dag, problem, method, x, elapsed, ideal):
        x = np.array(x)
        x[0, 1] = x[1, 0] = x[0, 1] + 100
        return orig(dag, problem, method, x, elapsed, ideal)

    monkeypatch.setattr(api, "_from_des", from_des)
    res, checks = run(tiny_root)
    assert not res["correct"]
    assert "invalid_plans" in failing(checks)


def swapped_pods(dag):
    """The first task runs the other way round its pod pair."""
    t = dag.tasks[1]
    return [dataclasses.replace(t, src_pod=t.dst_pod, dst_pod=t.src_pod,
                                src_gpus=t.dst_gpus, dst_gpus=t.src_gpus)
            if t.tid == 1 else t for t in dag.tasks], dag.deps


def moved_bytes(dag):
    """Half of one task's bytes go to another task of its pod pair."""
    a = dag.tasks[1]
    b = next(t for t in dag.tasks[2:] if (t.src_pod, t.dst_pod)
             == (a.src_pod, a.dst_pod))
    vol = {a.tid: a.volume + b.volume / 2, b.tid: b.volume / 2}
    return [dataclasses.replace(t, volume=vol[t.tid]) if t.tid in vol
            else t for t in dag.tasks], dag.deps


def rewired_dep(dag):
    """One dependency waits for a later task than the one it did."""
    have = {(d.pre, d.succ) for d in dag.deps}
    d = dag.deps[len(dag.deps) // 2]
    succ = next(s for s in range(len(dag.tasks) - 1, d.pre, -1)
                if s != d.succ and (d.pre, s) not in have)
    deps = list(dag.deps)
    deps[len(deps) // 2] = dataclasses.replace(d, succ=succ)
    return dag.tasks, deps


@pytest.mark.parametrize("broken", [swapped_pods, moved_bytes,
                                    rewired_dep])
def test_a_dag_altered_with_its_sums_kept_is_not_correct(
        tiny_root, monkeypatch, broken):
    import repro.core.schedule as schedule
    from perfbench.harness.common import dag_fingerprint
    from repro.core.dag import CommDAG
    orig = schedule.build_comm_dag
    sums = ("tasks", "deps", "pods", "active_pairs", "ports", "flows",
            "volume_bytes", "delay_s")

    def build(*a, **kw):
        dag = orig(*a, **kw)
        tasks, deps = broken(dag)
        out = CommDAG(tasks=tasks, deps=deps, cluster=dag.cluster,
                      meta=dag.meta)
        want, got = dag_fingerprint(dag), dag_fingerprint(out)
        assert all(got[k] == pytest.approx(want[k]) for k in sums)
        return out

    monkeypatch.setattr(schedule, "build_comm_dag", build)
    res, checks = run(tiny_root)
    assert not res["correct"]
    assert "invalid_plans" in failing(checks)


def test_fingerprint_ignores_how_tasks_are_numbered():
    from perfbench.harness.common import dag_fingerprint, make_job
    from perfbench.tests.conftest import TINY_CONFIG
    from repro.core.dag import CommDAG
    from repro.core.schedule import build_comm_dag
    dag = build_comm_dag(make_job(TINY_CONFIG), inter_pod_gbps=400.0)
    n = len(dag.tasks)
    new = [0] + list(range(n - 1, 0, -1))        # tid -> its new tid
    tasks = sorted((dataclasses.replace(t, tid=new[t.tid])
                    for t in dag.tasks), key=lambda t: t.tid)
    deps = [dataclasses.replace(d, pre=new[d.pre], succ=new[d.succ])
            for d in dag.deps]
    again = CommDAG(tasks=tasks, deps=deps, cluster=dag.cluster)
    assert dag_fingerprint(again) == dag_fingerprint(dag)
    assert dag_fingerprint(dag) == TINY_CONFIG["dag"]

