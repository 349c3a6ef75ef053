"""Expert-parallel all-to-all traffic in the comm DAG (MoE workloads).

Covers the per-peer EP traffic model end-to-end: one directed task per
peer of the EP group with that peer's share of the tokens (node-limited
routing included), task counts / volumes / flows on the Table-I MoE
workloads, a DP ring that leaves the sharded experts out, bit-exact
backward compatibility for ep == 1 jobs, full-vs-reduced projection
consistency, the DeepSeek-V3 parameter counts and stage split, and a
DELTA-Fast end-to-end smoke on a reduced MoE job.
"""
import collections
import dataclasses
import math

import numpy as np
import pytest

from conftest import one_circuit_topology
from repro.configs import PAPER_WORKLOADS, REGISTRY, make_job
from repro.configs.base import ArchSpec, ParallelismPlan
from repro.core.cluster import Placement
from repro.core.des import DESProblem, simulate
from repro.core.schedule import build_comm_dag
from repro.core.traffic import JobSpec
from repro.obs import TRACER


def moe_job(name: str, mb: int) -> JobSpec:
    return make_job(PAPER_WORKLOADS[name], microbatches=mb)


def tiny_moe_job(**overrides) -> JobSpec:
    defaults = dict(name="moe-tiny", tp=2, pp=2, dp=2, num_microbatches=3,
                    micro_tokens=2048, d_model=1024,
                    stage_params=(1e9, 1e9), gpus_per_pod_per_replica=4,
                    ep=2, moe_experts=4, moe_top_k=2,
                    moe_stage_layers=(2, 2))
    defaults.update(overrides)
    return JobSpec(**defaults)


def ep_tasks(dag):
    return [t for t in dag.real_tasks() if t.kind.startswith("ep_a2a")]


# ----------------------------------------------------------- volume model
@pytest.mark.parametrize("name,p_peer", [("mixtral-8x22b", 0.25),
                                         ("deepseek-671b", 0.5)])
def test_ep_a2a_volume_matches_analytic_model(name, p_peer):
    """Per-peer bytes per (microbatch, stage, direction): n_moe * tokens *
    d * (dispatch + combine bytes) * p_peer.  mixtral: top-2 of 8 experts,
    one per pod, 1 - C(7,2)/C(8,2); deepseek: 4 of 8 node groups."""
    job = moe_job(name, mb=8)
    assert job.ep_peer_share() == pytest.approx(p_peer, rel=1e-15)
    per_elem = {"mixtral-8x22b": 2 + 2, "deepseek-671b": 1 + 2}[name]
    for s in range(job.pp):
        assert job.ep_a2a_peer_volume(s) == pytest.approx(
            job.moe_stage_layers[s] * job.micro_tokens * job.d_model
            * per_elem * p_peer, rel=1e-15)


def test_mixtral_per_peer_bytes_are_the_old_fan_out_split_evenly():
    """Without a node limit, one copy per peer pod holding a selected
    expert gives mixtral the bytes its single-pair fan-out carried, split
    over the ep - 1 peers: top_k (ep-1)/ep copies of act_bytes, both ways."""
    job = moe_job("mixtral-8x22b", mb=4)
    old_total = (2 * job.moe_stage_layers[0] * job.micro_tokens
                 * job.d_model * job.act_bytes * job.moe_top_k
                 * (job.ep - 1) / job.ep)
    assert job.ep_a2a_peer_volume(0) * (job.ep - 1) == pytest.approx(
        old_total, rel=1e-12)


def test_node_limited_peer_share():
    job = tiny_moe_job(dp=4, ep=4, moe_experts=16, moe_top_k=4,
                       moe_groups=4, moe_topk_groups=3)
    assert job.ep_peer_share() == 0.75
    free = dataclasses.replace(job, moe_groups=0, moe_topk_groups=0)
    assert free.ep_peer_share() == pytest.approx(
        1 - math.comb(12, 4) / math.comb(16, 4))
    with pytest.raises(ValueError, match="one group per pod"):
        dataclasses.replace(job, moe_groups=8).ep_peer_share()
    assert dataclasses.replace(job, ep=1).ep_peer_share() == 0.0


@pytest.mark.parametrize("name,mb", [("mixtral-8x22b", 4),
                                     ("deepseek-671b", 2)])
def test_ep_a2a_tasks_counts_volumes_flows(name, mb):
    job = moe_job(name, mb)
    dag = build_comm_dag(job)
    kinds = collections.Counter(t.kind for t in dag.real_tasks())
    n_moe_stages = sum(1 for v in job.moe_stage_layers if v)
    assert n_moe_stages == job.pp  # every stage hosts an MoE layer
    peers = job.placement().ep_span - 1
    # one task per peer, per (microbatch, MoE stage, direction)
    assert kinds["ep_a2a_fwd"] == peers * mb * n_moe_stages
    assert kinds["ep_a2a_bwd"] == peers * mb * n_moe_stages
    agg = 0.0
    for t in ep_tasks(dag):
        assert t.flows == job.tp
        stage = t.tag[3]
        assert t.volume == pytest.approx(job.ep_a2a_peer_volume(stage))
        assert t.src_pod != t.dst_pod
        agg += t.volume
    analytic = 2 * peers * mb * sum(job.ep_a2a_peer_volume(s)
                                    for s in range(job.pp))
    assert agg == pytest.approx(analytic)


def test_projection_fans_out_from_pod_0_to_every_peer():
    """Replica 0's fan-out: EP tasks on 0 -> k for k = 1..7 only, each
    peer the same tasks and bytes, nothing on k -> 0 (the rotational image
    of 0 -> 8-k); 7 active pod pairs where the old projection had one."""
    job = moe_job("deepseek-671b", 2)
    dag = build_comm_dag(job)
    per_pair = collections.defaultdict(lambda: [0, 0.0])
    for t in ep_tasks(dag):
        per_pair[(t.src_pod, t.dst_pod)][0] += 1
        per_pair[(t.src_pod, t.dst_pod)][1] += t.volume
    assert sorted(per_pair) == [(0, k) for k in range(1, 8)]
    assert len({tuple(v) for v in per_pair.values()}) == 1
    assert len(dag.undirected_pairs()) == 7


def test_full_replica_builder_puts_the_all_to_all_on_every_ordered_pair():
    job = tiny_moe_job(dp=4, ep=2, moe_stage_layers=(1, 1))
    dag = build_comm_dag(job, reduce_replicas=False)
    pl = job.placement()
    pairs = {(t.src_pod, t.dst_pod) for t in ep_tasks(dag)}
    want = {(pl.pod_of(i, s), pl.pod_of(j, s)) for grp in pl.ep_groups()
            for i in grp for j in grp if i != j for s in range(job.pp)}
    assert pairs == want
    job4 = tiny_moe_job(dp=4, ep=4, moe_stage_layers=(1, 1))
    full = build_comm_dag(job4, reduce_replicas=False)
    kinds = collections.Counter(t.kind for t in full.real_tasks())
    # 4 x 3 ordered pairs per (microbatch, stage)
    assert kinds["ep_a2a_fwd"] == 12 * job4.num_microbatches * job4.pp


def test_moe_workloads_no_longer_dp_only():
    """The original bug: mixtral/deepseek pipelines fit inside one pod, so
    their DAGs carried *only* DP traffic and EP was silently dropped."""
    for name in ("mixtral-8x22b", "deepseek-671b"):
        dag = build_comm_dag(moe_job(name, 2))
        frac = dag.ep_volume_fraction()
        assert frac > 0.2, f"{name}: ep fraction {frac}"
        kinds = collections.Counter(t.kind for t in dag.real_tasks())
        assert kinds["dp"] > 0  # DP ring still present


def test_registry_moe_workloads_emit_ep_traffic():
    for name in ("grok-1-314b", "jamba-1.5-large-398b",
                 "granite-moe-1b-a400m"):
        dag = build_comm_dag(make_job(REGISTRY[name], microbatches=4))
        assert dag.ep_volume_fraction() > 0


# ------------------------------------------------------------- DP ring
def test_dp_ring_leaves_out_sharded_experts():
    """ep == dp: each expert lives on one replica, so the ring syncs only
    the parameters the replicas share; with ep == 1 it syncs them all."""
    job = moe_job("deepseek-671b", 2)
    dag = build_comm_dag(job)
    for t in dag.real_tasks():
        if t.kind == "dp":
            s = t.tag[2]
            shared = job.stage_params[s] - job.expert_stage_params[s]
            assert t.volume == pytest.approx(2 * 7 / 8 * shared * 2)
    # the experts are nearly all of DeepSeek-V3: the ring is ~40x lighter
    total = sum(job.stage_params)
    assert sum(job.expert_stage_params) > 0.97 * total
    ep1 = dataclasses.replace(job, ep=1)
    assert ep1.dp_volume(3) == pytest.approx(
        2 * 7 / 8 * job.stage_params[3] * 2)


def test_dp_ring_refuses_experts_replicated_beyond_the_ep_span():
    job = tiny_moe_job(dp=4, ep=2, expert_stage_params=(5e8, 5e8))
    with pytest.raises(ValueError, match="not modelled"):
        build_comm_dag(job)
    # no expert parameters stated: nothing to guess
    build_comm_dag(tiny_moe_job(dp=4, ep=2))


# ------------------------------------------------------- backward compat
def test_ep1_dag_bit_identical_to_pre_moe_builder():
    """ep == 1 with MoE metadata present must build exactly the DAG the
    pre-change builder produced (task list, deps, volumes)."""
    base = dict(name="gpt7b", tp=2, pp=4, dp=2, num_microbatches=4,
                micro_tokens=4096, d_model=4096,
                stage_params=(1.75e9,) * 4, gpus_per_pod_per_replica=4)
    d_plain = build_comm_dag(JobSpec(**base))
    d_moe = build_comm_dag(JobSpec(**base, ep=1, moe_experts=8,
                                   moe_top_k=2, moe_every=1,
                                   moe_stage_layers=(8,) * 4,
                                   expert_stage_params=(1e9,) * 4,
                                   moe_groups=2, moe_topk_groups=1,
                                   ep_dispatch_bytes=1))
    assert d_plain.tasks == d_moe.tasks
    assert d_plain.deps == d_moe.deps
    assert d_plain.cluster == d_moe.cluster


def test_ep1_workloads_have_no_ep_tasks():
    archs = {**PAPER_WORKLOADS,
             **{n: REGISTRY[n] for n in ("yi-6b", "qwen2.5-14b",
                                         "phi3-mini-3.8b",
                                         "whisper-large-v3")}}
    for name, arch in archs.items():
        if arch.plan.ep != 1:
            continue
        dag = build_comm_dag(make_job(arch, microbatches=4))
        assert not any(t.kind.startswith("ep_a2a")
                       for t in dag.real_tasks()), name
        assert dag.ep_volume_fraction() == 0.0


def test_moe_job_with_ep1_matches_moe_fields_stripped():
    job = dataclasses.replace(moe_job("mixtral-8x22b", 4), ep=1)
    stripped = dataclasses.replace(job, moe_experts=0, moe_top_k=0,
                                   moe_stage_layers=(),
                                   expert_stage_params=())
    d1, d2 = build_comm_dag(job), build_comm_dag(stripped)
    assert d1.tasks == d2.tasks and d1.deps == d2.deps


# ------------------------------------------------- projection consistency
@pytest.mark.parametrize("span", [2, 4])
def test_full_vs_reduced_ep_projection_consistent(span):
    """ep == dp: the single-replica projection and the full instance agree
    on the makespan when x[0,k] = x[0,span-k] (one circuit per pair), the
    condition under which replica 0's fan-out lifts to the group."""
    job = tiny_moe_job(dp=span, ep=span)
    d_red = build_comm_dag(job, reduce_replicas=True)
    d_full = build_comm_dag(job, reduce_replicas=False)
    m_red = simulate(DESProblem(d_red),
                     one_circuit_topology(d_red)).makespan
    m_full = simulate(DESProblem(d_full),
                      one_circuit_topology(d_full)).makespan
    assert m_red == pytest.approx(m_full, rel=1e-6)


def test_ep_a2a_crosses_pods_despite_single_pod_pipeline():
    # mixtral: tp*pp == gpus_per_pod_per_replica -> whole replica in one
    # pod, so PP never crosses pods but the EP a2a must
    job = moe_job("mixtral-8x22b", 4)
    assert job.placement().pods_per_replica == 1
    dag = build_comm_dag(job)
    kinds = collections.Counter(t.kind for t in dag.real_tasks())
    assert "pp_fwd" not in kinds
    assert kinds["ep_a2a_fwd"] > 0


def test_ep_spans_record_the_fan_out_and_the_prune():
    job = moe_job("deepseek-671b", 1)
    with TRACER.enabled():
        TRACER.clear()
        dag = build_comm_dag(job)
        recs = {r.name: r.attrs for r in TRACER.records}
        TRACER.clear()
    assert recs["dag.ep_a2a"]["peers"] == 7
    # 2 directions x 7 peers x 16 stages hosting an MoE layer
    assert recs["dag.ep_a2a"]["tasks"] == recs["dag.build"]["ep_tasks"] \
        == len(ep_tasks(dag)) == 224
    assert recs["dag.ep_a2a"]["bytes"] == pytest.approx(
        sum(t.volume for t in ep_tasks(dag)))
    assert recs["dag.prune"]["edges_kept"] == len(dag.deps) \
        <= recs["dag.prune"]["edges_in"]
    # the CPU keeps the loop; a TPU decides on the device (test_prune_device)
    assert recs["dag.prune"]["path"] == "host"
    assert recs["dag.prune"]["rechecked"] == 0
    assert recs["dag.prune"]["n_pad"] == 512  # 256 tasks and the source


# ------------------------------------------------------------ DeepSeek-V3
def test_deepseek_v3_parameter_counts_and_stage_split():
    cfg = PAPER_WORKLOADS["deepseek-671b"].config
    assert abs(cfg.total_params() / 671e9 - 1) < 0.005
    assert abs(cfg.total_active_params() / 37e9 - 1) < 0.02
    # one MoE layer and the 2d x d projection, apart from the main model
    assert 11e9 < cfg.mtp_params() < 12e9
    assert cfg.mtp_params(active=True) < cfg.mtp_params() / 10
    job = moe_job("deepseek-671b", 16)
    assert job.moe_stage_layers == (1,) + (4,) * 14 + (2,)
    assert sum(job.stage_params) == cfg.total_params() + cfg.mtp_params()
    # the split's largest stage is a stage of 4 MoE layers
    assert max(job.active_stage_params) == job.active_stage_params[1]


def test_stage_layers_split_is_checked():
    arch = PAPER_WORKLOADS["deepseek-671b"]
    bad = ArchSpec(arch.config, dataclasses.replace(
        arch.plan, stage_layers=(4,) * 16))
    with pytest.raises(ValueError, match="stage_layers"):
        make_job(bad)
    uneven = ArchSpec(arch.config, dataclasses.replace(arch.plan,
                                                       stage_layers=()))
    with pytest.raises(ValueError, match="not divisible"):
        make_job(uneven)
    # JSON gives a list
    plan = ParallelismPlan(tp=2, pp=2, dp=1, stage_layers=[3, 1])
    assert plan.stage_layers == (3, 1)


# --------------------------------------------------------- placement / EP
def test_placement_ep_groups_and_spans():
    p = Placement(tp=2, pp=2, dp=4, gpus_per_pod_per_replica=4, ep=2)
    assert p.ep_span == 2
    assert p.ep_groups() == [(0, 1), (2, 3)]
    pods = p.ep_group_pods((0, 1))
    assert pods == tuple(sorted({p.pod_of(r, s) for r in (0, 1)
                                 for s in range(2)}))
    cluster = p.cluster(nic_bandwidth=50e9)
    assert cluster.ep_spans == p.ep_spans()
    assert len(cluster.ep_spans) == 2


def test_placement_ep_span_saturates_at_dp():
    # jamba-style ep > dp: cross-replica span caps at dp
    p = Placement(tp=2, pp=2, dp=2, gpus_per_pod_per_replica=4, ep=4)
    assert p.ep_span == 2
    assert p.ep_groups() == [(0, 1)]


def test_bad_ep_configs_rejected():
    with pytest.raises(ValueError):
        Placement(tp=2, pp=2, dp=4, gpus_per_pod_per_replica=4, ep=3)
    with pytest.raises(ValueError):
        tiny_moe_job(dp=4, ep=3)
    with pytest.raises(ValueError):
        tiny_moe_job(moe_stage_layers=(1,))  # needs pp entries
    with pytest.raises(ValueError):
        tiny_moe_job(expert_stage_params=(1.0,))  # needs pp entries


def test_ep1_placement_has_no_groups():
    p = Placement(tp=2, pp=4, dp=2, gpus_per_pod_per_replica=4)
    assert p.ep_span == 1 and p.ep_groups() == []
    assert p.cluster(nic_bandwidth=50e9).ep_spans == ()


# ------------------------------------------------------------ end to end
def test_delta_fast_smoke_on_reduced_moe_job():
    """mixtral at 2 microbatches: 16 ports a pod for 7 EP peers and the DP
    ring.  (granite-moe-1b-a400m's 2 GPUs a pod cannot wire a circuit to
    each of its 7 EP peers: the GA refuses that placement.)"""
    from repro.core.api import optimize
    from repro.core.ga import GAOptions
    dag = build_comm_dag(moe_job("mixtral-8x22b", 2))
    res = optimize(dag, "delta-fast",
                   ga_options=GAOptions(seed=0, time_limit=15.0,
                                        patience=10))
    assert res.feasible
    assert np.isfinite(res.nct) and res.nct >= 1.0 - 1e-9
    assert res.total_ports > 0


def test_moe_dag_summary_surfaces_traffic_split():
    dag = build_comm_dag(moe_job("mixtral-8x22b", 4))
    s = dag.summary()
    assert 0.0 < s["ep_volume_fraction"] < 1.0
    by_kind = s["volume_by_kind_gb"]
    assert by_kind["ep_a2a_fwd"] > 0 and by_kind["ep_a2a_bwd"] > 0
    assert by_kind["ep_a2a_fwd"] == pytest.approx(by_kind["ep_a2a_bwd"])
