"""The program's model of a DeepSeek-V3 job against the plain reference
(`harness/traffic_reference.py`): parameter counts exactly, the
parameters of each stage, and the projected DAG's tasks, flows and bytes
by task kind and ordered pod pair, on seeded small DeepSeek-shaped jobs
and, counts and sums only, at the configuration's size.  The old
single-pair projection, or experts in the DP ring, fails the comparison."""
from __future__ import annotations

import collections
import dataclasses
import json
import math

import numpy as np
import pytest

from perfbench.harness import traffic_reference as ref
from perfbench.harness.common import make_job
from perfbench.tests.conftest import REPO
from repro.configs.base import ModelConfig
from repro.core.schedule import build_comm_dag, build_full_dag
from repro.core.traffic import JobSpec

CONFIG = json.loads(
    (REPO / "perfbench" / "configs" / "deepseek-v3-671b.json").read_text())


def model_of(c: dict) -> dict:
    """The program's ModelConfig keys for catalog keys `c`."""
    return {"name": "ds-small", "family": "moe",
            "layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "heads": c["num_attention_heads"],
            "kv_heads": c["num_key_value_heads"],
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "moe_experts": c["n_routed_experts"],
            "moe_top_k": c["num_experts_per_tok"], "moe_every": 1,
            "moe_d_ff": c["moe_intermediate_size"],
            "moe_shared_experts": c["n_shared_experts"],
            "dense_layers": c["first_k_dense_replace"],
            "moe_groups": c["n_group"], "moe_topk_groups": c["topk_group"],
            "q_lora_rank": c["q_lora_rank"],
            "kv_lora_rank": c["kv_lora_rank"],
            "qk_nope_head_dim": c["qk_nope_head_dim"],
            "qk_rope_head_dim": c["qk_rope_head_dim"],
            "v_head_dim": c["v_head_dim"],
            "mtp_layers": c["num_nextn_predict_layers"]}


def small_config(seed: int) -> dict:
    """A DeepSeek-shaped deployment with small random widths: EP over
    every replica, one expert group per pod of the EP group."""
    rng = np.random.default_rng(seed)
    pick = lambda *v: int(rng.choice(v))  # noqa: E731
    span = pick(2, 4)
    pp = pick(2, 4)
    tp = pick(1, 2)
    layers = pp + pick(0, 1, 3)
    cut = np.sort(rng.choice(np.arange(1, layers), pp - 1, replace=False))
    split = np.diff(np.concatenate([[0], cut, [layers]])).tolist()
    heads = pick(2, 4)
    c = {"hidden_size": pick(64, 96, 128), "num_hidden_layers": layers,
         "num_attention_heads": heads, "num_key_value_heads": heads,
         "intermediate_size": pick(160, 256), "vocab_size": pick(256, 500),
         "n_routed_experts": span * pick(2, 4), "n_shared_experts":
         pick(0, 1, 2), "moe_intermediate_size": pick(32, 48),
         "first_k_dense_replace": pick(0, 1, 2), "moe_layer_freq": 1,
         "n_group": span, "topk_group": int(rng.integers(1, span + 1)),
         "q_lora_rank": pick(16, 24), "kv_lora_rank": pick(8, 16),
         "qk_nope_head_dim": pick(8, 16), "qk_rope_head_dim": pick(4, 8),
         "v_head_dim": pick(8, 16), "num_nextn_predict_layers": pick(0, 1)}
    c["num_experts_per_tok"] = pick(2, 4)
    c["parallelism"] = {
        "tp": tp, "pp": pp, "dp": span, "ep": span,
        "gpus_per_pod_per_replica": tp * pp // pick(1, 2),
        "microbatches": pick(1, 2, 3), "micro_batch_size": pick(1, 2),
        "gpu_flops": 140e12, "stage_layers": split,
        "ep_dispatch_bytes": pick(1, 2), "ep_combine_bytes": pick(1, 2)}
    c["cluster"] = {"inter_pod_gbps": 400.0, "seq_len": pick(64, 128),
                    "act_bytes": 2, "grad_bytes": pick(2, 4)}
    c["model"] = model_of(c)
    c["name"] = f"ds-small-{seed}"
    return c


def by_kind_and_pair(tasks) -> dict:
    """The program's tasks as the reference's `kinds`."""
    out: dict = {}
    for t in tasks:
        p = out.setdefault(t.kind, {}).setdefault(
            f"{t.src_pod}>{t.dst_pod}",
            {"tasks": 0, "flows": 0.0, "volume_bytes": 0.0})
        p["tasks"] += 1
        p["flows"] += float(t.flows)
        p["volume_bytes"] += float(t.volume)
    return out


def differences(found: dict, want: dict, rtol: float = 1e-12) -> list:
    """(kind, pair, field) where the program's `kinds` and the
    reference's differ: counts exactly, bytes to `rtol`."""
    bad = []
    for kind in sorted(set(found) | set(want)):
        f, w = found.get(kind, {}), want.get(kind, {})
        for pair in sorted(set(f) | set(w)):
            if pair not in f or pair not in w:
                bad.append((kind, pair, "missing"))
                continue
            for key in ("tasks", "flows"):
                if f[pair][key] != w[pair][key]:
                    bad.append((kind, pair, key))
            if not math.isclose(f[pair]["volume_bytes"],
                                w[pair]["volume_bytes"], rel_tol=rtol):
                bad.append((kind, pair, "volume_bytes"))
    return bad


SEEDS = range(6)
# a seed whose EP group spans 4 replicas
SPAN_4 = 0


@pytest.mark.parametrize("seed", SEEDS)
def test_parameters_agree_on_small_deepseek_shapes(seed):
    c = small_config(seed)
    model = ModelConfig(**c["model"])
    assert model.total_params() == ref.parameters(c)
    assert model.mtp_params() == ref.mtp_parameters(c)
    job = make_job(c)
    st = ref.stages(c)
    assert list(job.stage_params) == [s["params"] for s in st]
    assert list(job.expert_stage_params) == [s["experts"] for s in st]
    assert list(job.moe_stage_layers) == [s["moe_layers"] for s in st]
    for got, want in zip(job.active_stage_params, st):
        assert got == pytest.approx(want["active"], rel=1e-12)


@pytest.mark.parametrize("seed", SEEDS)
def test_dag_by_kind_and_pair_agrees_on_small_deepseek_jobs(seed):
    c = small_config(seed)
    dag = build_comm_dag(make_job(c), inter_pod_gbps=400.0)
    want = ref.projected_dag(c)
    assert differences(by_kind_and_pair(dag.real_tasks()),
                       want["kinds"]) == []
    assert dag.num_real_tasks == want["tasks"]
    assert len(dag.undirected_pairs()) == want["active_pairs"]
    assert dag.cluster.num_pods == want["pods"]
    assert sum(dag.cluster.port_limits) == want["ports"]


def test_config_file_counts_and_sums_agree_with_the_reference():
    """At the configuration's size: the parameters it states are the
    reference's and the program's, and the tasks of the program's full
    DAG (before the reduction, which keeps every inter-pod task) match the
    reference kind by kind and pair by pair; the file's `dag` sums are the
    reference's."""
    c = CONFIG
    assert c["parameters"] == ref.parameters(c) \
        == ModelConfig(**c["model"]).total_params()
    assert c["mtp_parameters"] == ref.mtp_parameters(c) \
        == ModelConfig(**c["model"]).mtp_params()
    want = ref.projected_dag(c)
    job = make_job(c)
    full = build_full_dag(job, job.cluster(c["cluster"]["inter_pod_gbps"]))
    tasks = [n.task for n in full.nodes if n.kind == "inter"]
    assert differences(by_kind_and_pair(tasks), want["kinds"]) == []
    kinds = collections.Counter(t.kind for t in tasks)
    # 2 directions x 7 peers x 16 MoE stages per microbatch, and the ring
    assert kinds["ep_a2a_fwd"] + kinds["ep_a2a_bwd"] == 224 * 16
    assert kinds["dp"] == 32
    for key in ("tasks", "pods", "active_pairs", "ports", "flows",
                "volume_bytes", "pairs"):
        assert c["dag"][key] == want[key], key


def old_projection(tasks, placement):
    """Every EP task moved onto replica 0's and 1's pods, half each way,
    as the single-pair projection laid them out."""
    out = []
    for t in tasks:
        if t.kind.startswith("ep_a2a"):
            k, s = t.tag[1], t.tag[3]
            a, b = (0, 1) if k % 2 else (1, 0)
            t = dataclasses.replace(t, src_pod=placement.pod_of(a, s),
                                    dst_pod=placement.pod_of(b, s))
        out.append(t)
    return out


@pytest.mark.parametrize("fault", ["one_pair_projection", "experts_in_ring"])
def test_the_old_traffic_model_fails_the_comparison(fault, monkeypatch):
    c = small_config(SPAN_4)
    if fault == "experts_in_ring":
        def dp_volume(self, stage):
            return float(2.0 * (self.dp - 1) / self.dp
                         * self.stage_params[stage] * self.grad_bytes)
        monkeypatch.setattr(JobSpec, "dp_volume", dp_volume)
    job = make_job(c)
    tasks = build_comm_dag(job).real_tasks()
    if fault == "one_pair_projection":
        tasks = old_projection(tasks, job.placement())
    bad = differences(by_kind_and_pair(tasks), ref.projected_dag(c)["kinds"])
    assert bad
    kind = "dp" if fault == "experts_in_ring" else "ep_a2a_fwd"
    assert any(b[0] == kind for b in bad)
