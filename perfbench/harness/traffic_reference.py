"""Plain reference for what a DeepSeek-V3 deployment puts on the inter-pod
network: its parameter count, the parameters each pipeline stage holds,
and the tasks, flows and bytes of the projected communication DAG, by
task kind and ordered pod pair.

Written from DeepSeek-V3's config.json (the catalog keys the
configuration file carries at its top level: hidden_size,
num_hidden_layers, q_lora_rank, ...) and from the deployment of the
DELTA paper's Table I (the file's `parallelism` and `cluster`).  It
imports nothing of the program, so it checks the program's model of the
job rather than repeating it.

Weights, by the names of DeepSeek-V3's checkpoint:

  embed_tokens                      vocab x d
  per layer   input/post_attention_layernorm   2 d
              q_a_proj d x q_lora, q_a_layernorm q_lora,
              q_b_proj q_lora x H (nope + rope),
              kv_a_proj_with_mqa d x (kv_lora + rope), kv_a_layernorm
              kv_lora, kv_b_proj kv_lora x H (nope + v), o_proj H v x d
              dense mlp (the first first_k_dense_replace layers)
                                    3 d x intermediate_size
              moe: gate n_routed x d, n_routed routed experts and
                   n_shared shared experts of 3 d x moe_intermediate_size
  lm_head                           vocab x d
  MTP module  enorm, hnorm 2 d, eh_proj 2d x d, one MoE layer; embedding
              and head shared with the main model

Left out, as the program leaves them out: the final norm (d), each
router's score-correction bias (n_routed per MoE layer) and the MTP
head's norm (d).

The job, per iteration (one sequence per microbatch):

  stages      `stage_layers` layers each; the embedding on the first,
              the head and the MTP modules on the last
  pods        replica r's stage s lives in pod r * R + min(s // (G / tp),
              R - 1), with G GPUs per pod per replica and R = ceil(tp pp / G)
  PP          per microbatch and stage boundary that crosses pods, one
              task each way of tokens x d x act_bytes bytes, tp flows
  EP          the DAG projects the expert-parallel all-to-all onto
              replica 0: per microbatch, stage with MoE layers, direction
              (forward, backward) and peer k = 1 .. span - 1 of its EP
              group (span = min(ep, dp) replicas), one task from replica
              0's pod to replica k's, tp flows, of
                  n_moe(s) x tokens x d x (dispatch + combine bytes)
                  x topk_group / n_group
              bytes: with node-limited routing each token goes to at most
              topk_group of the n_group expert groups, one group per pod
              of the EP group; groups are loaded evenly (the auxiliary-
              loss-free balancing), and a token sends one copy to each pod
              it uses
  DP          per stage, the ring all-reduce of the parameters replicas
              share (the routed experts are sharded over the EP group,
              which spans every replica): a task from replica 0's pod to
              replica 1's and one back (the ring's wrap-around image),
              tp flows, 2 (dp - 1) / dp x shared params x grad_bytes bytes
"""
from __future__ import annotations

import math


def _layer(c: dict, moe: bool) -> dict:
    """Parameters of one decoder layer: all of them, those a token passes
    through, and those of its routed experts."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    qr, kvr, v = c["q_lora_rank"], c["kv_lora_rank"], c["v_head_dim"]
    attn = (d * qr + qr + qr * h * (nope + rope)
            + d * (kvr + rope) + kvr + kvr * h * (nope + v) + h * v * d)
    norms = 2 * d
    if not moe:
        mlp = 3 * d * c["intermediate_size"]
        return {"all": attn + norms + mlp, "active": attn + norms + mlp,
                "experts": 0}
    expert = 3 * d * c["moe_intermediate_size"]
    routed = c["n_routed_experts"] * expert
    shared = c["n_shared_experts"] * expert
    gate = c["n_routed_experts"] * d
    base = attn + norms + gate + shared
    return {"all": base + routed,
            "active": base + c["num_experts_per_tok"] * expert,
            "experts": routed}


def _is_moe(c: dict, i: int) -> bool:
    return i >= c["first_k_dense_replace"] and \
        i % c["moe_layer_freq"] == 0


def _mtp(c: dict) -> dict:
    d = c["hidden_size"]
    block = _layer(c, moe=True)
    extra = 2 * d * d + 2 * d
    return {"all": extra + block["all"], "active": extra + block["active"],
            "experts": block["experts"]}


def parameters(config: dict) -> int:
    """The main model's parameters: embedding, layers and head."""
    c = config
    n = 2 * c["vocab_size"] * c["hidden_size"]
    return n + sum(_layer(c, _is_moe(c, i))["all"]
                   for i in range(c["num_hidden_layers"]))


def mtp_parameters(config: dict) -> int:
    """Parameters of the multi-token prediction modules, apart from the
    embedding and head they share with the main model."""
    return config["num_nextn_predict_layers"] * _mtp(config)["all"]


def stages(config: dict) -> list[dict]:
    """Per pipeline stage: `params` (all it holds), `active` (what a token
    passes through; the embedding lookup charged as vocab x d / seq_len),
    `experts` (routed-expert parameters) and `moe_layers`."""
    c, par = config, config["parallelism"]
    seq = config["cluster"]["seq_len"]
    split = par["stage_layers"]
    assert sum(split) == c["num_hidden_layers"] and len(split) == par["pp"]
    embed = c["vocab_size"] * c["hidden_size"]
    out, first = [], 0
    for s, count in enumerate(split):
        st = {"params": 0, "active": 0.0, "experts": 0, "moe_layers": 0}
        for i in range(first, first + count):
            lay = _layer(c, _is_moe(c, i))
            st["params"] += lay["all"]
            st["active"] += lay["active"]
            st["experts"] += lay["experts"]
            st["moe_layers"] += int(_is_moe(c, i))
        first += count
        if s == 0:
            st["params"] += embed
            st["active"] += embed / seq
        if s == len(split) - 1:
            st["params"] += embed
            st["active"] += embed
            k = c["num_nextn_predict_layers"]
            st["params"] += k * _mtp(c)["all"]
            st["active"] += k * _mtp(c)["active"]
            st["experts"] += k * _mtp(c)["experts"]
            st["moe_layers"] += k
        out.append(st)
    return out


def peer_share(config: dict) -> float:
    """Token copies one peer pod of the EP group receives per token."""
    par = config["parallelism"]
    span = min(par["ep"], par["dp"])
    if config["n_group"] != span:
        raise ValueError(f"{config['n_group']} expert groups do not map "
                         f"one to one onto an EP group of {span} pods")
    return config["topk_group"] / config["n_group"]


def projected_dag(config: dict) -> dict:
    """Tasks, flows and bytes of the projected DAG: totals, by ordered
    pod pair (`pairs`, as the configuration file's `dag` states them) and
    by task kind and pair (`kinds`)."""
    par, cl = config["parallelism"], config["cluster"]
    tp, pp, dp = par["tp"], par["pp"], par["dp"]
    gppr, mb = par["gpus_per_pod_per_replica"], par["microbatches"]
    tokens = par["micro_batch_size"] * cl["seq_len"]
    d = config["hidden_size"]
    per_pod = max(1, gppr // tp)
    pods_per_replica = math.ceil(tp * pp / gppr)

    def pod(r: int, s: int) -> int:
        return r * pods_per_replica + min(s // per_pod,
                                          pods_per_replica - 1)

    kinds: dict[str, dict] = {}

    def add(kind: str, a: int, b: int, count: int, volume: float) -> None:
        if a == b or count == 0:
            return
        p = kinds.setdefault(kind, {}).setdefault(
            f"{a}>{b}", {"tasks": 0, "flows": 0.0, "volume_bytes": 0.0})
        p["tasks"] += count
        p["flows"] += float(count * tp)
        p["volume_bytes"] += count * volume

    for s in range(pp - 1):
        v = tokens * d * cl["act_bytes"]
        add("pp_fwd", pod(0, s), pod(0, s + 1), mb, v)
        add("pp_bwd", pod(0, s + 1), pod(0, s), mb, v)
    st = stages(config)
    span = min(par["ep"], par["dp"]) if par["ep"] > 1 else 1
    if span > 1:
        share = peer_share(config)
        for s in range(pp):
            v = (st[s]["moe_layers"] * tokens * d
                 * (par["ep_dispatch_bytes"] + par["ep_combine_bytes"])
                 * share)
            if v <= 0:
                continue
            for k in range(1, span):
                for kind in ("ep_a2a_fwd", "ep_a2a_bwd"):
                    add(kind, pod(0, s), pod(k, s), mb, v)
    if 1 < span < dp:
        raise ValueError(f"each expert lives on dp / span = {dp // span} "
                         f"replicas, whose gradient sync is not modelled")
    if dp > 1:
        for s in range(pp):
            shared = st[s]["params"] - (st[s]["experts"] if span > 1
                                        else 0)
            v = 2.0 * (dp - 1) / dp * shared * cl["grad_bytes"]
            add("dp", pod(0, s), pod(1, s), 1, v)
            add("dp", pod(1, s), pod(0, s), 1, v)
    pairs: dict[str, dict] = {}
    for by_pair in kinds.values():
        for key, p in by_pair.items():
            q = pairs.setdefault(key, {"tasks": 0, "flows": 0.0,
                                       "volume_bytes": 0.0})
            for f in q:
                q[f] += p[f]
    undirected = {tuple(sorted(map(int, k.split(">")))) for k in pairs}
    return {"tasks": sum(p["tasks"] for p in pairs.values()),
            "pods": pods_per_replica * dp,
            "active_pairs": len(undirected),
            "ports": tp * pp * dp,
            "flows": sum(p["flows"] for p in pairs.values()),
            "volume_bytes": sum(p["volume_bytes"] for p in pairs.values()),
            "pairs": dict(sorted(pairs.items())),
            "kinds": {k: dict(sorted(v.items()))
                      for k, v in sorted(kinds.items())}}
