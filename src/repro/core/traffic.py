"""Analytic traffic/duration model for LLM training iterations (paper F1).

The paper generates communication traces with simAI; because LLM traffic is
deterministic given (model, parallelism, schedule) -- feature F1 -- we compute
the same quantities analytically:

  PP activation/gradient volume per microbatch boundary:
      V_pp = micro_tokens * d_model * act_bytes
  DP gradient-sync volume per stage (unidirectional ring all-reduce, so the
  single-replica projection of Sec. IV-A1 stays port-exact):
      V_dp = 2 * (dp-1)/dp * shared_stage_param_bytes   per ring link
  r -> r+1.  When the EP group spans every replica (span == dp) the routed
  experts are left out: each lives on one replica and has nothing to
  sync.  With ep == 1 every replica holds every expert and the ring syncs
  them all.
  EP all-to-all bytes from one replica's stage shard to ONE peer of its
  EP group, per (microbatch, stage, direction):
      V_ep = n_moe(s) * micro_tokens * d_model
             * (dispatch_bytes + combine_bytes) * p_peer
  Each token sends one copy to each peer pod that holds at least one of
  its selected experts; p_peer is the chance of that.  With node-limited
  routing (experts in moe_groups groups, one per pod of the group, and a
  token using at most moe_topk_groups of them, groups loaded evenly)
  p_peer = moe_topk_groups / moe_groups; with no node limit, top_k of the
  E experts drawn evenly and E/span of them on the peer,
      p_peer = 1 - C(E - E/span, top_k) / C(E, top_k).
  Forward and backward each carry one dispatch and one combine per MoE
  layer.  EP groups stride across DP replicas within a stage: replica r
  exchanges tokens with the other span - 1 = min(ep, dp) - 1 replicas of
  its group, whose stage-s shards live in other pods.  When ep > dp
  (jamba-style expert sharding inside the TP group too) the span
  saturates at dp and the exchange inside a replica's pod is not charged.
  compute durations from a FLOPs model:
      fwd(b, s) = 2 * active_stage_params[s] * micro_tokens / (tp * gpu_flops)
      bwd       = 2 * fwd
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass

from repro.core.cluster import GBPS, ClusterSpec, Placement


@dataclass(frozen=True)
class JobSpec:
    """Everything DELTA needs to know about one training job.

    stage_params: parameters per pipeline stage, all experts included
      (bytes are derived with grad_bytes).
    active_stage_params: parameters touched per token (MoE: routed experts
      only) -- drives compute durations.
    expert_stage_params: routed-expert parameters per stage (pp entries,
      or empty): sharded by EP, so the DP ring leaves them out.
    moe_experts / moe_top_k / moe_every: MoE routing shape (from
      ModelConfig); with moe_groups / moe_topk_groups (node-limited
      routing) they set the share of tokens each EP peer receives.
    moe_stage_layers: number of MoE layers hosted by each pipeline stage
      (pp entries; make_job derives it from ModelConfig.is_moe_layer).
      Empty means no EP traffic is modeled even if ep > 1.
    ep: expert-parallel degree.  EP groups stride across DP replicas within
      a stage (see module docstring); ep == 1 disables EP traffic entirely
      and yields DAGs bit-identical to the pre-MoE builder.
    """

    name: str
    tp: int
    pp: int
    dp: int
    num_microbatches: int
    micro_tokens: int
    d_model: int
    stage_params: tuple[float, ...]
    active_stage_params: tuple[float, ...] = ()
    gpus_per_pod_per_replica: int = 16
    ep: int = 1
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_every: int = 1
    moe_stage_layers: tuple[int, ...] = ()
    expert_stage_params: tuple[float, ...] = ()
    moe_groups: int = 0
    moe_topk_groups: int = 0
    act_bytes: int = 2
    grad_bytes: int = 2
    ep_dispatch_bytes: int = 2  # per element of a dispatched token copy
    ep_combine_bytes: int = 2   # per element of a combined expert output
    gpu_flops: float = 140e12   # effective per-GPU throughput (bf16 * MFU)
    enc_stages: int = 0         # >0: first enc_stages stages form an encoder
    enc_tokens: int = 0         # encoder frames per microbatch (whisper stub)
    seq_len: int = 4096

    def __post_init__(self) -> None:
        if len(self.stage_params) != self.pp:
            raise ValueError("stage_params must have pp entries")
        if self.active_stage_params and \
                len(self.active_stage_params) != self.pp:
            raise ValueError("active_stage_params must have pp entries")
        if self.num_microbatches < 1 or self.pp < 1:
            raise ValueError("bad schedule sizes")
        if self.moe_stage_layers and len(self.moe_stage_layers) != self.pp:
            raise ValueError("moe_stage_layers must have pp entries")
        if self.expert_stage_params and \
                len(self.expert_stage_params) != self.pp:
            raise ValueError("expert_stage_params must have pp entries")
        if self.ep > 1:
            if self.ep <= self.dp and self.dp % self.ep:
                raise ValueError(
                    f"ep={self.ep} must divide dp={self.dp} (EP groups "
                    f"stride across DP replicas within a stage)")
            if self.ep > self.dp and self.ep % self.dp:
                raise ValueError(
                    f"ep={self.ep} > dp={self.dp} requires dp | ep (the "
                    f"per-replica remainder shards inside the TP group)")

    @property
    def active(self) -> tuple[float, ...]:
        return self.active_stage_params or self.stage_params

    # ------------------------------------------------------------- placement
    def placement(self, reverse_stages: bool = False) -> Placement:
        return Placement(tp=self.tp, pp=self.pp, dp=self.dp,
                         gpus_per_pod_per_replica=self.gpus_per_pod_per_replica,
                         ep=self.ep,
                         reverse_stages=reverse_stages)

    def cluster(self, inter_pod_gbps: float = 400.0,
                reverse_stages: bool = False, **kw) -> ClusterSpec:
        return self.placement(reverse_stages).cluster(
            nic_bandwidth=inter_pod_gbps * GBPS, **kw)

    # --------------------------------------------------------------- volumes
    def pp_volume(self) -> float:
        """Activation (== gradient) bytes crossing one stage boundary per
        microbatch, aggregated over the TP group (paper task aggregation)."""
        return float(self.micro_tokens * self.d_model * self.act_bytes)

    def xattn_volume(self) -> float:
        """Encoder-output bytes consumed by each decoder stage (enc-dec)."""
        return float(self.enc_tokens * self.d_model * self.act_bytes)

    def dp_volume(self, stage: int) -> float:
        """Ring all-reduce bytes per link of the parameters the replicas
        share.  Routed experts are sharded over the EP group; when the
        group spans every replica no expert has a replica to sync with."""
        params = self.stage_params[stage]
        span = self.placement().ep_span
        if self.expert_stage_params and span > 1:
            if span < self.dp and self.expert_stage_params[stage]:
                raise ValueError(
                    f"{self.name}: dp={self.dp} > EP span {span} "
                    f"replicates each expert over dp/span replicas, whose "
                    f"gradient sync is not modelled")
            params -= self.expert_stage_params[stage]
        bytes_ = params * self.grad_bytes
        return float(2.0 * (self.dp - 1) / self.dp * bytes_)

    def ep_peer_share(self) -> float:
        """p_peer: the expected token copies one peer pod of the EP group
        receives per token (module docstring)."""
        E, k = self.moe_experts, self.moe_top_k
        span = self.placement().ep_span
        if span < 2 or k <= 0:
            return 0.0
        if self.moe_groups:
            if self.moe_groups != span:
                raise ValueError(
                    f"{self.name}: node-limited routing over "
                    f"{self.moe_groups} groups needs one group per pod of "
                    f"the EP group ({span})")
            return self.moe_topk_groups / self.moe_groups
        if E % span:
            raise ValueError(f"{self.name}: {E} experts do not split over "
                             f"an EP span of {span}")
        return 1.0 - math.comb(E - E // span, k) / math.comb(E, k)

    def ep_a2a_peer_volume(self, stage: int) -> float:
        """Per-direction (fwd or bwd) EP all-to-all bytes one replica's
        `stage` shard sends one peer of its group per microbatch."""
        if not self.moe_stage_layers:
            return 0.0
        return float(self.moe_stage_layers[stage] * self.micro_tokens
                     * self.d_model
                     * (self.ep_dispatch_bytes + self.ep_combine_bytes)
                     * self.ep_peer_share())

    # -------------------------------------------------------------- durations
    def fwd_duration(self, stage: int) -> float:
        tokens = self.micro_tokens
        if self.enc_stages and stage < self.enc_stages:
            tokens = max(self.enc_tokens, 1)
        return 2.0 * self.active[stage] * tokens / (self.tp * self.gpu_flops)

    def bwd_duration(self, stage: int) -> float:
        return 2.0 * self.fwd_duration(stage)

    def intra_pp_duration(self, cluster: ClusterSpec) -> float:
        """Duration of a stage-boundary transfer when both stages share a
        pod (electrical intra-pod network)."""
        return self.pp_volume() / (self.tp * cluster.intra_pod_bandwidth)

    # ------------------------------------------------------------- reporting
    def total_params(self) -> float:
        return float(sum(self.stage_params))

    def iteration_tokens(self) -> int:
        return self.num_microbatches * self.micro_tokens

    def scaled(self, **overrides) -> "JobSpec":
        return dataclasses.replace(self, **overrides)


def ideal_step_compute_time(job: JobSpec) -> float:
    """Pipeline-unaware lower bound on compute time (for sanity checks)."""
    per_mb = sum(job.fwd_duration(s) + job.bwd_duration(s)
                 for s in range(job.pp))
    return per_mb * job.num_microbatches / job.pp
