"""Architecture / shape / parallelism-plan schema for the framework.

Each assigned architecture file (repro/configs/<id>.py) defines
    CONFIG: ModelConfig   -- exact published dimensions
    PLAN:   ParallelismPlan -- training parallelization + pod placement used
                               by DELTA's traffic generator
and registers itself in the registry (repro.configs.REGISTRY).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | vlm | encdec
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0         # 0 -> d_model // heads
    # --- MoE
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_every: int = 1        # MoE FFN every k-th layer (jamba: 2)
    moe_capacity: float = 1.25  # capacity factor (tokens may drop beyond)
    moe_d_ff: int = 0         # expert width (0 -> d_ff)
    moe_shared_experts: int = 0  # experts every token passes through
    dense_layers: int = 0     # leading layers with a dense FFN of d_ff
    moe_groups: int = 0       # node-limited routing: the experts form this
    moe_topk_groups: int = 0  # many groups, and a token uses at most these
    # --- multi-latent attention (MLA; on when kv_lora_rank > 0)
    q_lora_rank: int = 0      # 0 -> queries projected from d_model directly
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- multi-token prediction modules (counted apart: mtp_params)
    mtp_layers: int = 0
    # --- SSM / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    attn_every: int = 0       # hybrid: 1 attention layer per this many
    # --- modality frontends (stubs provide precomputed embeddings)
    cross_attn_every: int = 0  # vlm: cross-attn layer per this many
    num_image_tokens: int = 0
    encoder_layers: int = 0    # encdec decoder cross-attends to these
    enc_tokens: int = 0        # whisper: 1500 frames after conv frontend
    # --- flags
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    tie_embeddings: bool = False
    norm_eps: float = 1e-5

    # ------------------------------------------------------------- derived
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.heads)

    @property
    def group_size(self) -> int:
        """Layer-pattern period (scan groups stack identical periods)."""
        g = 1
        for v in (self.attn_every, self.moe_every, self.cross_attn_every):
            if v and v > 1:
                g = math.lcm(g, v)
        return g

    def is_attn_layer(self, i: int) -> bool:
        if self.family == "ssm":
            return False
        if self.family == "hybrid" and self.attn_every:
            return (i % self.attn_every) == self.attn_every - 1
        return True

    def is_moe_layer(self, i: int) -> bool:
        if self.moe_experts <= 0 or i < self.dense_layers:
            return False
        return (i % self.moe_every) == self.moe_every - 1

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    def is_xattn_layer(self, i: int) -> bool:
        if not self.cross_attn_every:
            return False
        return (i % self.cross_attn_every) == self.cross_attn_every - 1

    # ------------------------------------------------------- param counting
    def _mla_params(self) -> int:
        """Latent attention: queries through a q_lora_rank bottleneck (or
        straight from d_model), keys and values from one kv_lora_rank
        latent plus a shared rope key, each latent with its RMSNorm."""
        d, h = self.d_model, self.heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.q_lora_rank:
            n = d * self.q_lora_rank + self.q_lora_rank \
                + self.q_lora_rank * h * qk
        else:
            n = d * h * qk
        n += d * (self.kv_lora_rank + self.qk_rope_head_dim) \
            + self.kv_lora_rank
        n += self.kv_lora_rank * h * (self.qk_nope_head_dim
                                      + self.v_head_dim)
        return n + h * self.v_head_dim * d

    def layer_params(self, i: int) -> int:
        d, hd = self.d_model, self.hd
        n = 0
        if self.kv_lora_rank and self.is_attn_layer(i):
            n += self._mla_params()
        elif self.is_attn_layer(i):
            q = d * self.heads * hd
            kv = 2 * d * self.kv_heads * hd
            o = self.heads * hd * d
            n += q + kv + o
            if self.qkv_bias:
                n += (self.heads + 2 * self.kv_heads) * hd
        else:  # mamba2 block
            d_in = self.ssm_expand * d
            nheads = d_in // self.ssm_head_dim
            n += d * (2 * d_in + 2 * self.ssm_state + nheads)  # in_proj
            n += self.ssm_conv * (d_in + 2 * self.ssm_state)   # conv
            n += d_in * d                                       # out_proj
            n += 2 * nheads                                     # A_log, dt_b
        if self.is_moe_layer(i):
            n += d * self.moe_experts                           # router
            n += (self.moe_experts + self.moe_shared_experts) \
                * 3 * d * self.expert_d_ff
        elif self.d_ff > 0:
            n += 3 * d * self.d_ff                              # swiglu
        if self.is_xattn_layer(i):
            n += 2 * d * self.heads * hd + 2 * d * self.kv_heads * hd
        n += 2 * d                                              # 2 rmsnorms
        return n

    def layer_active_params(self, i: int) -> int:
        n = self.layer_params(i)
        if self.is_moe_layer(i):
            n -= (self.moe_experts - self.moe_top_k) * self.expert_params()
        return n

    def expert_params(self) -> int:
        """Parameters of one routed expert (a SwiGLU of expert_d_ff)."""
        return 3 * self.d_model * self.expert_d_ff

    def layer_expert_params(self, i: int) -> int:
        """Routed-expert parameters of layer i: what expert parallelism
        shards, so a replica holds its own share and syncs none of it with
        the others of its EP group."""
        return self.moe_experts * self.expert_params() \
            if self.is_moe_layer(i) else 0

    def mtp_params(self, active: bool = False) -> int:
        """The multi-token prediction modules, each the 2d -> d projection
        of [h; embedding], the RMSNorms of its two inputs and one MoE block
        (the layer after the dense ones); embedding and head are shared
        with the main model.  `active`: what a token passes through."""
        d, i = self.d_model, self.dense_layers
        block = self.layer_active_params(i) if active \
            else self.layer_params(i)
        return self.mtp_layers * (2 * d * d + 2 * d + block)

    def embed_params(self) -> int:
        return self.vocab * self.d_model

    def head_params(self) -> int:
        return 0 if self.tie_embeddings else self.vocab * self.d_model

    def encoder_params(self) -> int:
        if not self.encoder_layers:
            return 0
        d, hd = self.d_model, self.hd
        per = (self.heads * hd * d * 2 + 2 * d * self.kv_heads * hd
               + 3 * d * self.d_ff + 2 * d)
        return self.encoder_layers * per

    def total_params(self) -> int:
        """The main model: embedding, layers and head; the multi-token
        prediction modules are counted apart (`mtp_params`)."""
        n = self.embed_params() + self.head_params() + self.encoder_params()
        n += sum(self.layer_params(i) for i in range(self.layers))
        return n

    def total_active_params(self) -> int:
        n = self.embed_params() + self.head_params() + self.encoder_params()
        n += sum(self.layer_active_params(i) for i in range(self.layers))
        return n

    # ------------------------------------------------------------- reduction
    def reduced(self) -> "ModelConfig":
        """Small same-family config for CPU smoke tests."""
        g = self.group_size
        layers = max(g, 2 if g == 1 else g)
        enc = min(self.encoder_layers, 2)
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            layers=layers,
            d_model=128,
            heads=4,
            kv_heads=min(self.kv_heads, 2) if self.kv_heads < self.heads
            else 4,
            head_dim=32,
            d_ff=256 if self.d_ff else 0,
            vocab=512,
            moe_experts=min(self.moe_experts, 4),
            moe_top_k=min(self.moe_top_k, 2),
            moe_capacity=float(max(self.moe_experts, 1)),  # drop-free smoke
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=16 if self.ssm_state else 64,
            num_image_tokens=min(self.num_image_tokens, 16),
            encoder_layers=enc,
            enc_tokens=min(self.enc_tokens, 32),
        )


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}

SUBQUADRATIC_FAMILIES = ("ssm", "hybrid")


def shape_applicable(cfg: ModelConfig, shape: ShapeSpec) -> tuple[bool, str]:
    """Skip rules per the assignment (recorded in the dry-run table)."""
    if shape.name == "long_500k" and cfg.family not in \
            SUBQUADRATIC_FAMILIES:
        return False, "long_500k skipped: pure full-attention architecture"
    return True, ""


@dataclass(frozen=True)
class ParallelismPlan:
    """Training parallelization feeding DELTA's inter-pod DAG."""
    tp: int
    pp: int
    dp: int
    ep: int = 1
    gpus_per_pod_per_replica: int = 16
    microbatches: int = 0          # 0 -> 8 * pp (paper Sec. V-A1)
    micro_batch_size: int = 1      # sequences per microbatch
    gpu_flops: float = 140e12      # effective bf16/GPU incl. MFU
    # layers per pipeline stage, where they are not split evenly (pp
    # entries summing to the model's layers; () -> layers // pp each)
    stage_layers: tuple[int, ...] = ()
    # bytes per element of the expert-parallel all-to-all: the dispatch
    # of token copies to their experts and the combine of the results
    ep_dispatch_bytes: int = 2
    ep_combine_bytes: int = 2

    def __post_init__(self) -> None:
        # a JSON configuration gives the split as a list
        object.__setattr__(self, "stage_layers",
                           tuple(int(v) for v in self.stage_layers))

    @property
    def num_gpus(self) -> int:
        return self.tp * self.pp * self.dp

    @property
    def num_microbatches(self) -> int:
        return self.microbatches or 8 * self.pp


@dataclass(frozen=True)
class ArchSpec:
    config: ModelConfig
    plan: ParallelismPlan
    # provenance strings for humans reading the spec tables, not the code
    source: str = ""  # sentinel: ignore[RPR001]
    notes: str = ""  # sentinel: ignore[RPR001]


def make_job(arch: ArchSpec, seq_len: int = 4096,
             microbatches: int | None = None, act_bytes: int = 2,
             grad_bytes: int = 2):
    """ArchSpec -> repro.core.traffic.JobSpec (DELTA's input).

    Stage s holds `plan.stage_layers[s]` layers (encoder layers first),
    or an even split; the embedding goes on the first stage, the head and
    the multi-token prediction modules on the last."""
    from repro.core.traffic import JobSpec
    cfg, plan = arch.config, arch.plan
    pp = plan.pp
    enc_layers = cfg.encoder_layers
    total_layers = cfg.layers + enc_layers
    if plan.stage_layers:
        counts = plan.stage_layers
        if len(counts) != pp or sum(counts) != total_layers:
            raise ValueError(f"{cfg.name}: stage_layers {counts} must give "
                             f"pp={pp} stages summing to {total_layers} "
                             f"layers")
    elif total_layers % pp:
        raise ValueError(f"{cfg.name}: {total_layers} layers not divisible "
                         f"by pp={pp}")
    else:
        counts = (total_layers // pp,) * pp
    bounds = [sum(counts[:s]) for s in range(pp + 1)]
    enc_stages = sum(1 for hi in bounds[1:] if hi <= enc_layers) \
        if enc_layers else 0
    stage_params: list[float] = []
    stage_active: list[float] = []
    stage_expert: list[float] = []
    stage_moe: list[int] = []
    d = cfg.d_model
    enc_layer_p = (cfg.encoder_params() / max(enc_layers, 1)) \
        if enc_layers else 0.0
    for s in range(pp):
        p = a = 0.0
        n_moe = e = 0
        for li in range(bounds[s], bounds[s + 1]):
            if li < enc_layers:
                p += enc_layer_p
                a += enc_layer_p
            else:
                i = li - enc_layers
                p += cfg.layer_params(i)
                a += cfg.layer_active_params(i)
                e += cfg.layer_expert_params(i)
                n_moe += int(cfg.is_moe_layer(i))
        if s == 0:
            p += cfg.embed_params()
            a += cfg.embed_params() / max(seq_len, 1)  # sparse lookup
        if s == pp - 1:
            p += cfg.head_params()
            a += cfg.head_params()
            if cfg.mtp_layers:
                p += cfg.mtp_params()
                a += cfg.mtp_params(active=True)
                e += cfg.mtp_layers * cfg.layer_expert_params(
                    cfg.dense_layers)
                n_moe += cfg.mtp_layers * int(cfg.is_moe_layer(
                    cfg.dense_layers))
        stage_params.append(p)
        stage_active.append(a)
        stage_expert.append(float(e))
        stage_moe.append(n_moe)
    mb = microbatches or plan.num_microbatches
    return JobSpec(
        name=cfg.name,
        tp=plan.tp, pp=pp, dp=plan.dp, ep=plan.ep,
        num_microbatches=mb,
        micro_tokens=plan.micro_batch_size * seq_len,
        d_model=d,
        stage_params=tuple(stage_params),
        active_stage_params=tuple(stage_active),
        expert_stage_params=tuple(stage_expert) if cfg.moe_experts else (),
        moe_experts=cfg.moe_experts, moe_top_k=cfg.moe_top_k,
        moe_every=cfg.moe_every,
        moe_stage_layers=tuple(stage_moe) if cfg.moe_experts else (),
        moe_groups=cfg.moe_groups, moe_topk_groups=cfg.moe_topk_groups,
        gpus_per_pod_per_replica=plan.gpus_per_pod_per_replica,
        act_bytes=act_bytes, grad_bytes=grad_bytes,
        ep_dispatch_bytes=plan.ep_dispatch_bytes,
        ep_combine_bytes=plan.ep_combine_bytes,
        gpu_flops=plan.gpu_flops,
        enc_stages=enc_stages,
        enc_tokens=plan.micro_batch_size * cfg.enc_tokens,
        seq_len=seq_len,
    )
