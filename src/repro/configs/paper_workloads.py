"""The paper's four evaluation workloads (Table I) + the GPT-7B profiling
example of Fig. 1/3.  Parallelism configs match Table I exactly; model
dimensions are representative published configs with matching totals (the
DELTA benchmarks only consume parallelism + parameter/activation volumes).
"""
from repro.configs.base import ArchSpec, ModelConfig, ParallelismPlan

GPT_7B = ArchSpec(
    ModelConfig(name="gpt-7b", family="dense", layers=32, d_model=4096,
                heads=32, kv_heads=32, d_ff=11008, vocab=50257),
    ParallelismPlan(tp=2, pp=4, dp=2, gpus_per_pod_per_replica=4,
                    microbatches=8),
    source="paper Fig. 1", notes="profiling example; 4 pods")

MEGATRON_177B = ArchSpec(
    ModelConfig(name="megatron-177b", family="dense", layers=96,
                d_model=12288, heads=96, kv_heads=96, d_ff=32768,
                vocab=51200),
    ParallelismPlan(tp=8, pp=6, dp=8, gpus_per_pod_per_replica=16,
                    microbatches=48),
    source="paper Table I / Megatron benchmarks [59-61]")

MIXTRAL_8X22B = ArchSpec(
    ModelConfig(name="mixtral-8x22b", family="moe", layers=56,
                d_model=6144, heads=48, kv_heads=8, d_ff=16384,
                vocab=32768, moe_experts=8, moe_top_k=2, moe_every=1),
    ParallelismPlan(tp=2, pp=8, dp=8, ep=8, gpus_per_pod_per_replica=16,
                    microbatches=64),
    source="paper Table I [arXiv:2401.04088]")

MEGATRON_462B = ArchSpec(
    ModelConfig(name="megatron-462b", family="dense", layers=128,
                d_model=17408, heads=136, kv_heads=136, d_ff=46080,
                vocab=51200),
    ParallelismPlan(tp=8, pp=16, dp=8, gpus_per_pod_per_replica=32,
                    microbatches=128),
    source="paper Table I / Megatron benchmarks [59-61]")

# DeepSeek-V3 at its published widths (huggingface.co/deepseek-ai/
# DeepSeek-V3 config.json): MLA, 3 dense layers then 58 MoE layers of 256
# routed experts (top 8 within 4 of 8 groups) plus one shared expert, and
# one MTP module.  The 61 layers split 4 per stage over stages 0-14 and 1
# on stage 15, which also holds the head and the MTP module: the split
# with the smallest largest stage (4 MoE layers, 2.34B active parameters).
# FP8 dispatch and BF16 combine, as in the DeepSeek-V3 report
# (arXiv:2412.19437).
DEEPSEEK_671B = ArchSpec(
    ModelConfig(name="deepseek-671b", family="moe", layers=61,
                d_model=7168, heads=128, kv_heads=128, d_ff=18432,
                vocab=129280, moe_experts=256, moe_top_k=8, moe_every=1,
                moe_d_ff=2048, moe_shared_experts=1, dense_layers=3,
                moe_groups=8, moe_topk_groups=4, q_lora_rank=1536,
                kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, mtp_layers=1, norm_eps=1e-6),
    ParallelismPlan(tp=2, pp=16, dp=8, ep=8, gpus_per_pod_per_replica=32,
                    microbatches=128, stage_layers=(4,) * 15 + (1,),
                    ep_dispatch_bytes=1, ep_combine_bytes=2),
    source="paper Table I [DeepSeek-V3, arXiv:2412.19437]")

PAPER_WORKLOADS = {
    "gpt-7b": GPT_7B,
    "megatron-177b": MEGATRON_177B,
    "mixtral-8x22b": MIXTRAL_8X22B,
    "megatron-462b": MEGATRON_462B,
    "deepseek-671b": DEEPSEEK_671B,
}
