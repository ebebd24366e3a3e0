"""deepseek-v3-671b [moe] — 61L d_model=7168 128H d_ff=2048(expert)
vocab=129280, MoE 256 routed experts top-8 + 1 shared, MLA
(kv_lora=512, q_lora=1536, rope_dim=64) with YaRN rotary positions
(factor 40 over 4096 original positions, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 1), first 3 layers dense
(d_ff=18432), MTP depth-1 training objective (shared embedding/head +
one extra MLA block predicting token t+2; serving unaffected).
[arXiv:2412.19437]

This is the paper's motivating scale for DES: "directly searching ...
is intractable with a large number of experts like DeepSeek-V3 with
K=256" (§V-B)."""

import dataclasses

from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    arch_type="moe",
    source="[arXiv:2412.19437]",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,
    d_ff=18432,              # dense layers (first 3)
    vocab_size=129280,
    rope_theta=1e4,
    max_seq_len=131072,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=1.0,
    yarn_mscale_all_dim=1.0,
    mla=True,
    q_lora_rank=1536,
    kv_lora_rank=512,
    rope_head_dim=64,
    nope_head_dim=128,
    v_head_dim=128,
    mtp=True,
    moe=MoEConfig(
        num_experts=256,
        top_k=8,
        num_shared_experts=1,
        d_ff_expert=2048,
        first_dense_layers=3,
        routing="topk",
        qos_z=1.0,
        qos_gamma0=0.85,      # deeper model -> gentler QoS decay
        max_experts=8,
    ),
)


def smoke() -> ModelConfig:
    cfg = dataclasses.replace(
        CONFIG,
        name="deepseek-v3-smoke",
        num_layers=3,        # 1 dense + 2 MLA-MoE
        d_model=256,
        num_heads=8,
        num_kv_heads=8,
        d_ff=512,
        vocab_size=512,
        q_lora_rank=64,
        kv_lora_rank=32,
        rope_head_dim=16,
        nope_head_dim=32,
        v_head_dim=32,
        dtype="float32",
        param_dtype="float32",
    )
    return cfg.with_overrides(
        moe_num_experts=4, moe_top_k=2, moe_d_ff_expert=128,
        moe_first_dense_layers=1, moe_max_experts=2,
    )
