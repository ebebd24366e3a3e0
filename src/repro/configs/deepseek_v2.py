"""deepseek-v2 [moe] — DeepSeek-V2 (236B-A21B): 60L d_model=5120 128H,
MLA (q_lora 1536, kv_lora 512, qk_nope 128, qk_rope 64, v 128) with YaRN
rotary positions (theta 1e4, factor 40 over 4096 original positions,
beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707), vocab 102400,
untied.  Layer 0 is dense (SwiGLU 12288); every later layer has 160
routed experts of width 1536, top-6, and 2 shared experts (one SwiGLU of
3072).  The router is a softmax, `group_limited_greedy`: 8 groups of 20
experts, the best 3 groups kept, the top-6 taken within them, weighted
by softmax x 16 unnormalised.  [hf:deepseek-ai/DeepSeek-V2]

The protocol simulator serves each of the 8 groups as one edge node
(K=8): DES under z*gamma0^l picks at most D = topk_group = 3 nodes from
the nodes' group masses, and the published top-6 runs within them."""

import dataclasses

from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2",
    arch_type="moe",
    source="[hf:deepseek-ai/DeepSeek-V2]",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,
    d_ff=12288,              # the dense layer 0
    vocab_size=102400,
    rope_theta=1e4,
    max_seq_len=163840,
    norm_eps=1e-6,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    mla=True,
    q_lora_rank=1536,
    kv_lora_rank=512,
    rope_head_dim=64,
    nope_head_dim=128,
    v_head_dim=128,
    moe=MoEConfig(
        num_experts=160,
        top_k=6,
        num_shared_experts=2,
        d_ff_expert=1536,
        first_dense_layers=1,
        n_group=8,
        topk_group=3,
        routed_scaling_factor=16.0,
        norm_topk_prob=False,
        routing="topk",
        qos_z=1.0,
        qos_gamma0=0.7,
        max_experts=3,
    ),
)


def smoke() -> ModelConfig:
    cfg = dataclasses.replace(
        CONFIG,
        name="deepseek-v2-smoke",
        num_layers=3,        # 1 dense + 2 MLA-MoE
        d_model=128,
        num_heads=4,
        num_kv_heads=4,
        d_ff=256,
        vocab_size=256,
        q_lora_rank=64,
        kv_lora_rank=32,
        rope_head_dim=16,
        nope_head_dim=32,
        v_head_dim=32,
        dtype="float32",
        param_dtype="float32",
    )
    return cfg.with_overrides(
        moe_num_experts=16, moe_top_k=3, moe_d_ff_expert=64,
        moe_n_group=4, moe_topk_group=2, moe_max_experts=2,
    )
