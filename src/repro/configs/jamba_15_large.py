"""jamba-1.5-large-398b [hybrid] — 72L d_model=8192 64H (GQA kv=8)
d_ff=24576, MoE 16e top-2 — Mamba+attention 1:7 interleave (period 8),
MoE every 2nd layer; no positional encoding, RMSNorms on Mamba's dt, B
and C.  [arXiv:2403.19887]"""

import dataclasses

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

CONFIG = ModelConfig(
    name="jamba-1.5-large-398b",
    arch_type="hybrid",
    source="[arXiv:2403.19887]",
    num_layers=72,
    d_model=8192,
    num_heads=64,
    num_kv_heads=8,
    d_ff=24576,
    vocab_size=65536,
    rope=False,
    max_seq_len=262144,
    norm_eps=1e-6,
    sliding_window=4096,     # used by its attention layers at long ctx
    moe=MoEConfig(
        num_experts=16,
        top_k=2,
        d_ff_expert=24576,
        every=2,
        routing="topk",
        qos_gamma0=0.7,
        max_experts=2,
    ),
    ssm=SSMConfig(kind="mamba", d_state=16, d_conv=4, expand=2, attn_every=8,
                  inner_norms=True),
)


def smoke() -> ModelConfig:
    cfg = dataclasses.replace(
        CONFIG,
        name="jamba-smoke",
        num_layers=4,        # 2 periods of 2 (attn_every=2): mamba+attn
        d_model=256,
        num_heads=8,
        num_kv_heads=2,
        d_ff=512,
        vocab_size=512,
        dtype="float32",
        param_dtype="float32",
    )
    return cfg.with_overrides(
        moe_num_experts=4, moe_d_ff_expert=256,
        ssm_attn_every=2, ssm_d_state=8,
    )
