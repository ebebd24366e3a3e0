"""jamba2-mini [hybrid] — AI21 Jamba2 Mini: 32L d_model=4096 32H (GQA
kv=8), no positional encoding, vocab 65536; periods of 8 layers with
attention at offset 4 and Mamba-1 (d_state 16, d_conv 4, expand 2,
dt_rank 256, RMSNorms on dt, B and C) elsewhere; MoE of 16 experts
(width 14336) top-2 on every odd layer, a dense SwiGLU of width 14336 on
the even ones.  [hf:ai21labs/AI21-Jamba2-Mini]

The protocol simulator serves it with one edge node per expert (K=16):
an odd layer is a protocol round, an even layer's dense FFN runs in situ
at the query's node."""

import dataclasses

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

CONFIG = ModelConfig(
    name="jamba2-mini",
    arch_type="hybrid",
    source="[hf:ai21labs/AI21-Jamba2-Mini]",
    num_layers=32,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    d_ff=14336,
    vocab_size=65536,
    rope=False,
    max_seq_len=262144,
    norm_eps=1e-6,
    moe=MoEConfig(
        num_experts=16,
        top_k=2,
        d_ff_expert=14336,
        every=2,
        routing="topk",
        qos_gamma0=0.7,
        max_experts=2,
    ),
    ssm=SSMConfig(kind="mamba", d_state=16, d_conv=4, expand=2,
                  attn_every=8, inner_norms=True),   # dt_rank d_model/16
)


def smoke() -> ModelConfig:
    cfg = dataclasses.replace(
        CONFIG,
        name="jamba2-smoke",
        num_layers=8,        # one whole period: 7 Mamba + 1 attention
        d_model=128,
        num_heads=4,
        num_kv_heads=2,
        d_ff=256,
        vocab_size=256,
        dtype="float32",
        param_dtype="float32",
    )
    return cfg.with_overrides(moe_num_experts=4, moe_d_ff_expert=128,
                              ssm_d_state=8)
