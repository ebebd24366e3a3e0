"""Operations one protocol pass of a Jamba period requires, from the
shapes and the recorded selection.

Counted (2 operations per multiply-add), per token: on a Mamba layer the
input, x, dt and output projections, the depthwise convolution, and the
scan's state update and read-out (two multiply-adds per inner channel
and state); on the attention layer the q/k/v/o projections and the
causal products (pairs with key <= query only), with no rotary
positions to count; the SwiGLU of each dense layer; on each MoE layer
the router and the SwiGLU of each selected (token, expert) pair with
its Eq.-8 combine; then the unembedding.  Not counted: the FFN work of
experts that were not selected, which the program's dense all-expert
FFN also does, norms, softmaxes, the scan's exponentials and the gate's
elementwise products.
"""

from __future__ import annotations


def pass_flops(config: dict, k: int, n: int, selected) -> float:
    """config: the configuration file (Hugging Face key names); k queries
    of n tokens; `selected[r]` the number of (token, expert) pairs that
    the r-th MoE layer selected."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    hkv = config["num_key_value_heads"]
    dh = d // h
    e = config["num_experts"]
    f = config["intermediate_size"]
    v = config["vocab_size"]
    di = config["mamba_expand"] * d
    st = config["mamba_d_state"]
    r = config["mamba_dt_rank"]
    kc = config["mamba_d_conv"]
    t = k * n
    pairs = k * n * (n + 1) // 2
    mamba = 2 * t * (d * 2 * di + kc * di + di * (2 * st + r) + r * di
                     + 2 * di * st + di * d)
    attn = (2 * t * d * dh * (h + 2 * hkv) + 2 * t * h * dh * d
            + 2 * 2 * pairs * h * dh)
    dense = 2 * t * 3 * d * f
    total, moe = 0.0, iter(selected)
    for layer in range(config["num_hidden_layers"]):
        attention = (layer % config["attn_layer_period"]
                     == config["attn_layer_offset"])
        total += attn if attention else mamba
        if (layer % config["expert_layer_period"]
                == config["expert_layer_offset"]):
            total += 2 * t * d * e + next(moe) * (3 * 2 * d * f + 2 * d)
        else:
            total += dense
    return float(total + 2 * t * d * v)
