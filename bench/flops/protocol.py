"""Operations one protocol pass requires, from the shapes and the
recorded selection.

Counted (2 operations per multiply-add): the q/k/v/o projections, the
causal attention products (pairs with key <= query only), the router,
the SwiGLU FFN of each selected (token, expert) pair, the Eq.-8 combine
and the unembedding.  Not counted: the FFN work of experts that were not
selected, which the program's dense all-expert FFN also does, norms,
softmaxes and rotary positions.
"""

from __future__ import annotations


def pass_flops(config: dict, k: int, n: int, selected) -> float:
    """config: the configuration file (Hugging Face key names); k queries
    of n tokens; `selected[l]` the number of (token, expert) pairs that
    layer l selected."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    hkv = config["num_key_value_heads"]
    dh = d // h
    e = config["num_local_experts"]
    f = config["intermediate_size"]
    v = config["vocab_size"]
    t = k * n
    pairs = k * n * (n + 1) // 2
    attn = 2 * t * d * dh * (h + 2 * hkv) + 2 * t * h * dh * d
    attn += 2 * 2 * pairs * h * dh
    router = 2 * t * d * e
    total = 0.0
    for sel in selected:
        total += attn + router + sel * (3 * 2 * d * f + 2 * d)
    return float(total + 2 * t * d * v)
