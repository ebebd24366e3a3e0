"""Operations one protocol pass of a DeepSeek-V2 share requires, and the
operations and bytes of its expert-FFN step, from the shapes and the
program's counts.

Counted (2 operations per multiply-add), per token and layer: MLA's
q down- and up-projections, the kv down-projection (with the shared
rope key), the kv up-projection, the output projection and the causal
products (pairs with key <= query only, over the 192 qk and 128 v dims
of each head); the dense layer's SwiGLU; on each MoE layer the router
over every routed expert, the shared experts' SwiGLU, and the SwiGLU of
each (token, expert) pair the combine weighed from the held experts
(`routed_pairs` of the pass) with its weighted sum; then the
unembedding.  Not counted: the held experts' work on tokens that did
not choose them, which the program's dense expert FFN also does, norms,
softmaxes and rotary positions.

The expert-FFN step (`DMoESimulator._ffn_step`) computes every held
expert's SwiGLU for every token and the shared experts' SwiGLU.  Its
operations are all of that; its bytes the least it must move: the held
and shared weights read once, the step's input read and its outputs
written once, in bf16.
"""

from __future__ import annotations

BF16 = 2


def _dims(config: dict):
    return (config["hidden_size"], config["num_attention_heads"],
            config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"])


def pass_flops(config: dict, k: int, n: int, routed_pairs: int) -> float:
    """config: the configuration file (Hugging Face key names); k queries
    of n tokens; `routed_pairs` the (token, expert) pairs the pass's
    combines weighed from the held experts."""
    d, h, ql, kvl, dn, dr, dv = _dims(config)
    t = k * n
    pairs = k * n * (n + 1) // 2
    mla = 2 * t * (d * ql + ql * h * (dn + dr) + d * (kvl + dr)
                   + kvl * h * (dn + dv) + h * dv * d)
    mla += 2 * pairs * h * ((dn + dr) + dv)
    dense_layers = config["first_k_dense_replace"]
    moe_layers = config["num_hidden_layers"] - dense_layers
    published = config["reduced"]["n_routed_experts"]["published"]
    f = config["moe_intermediate_size"]
    shared = 2 * t * 3 * d * f * config["n_shared_experts"]
    router = 2 * t * d * published
    dense = 2 * t * 3 * d * config["intermediate_size"]
    total = config["num_hidden_layers"] * mla + dense_layers * dense
    total += moe_layers * (router + shared)
    total += routed_pairs * (3 * 2 * d * f + 2 * d)
    return float(total + 2 * t * d * config["vocab_size"])


def ffn_step_cost(config: dict, k: int, n: int) -> tuple[float, float]:
    """(operations, bytes) of one expert-FFN step over k queries of n
    tokens: every held expert and the shared experts on every token."""
    d = config["hidden_size"]
    f = config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    fs = f * config["n_shared_experts"]
    t = k * n
    flops = 2 * t * 3 * d * (held * f + fs)
    weights = 3 * d * (held * f + fs) * BF16
    acts = t * d * BF16 * (1 + held + 1)       # h in; experts' and shared out
    return float(flops), float(weights + acts)
