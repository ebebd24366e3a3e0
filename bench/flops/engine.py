"""Operations one engine batch requires, from the shapes: a prefill of
`batch` rows padded to `prompt` tokens, then `new` greedy decode steps
through the KV cache.

Counted (2 operations per multiply-add): q/k/v/o projections, causal
attention products (each query over its own and earlier positions), the
router, the SwiGLU FFN of the top-k experts of every token, and the
unembedding of the positions whose logits are used (the prefill's last
and every decode step's).  Not counted: work on experts a token did not
select, norms, softmaxes and rotary positions.
"""

from __future__ import annotations


def batch_flops(config: dict, batch: int, prompt: int, new: int) -> float:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    hkv = config["num_key_value_heads"]
    dh = d // h
    e = config["num_local_experts"]
    k = config["num_experts_per_tok"]
    f = config["intermediate_size"]
    v = config["vocab_size"]
    layers = config["num_hidden_layers"]
    tokens = batch * (prompt + new)
    # key positions each query attends: prompt causal, then the cache
    pairs = batch * (prompt * (prompt + 1) // 2
                     + sum(prompt + s + 1 for s in range(new)))
    per_layer = (2 * tokens * d * dh * (h + 2 * hkv) + 2 * tokens * h * dh * d
                 + 2 * 2 * pairs * h * dh
                 + 2 * tokens * d * e
                 + tokens * k * 3 * 2 * d * f)
    return float(layers * per_layer + 2 * batch * (1 + new) * d * v)
