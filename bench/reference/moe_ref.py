"""Plain float32 forward pass of the decoder-only MoE block that both
configurations run: RMSNorm, grouped-query attention with rotary
positions (rotate-half, as in the Hugging Face Mixtral code), a softmax
router, SwiGLU experts and the Eq.-8 combine, then an untied unembedding.

It imports nothing of the program.  Its sizes come from the benchmark's
configuration file (Hugging Face key names) and its weights are the
arrays the benchmark made (`bench/weights.py`), read by the names of the
program's parameter tree and widened to float32 one layer, or one expert,
at a time so that the pass fits beside the bf16 weights.  Matrix products
run at `Precision.HIGHEST`, so the TPU does not round their inputs.

`mode="fp8"` is the control: every matrix product takes its operands
rounded to float8 (e4m3, one absmax scale per operand), the precision
next below the bfloat16 the configurations state.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    vocab: int
    rope_theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   experts=c["num_local_experts"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]))


def _fp8(x):
    """Round to float8 e4m3 under one absmax scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, b, mode):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, H, Dh); positions 0..S-1; halves rotated together."""
    s, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs      # (S, Dh/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("dims", "mode"))
def attention_and_gate(x, blk, layer, *, dims: Dims, mode: str):
    """One layer's attention with its residual, then the router.
    x: (B, S, d) float32.  Returns (x, h, gates) with h the normed input
    of the experts and gates (B, S, E) the router's softmax."""
    at = jax.tree.map(lambda a: a[layer], blk["attn"])
    h = _rmsnorm(x, blk["norm1"][layer], dims.eps)
    q = _rope(_mm("bsd,dhe->bshe", h, at["wq"], mode), dims.rope_theta)
    k = _rope(_mm("bsd,dhe->bshe", h, at["wk"], mode), dims.rope_theta)
    v = _mm("bsd,dhe->bshe", h, at["wv"], mode)
    rep = dims.heads // dims.kv_heads
    k = jnp.repeat(k, rep, axis=2)      # query head i reads kv head i // rep
    v = jnp.repeat(v, rep, axis=2)
    s = x.shape[1]
    scores = _mm("bqhe,bkhe->bhqk", q, k, mode) / np.sqrt(dims.head_dim)
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhe->bqhe", probs, v, mode)
    x = x + _mm("bqhe,hed->bqd", o, at["wo"], mode)
    h = _rmsnorm(x, blk["norm2"][layer], dims.eps)
    router = blk["ffn"]["w_gate_router"][layer]
    gates = jax.nn.softmax(_mm("bsd,de->bse", h, router, mode), axis=-1)
    return x, h, gates


@partial(jax.jit, static_argnames=("mode",))
def expert(h, ffn, layer, j, *, mode: str):
    """SwiGLU expert j of `layer` on every token: (B, S, d)."""
    w1, wu, w2 = (ffn[n][layer, j] for n in ("w1", "wu", "w2"))
    g = _mm("bsd,df->bsf", h, w1, mode)
    u = _mm("bsd,df->bsf", h, wu, mode)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w2, mode)


@jax.jit
def combine_weights(alpha, gates):
    """Eq. 8: selected gate mass renormalised over the selection."""
    w = alpha.astype(jnp.float32) * gates
    return w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)


@partial(jax.jit, static_argnames=("dims", "mode"))
def unembed(x, final_norm, table, *, dims: Dims, mode: str):
    return _mm("bsd,vd->bsv", _rmsnorm(x, final_norm, dims.eps), table, mode)


def forward(params, tokens, alphas, dims: Dims, mode: str = "f32"):
    """The protocol pass: tokens (K, N); `alphas[l]` the (K, N, E)
    selection of layer l.  Returns (logits (K, N, V) on the device,
    [gates (K, N, E) per layer] on the device)."""
    blk = params["stages"]["stage0"]
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0)
    x = x.astype(jnp.float32)
    if mode == "fp8":
        x = _fp8(x)
    gates_all = []
    for layer, alpha in enumerate(alphas):
        x, h, gates = attention_and_gate(x, blk, layer, dims=dims, mode=mode)
        w = combine_weights(jnp.asarray(alpha), gates)
        y = jnp.zeros_like(x)
        used = np.flatnonzero(np.asarray(alpha).reshape(-1, dims.experts)
                              .any(axis=0))
        for j in used:
            y = y + w[..., j, None] * expert(h, blk["ffn"], layer, int(j),
                                             mode=mode)
        x = x + y
        gates_all.append(gates)
    logits = unembed(x, params["final_norm"], params["unembed"], dims=dims,
                     mode=mode)
    return logits, gates_all


@partial(jax.jit, static_argnames=("top_k",))
def top_k_alpha(gates, *, top_k: int):
    """Published top-k routing: the k experts of highest gate, the lower
    index first among equals."""
    idx = jax.lax.top_k(gates, top_k)[1]
    return jax.nn.one_hot(idx, gates.shape[-1], dtype=jnp.float32).sum(-2)


def routed_forward(params, tokens, dims: Dims, top_k: int,
                   mode: str = "f32"):
    """The served model's forward pass over whole sequences (B, S) with
    its own top-k routing: logits (B, S, V) at every position."""
    blk = params["stages"]["stage0"]
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0)
    x = x.astype(jnp.float32)
    if mode == "fp8":
        x = _fp8(x)
    layers = blk["norm1"].shape[0]
    for layer in range(layers):
        x, h, gates = attention_and_gate(x, blk, layer, dims=dims, mode=mode)
        w = combine_weights(top_k_alpha(gates, top_k=top_k), gates)
        y = jnp.zeros_like(x)
        for j in range(dims.experts):
            y = y + w[..., j, None] * expert(h, blk["ffn"], layer, j,
                                             mode=mode)
        x = x + y
    return unembed(x, params["final_norm"], params["unembed"], dims=dims,
                   mode=mode)


@jax.jit
def served_gaps(ref_logits, served):
    """How far each served token's reference logit lies below the
    reference's best at its position: (B, T) for served (B, T) tokens
    predicted from positions S-T-1 .. S-2 of ref_logits (B, S, V)."""
    t = served.shape[1]
    rows = ref_logits[:, -t - 1:-1]
    got = jnp.take_along_axis(rows, served[..., None], axis=-1)[..., 0]
    return rows.max(-1) - got


@jax.jit
def position_errors(got, want):
    """Per position, the norm of the logit error over the norm of the
    reference's logits: (K, N)."""
    got = got.astype(jnp.float32)
    num = jnp.sqrt(jnp.sum((got - want) ** 2, axis=-1))
    return num / jnp.maximum(jnp.sqrt(jnp.sum(want * want, axis=-1)), 1e-30)
