"""Plain float32 forward pass of DeepSeek-V2 as the DMoE protocol serves
one chip's share of it, written from the published model (Hugging Face
`DeepseekV2ForCausalLM`, arXiv 2405.04434): per layer an RMSNorm, then
multi-head latent attention -- q through its low-rank projection and
norm, k and v from the compressed kv and its norm, YaRN rotary positions
on the 64 rope dims (one k_pe shared by every head), causal softmax at
scale 192^-0.5 x mscale(40, 0.707)^2 -- and its residual, then an
RMSNorm and the FFN -- a SwiGLU on the leading dense layer; on a MoE
layer the router's softmax over every routed expert, the top-6 within
the selected nodes weighted by score x 16, the held experts' SwiGLUs and
the shared experts' SwiGLU -- and its residual; a final RMSNorm and an
untied unembedding.

It imports nothing of the program.  Sizes come from the benchmark's
configuration file (Hugging Face key names, with the published expert
count and the held nodes beside the held count), weights are the arrays
the benchmark made (`bench/weights_dsv2.py`), read by the program's
parameter names and widened to float32 one layer, or one block of
experts, at a time.  Matrix products run at `Precision.HIGHEST`.
`mode="fp8"` is the control, as in `moe_ref.py`: every matrix product's
operands rounded to float8 e4m3.

Teacher-forced, as the other references are: each MoE layer takes the
program's node selection alpha, and takes each token's experts as the
top-6 of the program's float32 scores within the selected nodes, so the
expert sets match by construction; their weights are this reference's
own scores x 16.  What the experts outside the held nodes would add is
left out, as the program leaves it out.

Departures from the published model, all of them the protocol's or the
share's:
- the nodes (expert groups) are the protocol's DES choice of at most
  topk_group of them, in place of the 3 groups of highest max score;
- the rope dims are rotated as halves; DeepSeek-V2 first de-interleaves
  pairs, a fixed permutation of the rope columns of `wq_b` and `wkv_a`
  that random weights absorb.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference.moe_ref import _fp8, _mm, _rmsnorm, position_errors

__all__ = ["Dims", "forward", "position_errors", "score_errors",
           "top_experts", "yarn_frequencies"]

#: experts whose SwiGLUs one jitted call computes, at most
EXPERT_BLOCK = 8


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v: int
    vocab: int
    eps: float
    theta: float
    factor: float
    original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    dense_layers: int
    layers: int
    n_group: int
    group: int           # routed experts a node holds
    held_start: int      # first held expert's id
    held: int            # routed experts held
    top_k: int
    scaling: float      # routed weights: score x this, unnormalised

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        r = c["rope_scaling"]
        published = c["reduced"]["n_routed_experts"]["published"]
        group = published // c["n_group"]
        assert not c["norm_topk_prob"], "grouped routing weighs unnormalised"
        return cls(
            d=c["hidden_size"], heads=c["num_attention_heads"],
            q_lora=c["q_lora_rank"], kv_lora=c["kv_lora_rank"],
            nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
            v=c["v_head_dim"], vocab=c["vocab_size"],
            eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
            factor=float(r["factor"]),
            original=int(r["original_max_position_embeddings"]),
            beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
            mscale=float(r["mscale"]),
            mscale_all_dim=float(r["mscale_all_dim"]),
            dense_layers=c["first_k_dense_replace"],
            layers=c["num_hidden_layers"], n_group=c["n_group"],
            group=group, held_start=c["held_nodes"][0] * group,
            held=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
            scaling=float(c["routed_scaling_factor"]))


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_frequencies(dims: Dims) -> np.ndarray:
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies (rope/2,):
    freq / factor below the correction dim of beta_slow, freq above that
    of beta_fast, a linear ramp between, at the original positions."""
    dim, base = dims.rope, dims.theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / dims.factor

    def corr(rot):
        return (dim * math.log(dims.original / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(dims.beta_fast)), 0)
    high = min(math.ceil(corr(dims.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def _rope(x, dims: Dims):
    """x: (B, S, H, rope); positions 0..S-1; halves rotated together;
    cos and sin x mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    s, half = x.shape[1], dims.rope // 2
    freqs = jnp.asarray(yarn_frequencies(dims), jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    mag = (_mscale(dims.factor, dims.mscale)
           / _mscale(dims.factor, dims.mscale_all_dim))
    cos = (jnp.cos(ang) * mag)[None, :, None, :]
    sin = (jnp.sin(ang) * mag)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(dims: Dims) -> float:
    return ((dims.nope + dims.rope) ** -0.5
            * _mscale(dims.factor, dims.mscale_all_dim) ** 2)


@partial(jax.jit, static_argnames=("dims", "mode"))
def attention(x, stage, layer, *, dims: Dims, mode: str):
    """Multi-head latent attention of `layer` of a stage, and its
    residual.  x: (B, S, d) float32."""
    at = jax.tree.map(lambda a: a[layer], stage["attn"])
    h = _rmsnorm(x, stage["norm1"][layer], dims.eps)
    q_lat = _rmsnorm(_mm("bsd,dr->bsr", h, at["wq_a"], mode), at["q_norm"],
                     dims.eps)
    q = _mm("bsr,rhe->bshe", q_lat, at["wq_b"], mode)
    q_nope, q_pe = q[..., :dims.nope], _rope(q[..., dims.nope:], dims)
    kv_a = _mm("bsd,dr->bsr", h, at["wkv_a"], mode)
    ckv = _rmsnorm(kv_a[..., :dims.kv_lora], at["kv_norm"], dims.eps)
    k_pe = _rope(kv_a[..., None, dims.kv_lora:], dims)       # (B, S, 1, r)
    kv = _mm("bsr,rhe->bshe", ckv, at["wkv_b"], mode)
    k_nope, v = kv[..., :dims.nope], kv[..., dims.nope:]
    scores = (_mm("bqhe,bkhe->bhqk", q_nope, k_nope, mode)
              + _mm("bqhe,bkxe->bhqk", q_pe, k_pe, mode)) * softmax_scale(dims)
    s = x.shape[1]
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhe->bqhe", probs, v, mode)
    return x + _mm("bqhe,hed->bqd", o, at["wo"], mode)


def _swiglu(h, f, mode):
    g = _mm("bsd,df->bsf", h, f["w_gate"], mode)
    u = _mm("bsd,df->bsf", h, f["w_up"], mode)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, f["w_down"], mode)


@partial(jax.jit, static_argnames=("dims", "mode"))
def dense_ffn(x, stage, layer, *, dims: Dims, mode: str):
    """The dense layer's SwiGLU, and its residual."""
    f = jax.tree.map(lambda a: a[layer], stage["ffn"])
    return x + _swiglu(_rmsnorm(x, stage["norm2"][layer], dims.eps), f, mode)


@partial(jax.jit, static_argnames=("dims", "mode"))
def gate(x, stage, layer, *, dims: Dims, mode: str):
    """The FFN's RMSNorm, the router's softmax over every routed expert
    (h, gates (B, S, E)), and the shared experts' SwiGLU."""
    h = _rmsnorm(x, stage["norm2"][layer], dims.eps)
    router = stage["ffn"]["w_gate_router"][layer]
    gates = jax.nn.softmax(_mm("bsd,de->bse", h, router, mode), axis=-1)
    shared = jax.tree.map(lambda a: a[layer], stage["ffn"]["shared"])
    return h, gates, _swiglu(h, shared, mode)


@partial(jax.jit, static_argnames=("mode",))
def experts(h, ffn, layer, j0, w, *, mode: str):
    """The weighted sum over a block of held experts from local id j0:
    sum_j w[..., j] SwiGLU_j(h); w: (B, S, block)."""
    w1, wu, w2 = (jax.lax.dynamic_slice_in_dim(ffn[n][layer], j0,
                                               w.shape[-1])
                  for n in ("w1", "wu", "w2"))
    g = _mm("bsd,edf->bsef", h, w1, mode)
    u = _mm("bsd,edf->bsef", h, wu, mode)
    y = _mm("bsef,efd->bsed", jax.nn.silu(g) * u, w2, mode)
    return jnp.einsum("bsed,bse->bsd", y, w, precision=jax.lax.Precision.HIGHEST)


def top_experts(scores, alpha, dims: Dims) -> np.ndarray:
    """Each token's top_k experts (K, N, top_k) by `scores` (K, N, E)
    among the experts of the nodes alpha (K, N, n_group) selects, the
    lower id first among equal scores; -1 where fewer are selected."""
    s = np.asarray(scores, np.float64)
    sel = np.repeat(np.asarray(alpha) > 0, dims.group, axis=-1)
    order = np.argsort(np.where(sel, -s, np.inf), axis=-1,
                       kind="stable")[..., :dims.top_k]
    return np.where(np.take_along_axis(sel, order, -1), order, -1)


def _held_weights(chosen, gates, dims: Dims) -> jnp.ndarray:
    """The routed weights of the held experts (K, N, held): score x
    routed_scaling_factor of each chosen expert that is held here."""
    g = np.asarray(gates, np.float64)
    ok = chosen >= 0
    w = np.where(ok, np.take_along_axis(g, np.maximum(chosen, 0), -1), 0.0)
    w = w * dims.scaling
    out = np.zeros(chosen.shape[:-1] + (dims.held,))
    local = chosen - dims.held_start
    held = ok & (local >= 0) & (local < dims.held)
    idx = np.nonzero(held)
    np.add.at(out, (*idx[:-1], local[idx]), w[idx])
    return jnp.asarray(out, jnp.float32)


def score_errors(got, want) -> np.ndarray:
    """Per token, the L1 distance between two of the router's
    distributions over the routed experts (..., E): the mass routed
    elsewhere, counted from both sides, in [0, 2] whatever E is."""
    return np.abs(np.asarray(got, np.float64)
                  - np.asarray(want, np.float64)).sum(-1)


@partial(jax.jit, static_argnames=("dims", "mode"))
def unembed(x, final_norm, table, *, dims: Dims, mode: str):
    return _mm("bsd,vd->bsv", _rmsnorm(x, final_norm, dims.eps), table, mode)


def forward(params, tokens, alphas, scores, dims: Dims, mode: str = "f32"):
    """The protocol pass of one chip's share: tokens (K, N); per MoE
    layer r, `alphas[r]` the (K, N, n_group) node selection and
    `scores[r]` the program's (K, N, E) router scores, from which the
    experts are taken.  Returns (logits (K, N, V), [gates (K, N, E) per
    MoE layer])."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, alphas, scores, dims, mode)


def _forward(params, tokens, alphas, scores, dims: Dims, mode: str):
    stages = params["stages"]
    block = math.gcd(EXPERT_BLOCK, dims.held)
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0)
    x = x.astype(jnp.float32)
    if mode == "fp8":
        x = _fp8(x)
    gates_all = []
    for layer in range(dims.layers):
        dense = layer < dims.dense_layers
        stage = stages["stage0"] if dense else stages["stage1"]
        li = layer if dense else layer - dims.dense_layers
        x = attention(x, stage, li, dims=dims, mode=mode)
        if dense:
            x = dense_ffn(x, stage, li, dims=dims, mode=mode)
            continue
        h, gates, shared = gate(x, stage, li, dims=dims, mode=mode)
        chosen = top_experts(scores[li], alphas[li], dims)
        w = _held_weights(chosen, gates, dims)
        y = shared
        for j0 in range(0, dims.held, block):
            wb = w[..., j0:j0 + block]
            if bool(jnp.any(wb != 0)):
                y = y + experts(h, stage["ffn"], li, j0, wb, mode=mode)
        x = x + y
        gates_all.append(gates)
    logits = unembed(x, params["final_norm"], params["unembed"], dims=dims,
                     mode=mode)
    return logits, gates_all
