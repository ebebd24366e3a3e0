"""Plain float32 forward pass of a Jamba period as the DMoE protocol
serves it, written from the published model (Hugging Face
`JambaForCausalLM`, arXiv 2403.19887): per layer an RMSNorm, then the
mixer -- GQA causal attention with no positional encoding, or a Mamba-1
selective scan with RMSNorms on dt, B and C -- and its residual, then an
RMSNorm and the FFN -- the protocol's experts with the Eq.-8 combine on
MoE layers, a SwiGLU elsewhere -- and its residual; a final RMSNorm and
an untied unembedding.  Which layer is attention and which is MoE comes
from the configuration's published `attn_layer_*` and `expert_layer_*`
keys.

It imports nothing of the program.  Sizes come from the benchmark's
configuration file (Hugging Face key names), weights are the arrays the
benchmark made (`bench/weights_hybrid.py`), read by the program's
parameter names, widened to float32 one layer, or one expert, at a time.
The scan runs token by token (`lax.scan`).  Matrix products run at
`Precision.HIGHEST`.  `mode="fp8"` is the control, as in `moe_ref.py`:
every matrix product's operands rounded to float8 e4m3.

Departures from the published model, all of them the protocol's:
- the router: the protocol's softmax gate with the program's selection
  (exact DES under z*gamma0^l, D=2) teacher-forced, and the Eq.-8
  combine renormalising the selected gates, in place of Jamba's top-2
  weighted by the unnormalised softmax;
- the layout of x_proj's output is the program's [B, C, dt] (Hugging
  Face splits [dt, B, C]); the equations are the same.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference.moe_ref import (HIGHEST, _fp8, _mm, _rmsnorm,
                               combine_weights, expert, position_errors,
                               top_k_alpha)

__all__ = ["Dims", "forward", "position_errors", "routed_forward"]


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    vocab: int
    eps: float
    layers: int
    attn_offset: int
    attn_period: int
    expert_offset: int
    expert_period: int
    d_state: int

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   experts=c["num_experts"], vocab=c["vocab_size"],
                   eps=float(c["rms_norm_eps"]),
                   layers=c["num_hidden_layers"],
                   attn_offset=c["attn_layer_offset"],
                   attn_period=c["attn_layer_period"],
                   expert_offset=c["expert_layer_offset"],
                   expert_period=c["expert_layer_period"],
                   d_state=c["mamba_d_state"])

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_period == self.attn_offset

    def is_moe(self, layer: int) -> bool:
        return layer % self.expert_period == self.expert_offset


def _sub(blk, layer, dims: Dims):
    """The weights of `layer` in the program's period-stacked tree: the
    period's index and its sublayer's subtree."""
    return layer // dims.attn_period, blk[f"sub{layer % dims.attn_period}"]


@partial(jax.jit, static_argnames=("dims", "mode"))
def attention(x, sub, period, *, dims: Dims, mode: str):
    """Causal GQA with no positional encoding, and its residual."""
    at = jax.tree.map(lambda a: a[period], sub["mixer"])
    h = _rmsnorm(x, sub["norm1"][period], dims.eps)
    q = _mm("bsd,dhe->bshe", h, at["wq"], mode)
    k = _mm("bsd,dhe->bshe", h, at["wk"], mode)
    v = _mm("bsd,dhe->bshe", h, at["wv"], mode)
    rep = dims.heads // dims.kv_heads
    k = jnp.repeat(k, rep, axis=2)      # query head i reads kv head i // rep
    v = jnp.repeat(v, rep, axis=2)
    s = x.shape[1]
    scores = _mm("bqhe,bkhe->bhqk", q, k, mode) / np.sqrt(dims.head_dim)
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhe->bqhe", probs, v, mode)
    return x + _mm("bqhe,hed->bqd", o, at["wo"], mode)


@partial(jax.jit, static_argnames=("dims", "mode"))
def mamba(x, sub, period, *, dims: Dims, mode: str):
    """Mamba-1 and its residual.  With u the normed input:
        [x; z] = u W_in;  xc = silu(causal depthwise conv(x) + b)
        [B, C, r] = xc W_x;  B, C, r each RMSNorm'd
        dt = softplus(r W_dt + b_dt);  A = -exp(A_log)
        h_t = exp(dt_t A) h_{t-1} + dt_t B_t xc_t;  y_t = C_t h_t + D xc_t
        out = (y * silu(z)) W_out
    """
    m = jax.tree.map(lambda a: a[period], sub["mixer"])
    n = dims.d_state
    u = _rmsnorm(x, sub["norm1"][period], dims.eps)
    xz = _mm("bsd,de->bse", u, m["w_in"], mode)
    di = xz.shape[-1] // 2
    xi, z = xz[..., :di], xz[..., di:]
    conv_w = m["conv_w"].astype(jnp.float32)             # (d_conv, d_inner)
    kc, s = conv_w.shape[0], x.shape[1]
    xpad = jnp.pad(xi, ((0, 0), (kc - 1, 0), (0, 0)))
    conv = sum(xpad[:, i:i + s] * conv_w[i] for i in range(kc))
    xc = jax.nn.silu(conv + m["conv_b"].astype(jnp.float32))
    bcr = _mm("bse,ef->bsf", xc, m["w_bcdt"], mode)
    b_t = _rmsnorm(bcr[..., :n], m["b_norm"], dims.eps)
    c_t = _rmsnorm(bcr[..., n:2 * n], m["c_norm"], dims.eps)
    r = _rmsnorm(bcr[..., 2 * n:], m["dt_norm"], dims.eps)
    dt = jax.nn.softplus(_mm("bsr,re->bse", r, m["w_dt"], mode)
                         + m["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(m["a_log"].astype(jnp.float32))         # (d_inner, n)
    d_skip = m["d_skip"].astype(jnp.float32)

    def step(h, inp):
        dt_t, b, c, x_t = inp                            # (B, di), (B, n) ..
        h = (jnp.exp(dt_t[..., None] * a) * h
             + dt_t[..., None] * b[:, None, :] * x_t[..., None])
        y = jnp.einsum("bdn,bn->bd", h, c, precision=HIGHEST) + d_skip * x_t
        return h, y

    h0 = jnp.zeros((x.shape[0], di, n), jnp.float32)
    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, b_t, c_t, xc))
    _, y = jax.lax.scan(step, h0, seq)
    y = jnp.moveaxis(y, 0, 1) * jax.nn.silu(z)
    return x + _mm("bse,ed->bsd", y, m["w_out"], mode)


@partial(jax.jit, static_argnames=("dims", "mode"))
def gate(x, sub, period, *, dims: Dims, mode: str):
    """The FFN's RMSNorm and the router's softmax: (h, gates (B, S, E))."""
    h = _rmsnorm(x, sub["norm2"][period], dims.eps)
    router = sub["ffn"]["w_gate_router"][period]
    return h, jax.nn.softmax(_mm("bsd,de->bse", h, router, mode), axis=-1)


@partial(jax.jit, static_argnames=("dims", "mode"))
def dense_ffn(x, sub, period, *, dims: Dims, mode: str):
    """The SwiGLU of a dense layer, and its residual."""
    f = jax.tree.map(lambda a: a[period], sub["ffn"])
    h = _rmsnorm(x, sub["norm2"][period], dims.eps)
    g = _mm("bsd,df->bsf", h, f["w_gate"], mode)
    u = _mm("bsd,df->bsf", h, f["w_up"], mode)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, f["w_down"], mode)


@partial(jax.jit, static_argnames=("dims", "mode"))
def unembed(x, final_norm, table, *, dims: Dims, mode: str):
    return _mm("bsd,vd->bsv", _rmsnorm(x, final_norm, dims.eps), table, mode)


def forward(params, tokens, alphas, dims: Dims, mode: str = "f32"):
    """The protocol pass: tokens (K, N); `alphas[r]` the (K, N, E)
    selection of the r-th MoE layer, or a function of that layer's gates
    that returns it.  Returns (logits (K, N, V), [gates (K, N, E) per
    MoE layer])."""
    blk = params["stages"]["stage0"]
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0)
    x = x.astype(jnp.float32)
    if mode == "fp8":
        x = _fp8(x)
    alphas = alphas if callable(alphas) else iter(alphas)
    gates_all = []
    for layer in range(dims.layers):
        period, sub = _sub(blk, layer, dims)
        mixer = attention if dims.is_attention(layer) else mamba
        x = mixer(x, sub, period, dims=dims, mode=mode)
        if not dims.is_moe(layer):
            x = dense_ffn(x, sub, period, dims=dims, mode=mode)
            continue
        h, gates = gate(x, sub, period, dims=dims, mode=mode)
        alpha = np.asarray(alphas(gates) if callable(alphas)
                           else next(alphas))
        w = combine_weights(jnp.asarray(alpha), gates)
        y = jnp.zeros_like(x)
        for j in np.flatnonzero(alpha.reshape(-1, dims.experts).any(axis=0)):
            y = y + w[..., j, None] * expert(h, sub["ffn"], period, int(j),
                                             mode=mode)
        x = x + y
        gates_all.append(gates)
    logits = unembed(x, params["final_norm"], params["unembed"], dims=dims,
                     mode=mode)
    return logits, gates_all


def routed_forward(params, tokens, dims: Dims, top_k: int,
                   mode: str = "f32"):
    """The model's forward pass over whole sequences (B, S) with the
    published top-k routing in place of the protocol's selection (the
    Eq.-8 combine kept): logits (B, S, V)."""
    return forward(params, tokens, lambda g: top_k_alpha(g, top_k=top_k),
                   dims, mode)[0]
