"""Attention-free sequence mixers: RWKV6 ("Finch") and Mamba-1 selective SSM.

Both expose a full-sequence form (training / prefill; parallel where the
math allows — Mamba uses `jax.lax.associative_scan`, RWKV6 a time scan
whose Pallas chunked kernel lives in repro/kernels/rwkv_scan.py) and an
O(1)-state single-token decode step (`*_decode`) — this is what makes
long_500k decode native for these families.

RWKV6 recurrence (per head, k/v dims dk = dv = head_dim):
    y_t = r_t (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T            (w_t data-dependent)

Mamba-1 (diagonal A):
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ;  y_t = C_t h_t + D x_t
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L

LORA_RANK = 32
DECAY_RANK = 64


# ======================================================================
# RWKV6
# ======================================================================

def init_rwkv6(key, cfg: ModelConfig, dtype) -> Dict[str, jnp.ndarray]:
    d = cfg.d_model
    ks = jax.random.split(key, 12)
    n_mix = 5  # r, k, v, w, g
    return {
        # data-dependent token-shift (ddlerp)
        "mu_base": jnp.full((d,), 0.5, dtype=dtype),
        "mu": (jnp.ones((n_mix, d), dtype=jnp.float32) * 0.5).astype(dtype),
        "mix_a": L.dense_init(ks[0], d, (n_mix, LORA_RANK), dtype),
        "mix_b": (jax.random.normal(ks[1], (n_mix, LORA_RANK, d),
                                    dtype=jnp.float32) * 0.01).astype(dtype),
        # projections
        "w_r": L.dense_init(ks[2], d, d, dtype),
        "w_k": L.dense_init(ks[3], d, d, dtype),
        "w_v": L.dense_init(ks[4], d, d, dtype),
        "w_g": L.dense_init(ks[5], d, d, dtype),
        "w_o": L.dense_init(ks[6], d, d, dtype),
        # data-dependent decay
        "w0": (jnp.zeros((d,), dtype=jnp.float32) - 0.5).astype(dtype),
        "decay_a": L.dense_init(ks[7], d, DECAY_RANK, dtype),
        "decay_b": (jax.random.normal(ks[8], (DECAY_RANK, d),
                                      dtype=jnp.float32) * 0.01).astype(dtype),
        "u": (jax.random.normal(ks[9], (d,), dtype=jnp.float32)
              * 0.1).astype(dtype),
        "ln_out": L.rmsnorm_init(d, dtype),
    }


def _rwkv6_rkvwg(params, x, x_prev, cfg):
    """Token-shift + projections. x: (B,S,d); x_prev: (B,S,d) shifted."""
    dx = x_prev - x
    base = x + dx * params["mu_base"]
    delta = jnp.einsum("bsd,dnr->bsnr", jnp.tanh(base), params["mix_a"])
    delta = jnp.einsum("bsnr,nrd->bsnd", delta, params["mix_b"])
    mixed = x[:, :, None, :] + dx[:, :, None, :] * (params["mu"] + delta)
    xr, xk, xv, xw, xg = [mixed[:, :, i] for i in range(5)]
    r = jnp.einsum("bsd,de->bse", xr, params["w_r"])
    k = jnp.einsum("bsd,de->bse", xk, params["w_k"])
    v = jnp.einsum("bsd,de->bse", xv, params["w_v"])
    g = jax.nn.silu(jnp.einsum("bsd,de->bse", xg, params["w_g"])
                    .astype(jnp.float32))
    # decay in (0, 1): w = exp(-exp(w0 + lora(xw)))
    dec = jnp.einsum("bsd,dr->bsr", jnp.tanh(xw), params["decay_a"])
    dec = jnp.einsum("bsr,rd->bsd", dec, params["decay_b"])
    logw = params["w0"].astype(jnp.float32) + dec.astype(jnp.float32)
    w = jnp.exp(-jnp.exp(logw))
    return r, k, v, w, g


def _rwkv6_heads(cfg, *arrs):
    hd = cfg.ssm.head_dim
    h = cfg.d_model // hd
    return [a.reshape(*a.shape[:-1], h, hd) for a in arrs]


def rwkv6_mix(params, x, cfg: ModelConfig, *,
              state: Optional[jnp.ndarray] = None,
              x_prev_last: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-sequence RWKV6 time-mixing.

    Returns (y, final_state, last_x) so callers can seed decode.
    state: (B, H, dk, dv) initial (zeros if None).
    """
    b, s, d = x.shape
    hd = cfg.ssm.head_dim
    h = d // hd
    if x_prev_last is None:
        x_prev_last = jnp.zeros((b, d), dtype=x.dtype)
    x_prev = jnp.concatenate([x_prev_last[:, None], x[:, :-1]], axis=1)

    r, k, v, w, g = _rwkv6_rkvwg(params, x, x_prev, cfg)
    r, k, v, w = _rwkv6_heads(cfg, r, k, v, w)           # (B,S,H,hd)
    u = params["u"].astype(jnp.float32).reshape(h, hd)

    if state is None:
        state = jnp.zeros((b, h, hd, hd), dtype=jnp.float32)

    def step(S, inp):
        rt, kt, vt, wt = inp                             # (B,H,hd) each fp32
        kv = kt[..., :, None] * vt[..., None, :]         # (B,H,dk,dv)
        yt = jnp.einsum("bhk,bhkv->bhv", rt, S + u[None, :, :, None] * kv)
        S = wt[..., :, None] * S + kv
        return S, yt

    seq = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (r, k, v, w))
    final_state, y = jax.lax.scan(step, state, seq)
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, d)           # (B,S,d)
    y = L.rmsnorm(y, params["ln_out"], cfg.norm_eps) * g
    out = jnp.einsum("bsd,de->bse", y.astype(x.dtype), params["w_o"])
    return out, final_state, x[:, -1]


def rwkv6_decode(params, x, cache, cfg: ModelConfig):
    """One-token step. x: (B,1,d); cache: {state, x_prev, idx}."""
    b, _, d = x.shape
    hd = cfg.ssm.head_dim
    h = d // hd
    x_prev = cache["x_prev"][:, None]                    # (B,1,d)
    r, k, v, w, g = _rwkv6_rkvwg(params, x, x_prev, cfg)
    r, k, v, w = _rwkv6_heads(cfg, r, k, v, w)
    u = params["u"].astype(jnp.float32).reshape(h, hd)
    S = cache["state"]
    rt, kt, vt, wt = (a[:, 0].astype(jnp.float32) for a in (r, k, v, w))
    kv = kt[..., :, None] * vt[..., None, :]
    yt = jnp.einsum("bhk,bhkv->bhv", rt, S + u[None, :, :, None] * kv)
    S = wt[..., :, None] * S + kv
    y = yt.reshape(b, 1, d)
    y = L.rmsnorm(y, params["ln_out"], cfg.norm_eps) * g
    out = jnp.einsum("bsd,de->bse", y.astype(x.dtype), params["w_o"])
    new_cache = {"state": S, "x_prev": x[:, 0], "idx": cache["idx"] + 1}
    return out, new_cache


def init_rwkv6_channel_mix(key, cfg: ModelConfig, dtype) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    k1, k2 = jax.random.split(key)
    return {
        "mu_k": jnp.full((d,), 0.5, dtype=dtype),
        "w_in": L.dense_init(k1, d, f, dtype),
        "w_out": L.dense_init(k2, f, d, dtype),
    }


def rwkv6_channel_mix(params, x, *, x_prev_last=None):
    b, s, d = x.shape
    if x_prev_last is None:
        x_prev_last = jnp.zeros((b, d), dtype=x.dtype)
    x_prev = jnp.concatenate([x_prev_last[:, None], x[:, :-1]], axis=1)
    xk = x + (x_prev - x) * params["mu_k"]
    h = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", xk, params["w_in"])))
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"]), x[:, -1]


def init_rwkv6_state(batch: int, cfg: ModelConfig, dtype) -> Dict:
    hd = cfg.ssm.head_dim
    h = cfg.d_model // hd
    return {
        "state": jnp.zeros((batch, h, hd, hd), dtype=jnp.float32),
        "x_prev": jnp.zeros((batch, cfg.d_model), dtype=dtype),
        "x_prev_ffn": jnp.zeros((batch, cfg.d_model), dtype=dtype),
        "idx": jnp.zeros((), dtype=jnp.int32),
    }


# ======================================================================
# Mamba-1
# ======================================================================

def init_mamba(key, cfg: ModelConfig, dtype) -> Dict[str, jnp.ndarray]:
    d = cfg.d_model
    di = cfg.ssm.expand * d
    n = cfg.ssm.d_state
    kconv = cfg.ssm.d_conv
    ks = jax.random.split(key, 6)
    dt_rank = max(d // 16, 1)
    a_init = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None], (di, 1))
    params = {
        "w_in": L.dense_init(ks[0], d, 2 * di, dtype),
        "conv_w": (jax.random.normal(ks[1], (kconv, di), dtype=jnp.float32)
                   / np.sqrt(kconv)).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype=dtype),
        "w_bcdt": L.dense_init(ks[2], di, 2 * n + dt_rank, dtype),
        "w_dt": L.dense_init(ks[3], dt_rank, di, dtype),
        "dt_bias": jnp.full((di,), -4.0, dtype=dtype),  # softplus(-4) ~ 0.018
        "a_log": jnp.log(a_init).astype(jnp.float32),
        "d_skip": jnp.ones((di,), dtype=jnp.float32),
        "w_out": L.dense_init(ks[4], di, d, dtype),
    }
    if cfg.ssm.inner_norms:
        params.update(dt_norm=L.rmsnorm_init(dt_rank, dtype),
                      b_norm=L.rmsnorm_init(n, dtype),
                      c_norm=L.rmsnorm_init(n, dtype))
    return params


def _mamba_bcdt(params, xc, cfg):
    """B, C and dt from the convolved input.  With `inner_norms` (Jamba)
    each of dt's low-rank input, B and C passes an RMSNorm first."""
    n = cfg.ssm.d_state
    bcdt = jnp.einsum("bsd,de->bse", xc, params["w_bcdt"])
    b_mat = bcdt[..., :n]
    c_mat = bcdt[..., n:2 * n]
    dt_in = bcdt[..., 2 * n:]
    if cfg.ssm.inner_norms:
        b_mat = L.rmsnorm(b_mat, params["b_norm"], cfg.norm_eps)
        c_mat = L.rmsnorm(c_mat, params["c_norm"], cfg.norm_eps)
        dt_in = L.rmsnorm(dt_in, params["dt_norm"], cfg.norm_eps)
    dt = jnp.einsum("bsr,rd->bsd", dt_in, params["w_dt"])
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    return b_mat, c_mat, dt


def mamba_mix(params, x, cfg: ModelConfig, *,
              state: Optional[Dict] = None):
    """Full-sequence Mamba. x: (B,S,d). Returns (y, final_state_dict).

    The SSM recurrence runs CHUNKED (`cfg.ssm.scan_chunk`): the
    state-expanded intermediates a_bar / Bx are (B, C, d_inner, d_state)
    fp32 per chunk instead of the full (B, S, ...) — the full-sequence
    associative scan was the dominant temp on jamba prefill_32k
    (70 GB/device; EXPERIMENTS.md §Perf D).  Chunks chain exactly: the
    carried (h, conv_tail) makes chunked == full-sequence bit-for-bit up
    to fp32 reassociation.
    """
    b, s, d = x.shape
    di = cfg.ssm.expand * d
    n = cfg.ssm.d_state
    kconv = cfg.ssm.d_conv

    xz = jnp.einsum("bsd,de->bse", x, params["w_in"])
    xi, z = xz[..., :di], xz[..., di:]

    if state is not None:
        prev = state["conv"]                             # (B, kconv-1, di)
        h0 = state["h"].astype(jnp.float32)              # (B, di, n)
    else:
        prev = jnp.zeros((b, kconv - 1, di), dtype=xi.dtype)
        h0 = jnp.zeros((b, di, n), dtype=jnp.float32)

    # chunking: pick the largest divisor of S <= scan_chunk
    csz = min(cfg.ssm.scan_chunk, s)
    while s % csz != 0:
        csz -= 1
    nc = s // csz
    xi_c = jnp.moveaxis(xi.reshape(b, nc, csz, di), 1, 0)  # (nc,B,C,di)

    a = -jnp.exp(params["a_log"])                        # (di, n)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2

    def chunk_step(carry, xi_k):
        h_in, tail = carry                               # (B,di,n), (B,kc-1,di)
        xpad = jnp.concatenate([tail, xi_k], axis=1)     # (B, C+kc-1, di)
        conv = sum(
            xpad[:, i: i + csz] * params["conv_w"][i] for i in range(kconv)
        ) + params["conv_b"]
        xc = jax.nn.silu(conv.astype(jnp.float32)).astype(xi_k.dtype)
        b_mat, c_mat, dt = _mamba_bcdt(params, xc, cfg)
        a_bar = jnp.exp(dt[..., None] * a)               # (B,C,di,n)
        bx = (dt[..., None] * b_mat[:, :, None, :]
              * xc.astype(jnp.float32)[..., None])
        bx = bx.at[:, 0].add(a_bar[:, 0] * h_in)
        _, h = jax.lax.associative_scan(combine, (a_bar, bx), axis=1)
        y = jnp.einsum("bsdn,bsn->bsd", h, c_mat.astype(jnp.float32))
        y = y + params["d_skip"] * xc.astype(jnp.float32)
        return (h[:, -1], xpad[:, -(kconv - 1):]), y.astype(x.dtype)

    (h_last, tail), y = jax.lax.scan(chunk_step, (h0, prev), xi_c)
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, di).astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), params["w_out"])
    final = {"h": h_last, "conv": tail}
    return out, final


def mamba_decode(params, x, cache, cfg: ModelConfig):
    """One-token step. cache: {h: (B,di,n), conv: (B,kconv-1,di), idx}."""
    b, _, d = x.shape
    di = cfg.ssm.expand * d
    kconv = cfg.ssm.d_conv
    xz = jnp.einsum("bsd,de->bse", x, params["w_in"])
    xi, z = xz[:, 0, :di], xz[:, 0, di:]

    conv_win = jnp.concatenate([cache["conv"], xi[:, None]], axis=1)  # (B,kconv,di)
    conv = jnp.einsum("bkd,kd->bd", conv_win, params["conv_w"]) + params["conv_b"]
    xc = jax.nn.silu(conv.astype(jnp.float32)).astype(x.dtype)

    b_mat, c_mat, dt = _mamba_bcdt(params, xc[:, None], cfg)
    b_mat, c_mat, dt = b_mat[:, 0], c_mat[:, 0], dt[:, 0]
    a = -jnp.exp(params["a_log"])
    a_bar = jnp.exp(dt[..., None] * a)                   # (B,di,n)
    bx = dt[..., None] * b_mat[:, None, :] * xc.astype(jnp.float32)[..., None]
    h = a_bar * cache["h"] + bx
    y = jnp.einsum("bdn,bn->bd", h, c_mat.astype(jnp.float32))
    y = y + params["d_skip"] * xc.astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    out = jnp.einsum("be,ed->bd", y.astype(x.dtype), params["w_out"])
    new_cache = {"h": h, "conv": conv_win[:, 1:], "idx": cache["idx"] + 1}
    return out[:, None], new_cache


def init_mamba_state(batch: int, cfg: ModelConfig, dtype) -> Dict:
    di = cfg.ssm.expand * cfg.d_model
    return {
        "h": jnp.zeros((batch, di, cfg.ssm.d_state), dtype=jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm.d_conv - 1, di), dtype=dtype),
        "idx": jnp.zeros((), dtype=jnp.int32),
    }
