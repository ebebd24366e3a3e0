"""Attention: GQA (full / causal / sliding-window), chunked flash-style
prefill, MLA (DeepSeek-V3) with absorbed-weight decode, cross-attention,
and KV caches.

Memory discipline: prefill at 32k+ never materializes the (S, S) score
matrix — `chunked_attention` runs an online-softmax scan over KV chunks
per Q chunk (the pure-jnp twin of the Pallas flash kernel in
repro/kernels; the kernel is used on real TPUs, this path is the
lowering-safe reference used by the dry-run and CPU tests).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L

NEG_INF = -1e30
CHUNKED_THRESHOLD = 2048  # use chunked attention when S_kv exceeds this


# ----------------------------------------------------------------------
# caches
# ----------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype) -> Dict[str, jnp.ndarray]:
    # idx is PER SEQUENCE: continuous batching admits requests into slots
    # at different times, so every slot tracks its own write position.
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype=dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype=dtype),
        "idx": jnp.zeros((batch,), dtype=jnp.int32),
    }


def init_mla_cache(batch: int, max_len: int, kv_lora: int, rope_dim: int,
                   dtype) -> Dict[str, jnp.ndarray]:
    return {
        "ckv": jnp.zeros((batch, max_len, kv_lora), dtype=dtype),
        "krope": jnp.zeros((batch, max_len, rope_dim), dtype=dtype),
        "idx": jnp.zeros((batch,), dtype=jnp.int32),
    }


# ----------------------------------------------------------------------
# masks & softmax attention cores
# ----------------------------------------------------------------------

_PAD_POS = 2 ** 29  # kv positions >= this are padding (chunked path)


def _mask_bias(q_pos, kv_pos, *, causal: bool, window: int) -> jnp.ndarray:
    """(..., Sq, Sk) additive bias: 0 allowed / NEG_INF masked."""
    d = q_pos[..., :, None] - kv_pos[..., None, :]
    ok = (kv_pos < _PAD_POS)[..., None, :] & jnp.ones(d.shape, dtype=bool)
    if causal:
        ok &= d >= 0
    if window > 0:
        ok &= d < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q, k, v, bias, scale) -> jnp.ndarray:
    """Naive softmax attention. q: (B,Sq,Hkv,R,Dh); k/v: (B,Sk,Hkv,Dh).
    bias: (B or 1, 1, Sq, Sk) additive.

    Mixed precision via preferred_element_type: upcasting K/V with
    .astype(f32) materializes an fp32 copy of the WHOLE KV cache per
    decode layer (XLA hoists the loop-invariant convert) — instead the
    dot takes bf16 operands and accumulates in f32 (MXU-native)."""
    scores = jnp.einsum("bqhrd,bkhd->bhrqk", q.astype(k.dtype), k,
                        preferred_element_type=jnp.float32) * scale
    scores = scores + bias[:, None, :, :][:, :, None]  # (B,1,1,Sq,Sk) broadcast
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out


def chunked_attention(q, k, v, *, q_pos, kv_pos, causal: bool, window: int,
                      q_chunk: int = 1024, kv_chunk: int = 1024) -> jnp.ndarray:
    """Online-softmax attention; never materializes (Sq, Sk).

    q: (B, Sq, Hkv, R, Dh); k, v: (B, Sk, Hkv, Dh);
    q_pos: (Sq,), kv_pos: (Sk,) absolute positions.
    Returns (B, Sq, Hkv, R, Dh) fp32.
    """
    b, sq, hkv, r, dh = q.shape
    sk = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    # pad to multiples
    nq = -(-sq // q_chunk)
    nk = -(-sk // kv_chunk)
    pq, pk = nq * q_chunk - sq, nk * kv_chunk - sk
    scale = 1.0 / np.sqrt(dh)

    qf = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qp = jnp.pad(q_pos, (0, pq), constant_values=-1)
    kp = jnp.pad(kv_pos, (0, pk), constant_values=2**30)

    qf = qf.reshape(b, nq, q_chunk, hkv, r, dh)
    kf = jnp.moveaxis(kf.reshape(b, nk, kv_chunk, hkv, dh), 1, 0)  # (nk, B, ...)
    vf = jnp.moveaxis(vf.reshape(b, nk, kv_chunk, hkv, dh), 1, 0)
    qp = qp.reshape(nq, q_chunk)
    kp = kp.reshape(nk, kv_chunk)

    def per_q_chunk(qc, qpc):
        # qc: (B, Cq, Hkv, R, Dh), qpc: (Cq,)
        m0 = jnp.full((b, hkv, r, q_chunk), NEG_INF, dtype=jnp.float32)
        s0 = jnp.zeros((b, hkv, r, q_chunk), dtype=jnp.float32)
        o0 = jnp.zeros((b, q_chunk, hkv, r, dh), dtype=jnp.float32)

        # checkpointed kv step: the (Cq, Ck) score/prob matrices are
        # recomputed in the backward pass instead of being stored per
        # chunk (the flash-attention recompute trick, jnp edition).
        @jax.checkpoint
        def kv_step(carry, kv):
            m, s, o = carry
            kc, vc, kpc = kv
            bias = _mask_bias(qpc, kpc, causal=causal, window=window)  # (Cq, Ck)
            scores = jnp.einsum("bqhrd,bkhd->bhrqk", qc.astype(kc.dtype), kc,
                                preferred_element_type=jnp.float32
                                ) * scale + bias
            new_m = jnp.maximum(m, scores.max(axis=-1))
            alpha = jnp.exp(m - new_m)
            p = jnp.exp(scores - new_m[..., None])
            s = s * alpha + p.sum(axis=-1)
            o = o * jnp.moveaxis(alpha, -1, 1)[..., None] + jnp.einsum(
                "bhrqk,bkhd->bqhrd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (new_m, s, o), None

        (m, s, o), _ = jax.lax.scan(kv_step, (m0, s0, o0), (kf, vf, kp))
        denom = jnp.moveaxis(s, -1, 1)[..., None]
        return o / jnp.maximum(denom, 1e-30)

    out = jax.lax.map(lambda x: per_q_chunk(*x), (jnp.moveaxis(qf, 1, 0), qp))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * q_chunk, hkv, r, dh)
    return out[:, :sq]


# ----------------------------------------------------------------------
# GQA attention layer
# ----------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, dtype) -> Dict[str, jnp.ndarray]:
    dh = cfg.resolved_head_dim()
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(k1, cfg.d_model, (cfg.num_heads, dh), dtype),
        "wk": L.dense_init(k2, cfg.d_model, (cfg.num_kv_heads, dh), dtype),
        "wv": L.dense_init(k3, cfg.d_model, (cfg.num_kv_heads, dh), dtype),
        "wo": (jax.random.normal(k4, (cfg.num_heads, dh, cfg.d_model),
                                 dtype=jnp.float32)
               / np.sqrt(cfg.num_heads * dh)).astype(dtype),
    }


def gqa_prefill(params, x, cfg: ModelConfig, *, causal: bool = True,
                window: int = 0, positions: Optional[jnp.ndarray] = None,
                cache: Optional[Dict] = None,
                ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full-sequence attention. x: (B, S, d). Returns (y, updated cache)."""
    b, s, _ = x.shape
    dh = cfg.resolved_head_dim()
    hkv, h = cfg.num_kv_heads, cfg.num_heads
    r = h // hkv
    if positions is None:
        positions = jnp.arange(s)

    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    if cfg.rope:
        q = L.apply_rope(q, positions[None, :], cfg.rope_theta)
        k = L.apply_rope(k, positions[None, :], cfg.rope_theta)
    qg = q.reshape(b, s, hkv, r, dh)

    if s > cfg.attn_chunk_threshold:
        out = chunked_attention(qg, k, v, q_pos=positions, kv_pos=positions,
                                causal=causal, window=window,
                                q_chunk=cfg.attn_q_chunk,
                                kv_chunk=cfg.attn_kv_chunk)
    else:
        bias = _mask_bias(positions, positions, causal=causal,
                          window=window)[None]
        out = _sdpa(qg, k, v, bias, 1.0 / np.sqrt(dh))

    out = out.reshape(b, s, h, dh).astype(x.dtype)
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])

    if cache is not None:
        cache = dict(cache)
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        cache["idx"] = jnp.full((b,), s, dtype=jnp.int32)
    return y, cache


def gqa_decode(params, x, cache, cfg: ModelConfig, *, window: int = 0,
               ) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode step. x: (B, 1, d); cache holds `idx` past tokens."""
    b, s1, _ = x.shape
    assert s1 == 1
    dh = cfg.resolved_head_dim()
    hkv, h = cfg.num_kv_heads, cfg.num_heads
    r = h // hkv
    idx = cache["idx"]                             # (B,) per-slot positions
    pos = idx[:, None]                             # (B, 1)

    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k_new = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v_new = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    if cfg.rope:
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k_new = L.apply_rope(k_new, pos, cfg.rope_theta)

    rows = jnp.arange(b)
    k_cache = cache["k"].at[rows, idx].set(
        k_new[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[rows, idx].set(
        v_new[:, 0].astype(cache["v"].dtype))

    s_max = k_cache.shape[1]
    kv_pos = jnp.arange(s_max)
    valid = kv_pos[None, :] <= idx[:, None]        # (B, S)
    if window > 0:
        valid &= kv_pos[None, :] > (idx - window)[:, None]
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[:, None, :]

    qg = q.reshape(b, 1, hkv, r, dh)
    out = _sdpa(qg, k_cache, v_cache, bias, 1.0 / np.sqrt(dh))
    out = out.reshape(b, 1, h, dh).astype(x.dtype)
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return y, {"k": k_cache, "v": v_cache, "idx": idx + 1}


# ----------------------------------------------------------------------
# MLA (DeepSeek-V3) — low-rank KV compression, absorbed decode
# ----------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype) -> Dict[str, jnp.ndarray]:
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": L.dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype),
        "q_norm": L.rmsnorm_init(cfg.q_lora_rank, dtype),
        "wq_b": L.dense_init(ks[1], cfg.q_lora_rank,
                             (cfg.num_heads, dn + dr), dtype),
        "wkv_a": L.dense_init(ks[2], cfg.d_model, cfg.kv_lora_rank + dr, dtype),
        "kv_norm": L.rmsnorm_init(cfg.kv_lora_rank, dtype),
        "wkv_b": L.dense_init(ks[3], cfg.kv_lora_rank,
                              (cfg.num_heads, dn + dv), dtype),
        "wo": (jax.random.normal(ks[4], (cfg.num_heads, dv, cfg.d_model),
                                 dtype=jnp.float32)
               / np.sqrt(cfg.num_heads * dv)).astype(dtype),
    }


def _mla_qkv_prefill(params, x, cfg, positions):
    b, s, _ = x.shape
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q_lat = L.rmsnorm(jnp.einsum("bsd,dr->bsr", x, params["wq_a"]),
                      params["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhe->bshe", q_lat, params["wq_b"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, positions[None, :], cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    ckv = L.rmsnorm(kv_a[..., : cfg.kv_lora_rank], params["kv_norm"],
                    cfg.norm_eps)
    k_rope = kv_a[..., cfg.kv_lora_rank:]
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions[None, :],
                          cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, k_rope


def mla_prefill(params, x, cfg: ModelConfig, *, window: int = 0,
                positions: Optional[jnp.ndarray] = None,
                cache: Optional[Dict] = None):
    """MLA prefill — expands c_kv to per-head K/V (compute-optimal here)."""
    b, s, _ = x.shape
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    h = cfg.num_heads
    if positions is None:
        positions = jnp.arange(s)
    q_nope, q_rope, ckv, k_rope = _mla_qkv_prefill(params, x, cfg, positions)

    kv = jnp.einsum("bsr,rhe->bshe", ckv, params["wkv_b"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    qg = q[:, :, :, None, :]  # Hkv = H, R = 1

    if s > cfg.attn_chunk_threshold:
        out = chunked_attention(qg, k, v_pad(v, k), q_pos=positions,
                                kv_pos=positions, causal=True, window=window,
                                q_chunk=cfg.attn_q_chunk,
                                kv_chunk=cfg.attn_kv_chunk)
        out = out[..., :dv]
    else:
        bias = _mask_bias(positions, positions, causal=True, window=window)[None]
        out = _sdpa(qg, k, v_pad(v, k), bias, 1.0 / np.sqrt(dn + dr))[..., :dv]
    out = out.reshape(b, s, h, dv).astype(x.dtype)
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])

    if cache is not None:
        cache = dict(cache)
        cache["ckv"] = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0))
        cache["krope"] = jax.lax.dynamic_update_slice(
            cache["krope"], k_rope.astype(cache["krope"].dtype), (0, 0, 0))
        cache["idx"] = jnp.full((b,), s, dtype=jnp.int32)
    return y, cache


def v_pad(v, k):
    """Pad V's head_dim up to K's so chunked/naive cores can share math."""
    dv, dk = v.shape[-1], k.shape[-1]
    if dv == dk:
        return v
    return jnp.pad(v, ((0, 0),) * (v.ndim - 1) + ((0, dk - dv),))


def mla_decode(params, x, cache, cfg: ModelConfig, *, window: int = 0):
    """Absorbed-weight MLA decode: attention runs in the compressed
    kv_lora space — the cache is (B, S, d_c + d_r), not per-head."""
    b, s1, _ = x.shape
    assert s1 == 1
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    dc = cfg.kv_lora_rank
    h = cfg.num_heads
    idx = cache["idx"]                             # (B,)
    pos = idx[:, None]                             # (B, 1)

    q_lat = L.rmsnorm(jnp.einsum("bsd,dr->bsr", x, params["wq_a"]),
                      params["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhe->bshe", q_lat, params["wq_b"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, pos, cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    ckv_new = L.rmsnorm(kv_a[..., :dc], params["kv_norm"], cfg.norm_eps)
    krope_new = L.apply_rope(kv_a[:, :, None, dc:], pos, cfg.rope_theta)[:, :, 0]

    rows = jnp.arange(b)
    ckv = cache["ckv"].at[rows, idx].set(
        ckv_new[:, 0].astype(cache["ckv"].dtype))
    krope = cache["krope"].at[rows, idx].set(
        krope_new[:, 0].astype(cache["krope"].dtype))

    # absorb W_uk into q: q_c (B,1,H,dc)
    w_k = params["wkv_b"][..., :dn]                      # (dc, H, dn)
    q_c = jnp.einsum("bshe,rhe->bshr", q_nope, w_k)      # (B,1,H,dc)

    s_max = ckv.shape[1]
    kv_pos = jnp.arange(s_max)
    valid = kv_pos[None, :] <= idx[:, None]        # (B, S)
    if window > 0:
        valid &= kv_pos[None, :] > (idx - window)[:, None]
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)

    scores = (jnp.einsum("bshr,bkr->bhsk", q_c.astype(ckv.dtype), ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshe,bke->bhsk", q_rope.astype(krope.dtype),
                           krope, preferred_element_type=jnp.float32))
    scores = scores / np.sqrt(dn + dr) + bias[:, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_c = jnp.einsum("bhsk,bkr->bshr", probs.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32)
    w_v = params["wkv_b"][..., dn:]                      # (dc, H, dv)
    out = jnp.einsum("bshr,rhe->bshe", ctx_c, w_v).astype(x.dtype)
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return y, {"ckv": ckv, "krope": krope, "idx": idx + 1}


# ----------------------------------------------------------------------
# cross-attention (whisper decoder)
# ----------------------------------------------------------------------

def init_cross(key, cfg: ModelConfig, dtype) -> Dict[str, jnp.ndarray]:
    dh = cfg.resolved_head_dim()
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(k1, cfg.d_model, (cfg.num_heads, dh), dtype),
        "wk": L.dense_init(k2, cfg.d_model, (cfg.num_heads, dh), dtype),
        "wv": L.dense_init(k3, cfg.d_model, (cfg.num_heads, dh), dtype),
        "wo": (jax.random.normal(k4, (cfg.num_heads, dh, cfg.d_model),
                                 dtype=jnp.float32)
               / np.sqrt(cfg.num_heads * dh)).astype(dtype),
    }


def cross_attention(params, x, enc_out, cfg: ModelConfig) -> jnp.ndarray:
    """x: (B, Sq, d) decoder states; enc_out: (B, Sk, d)."""
    b, sq, _ = x.shape
    dh = cfg.resolved_head_dim()
    h = cfg.num_heads
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dhe->bshe", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", enc_out, params["wv"])
    qg = q[:, :, :, None, :]
    sk = enc_out.shape[1]
    bias = jnp.zeros((1, sq, sk), dtype=jnp.float32)
    out = _sdpa(qg, k, v, bias, 1.0 / np.sqrt(dh))
    out = out.reshape(b, sq, h, dh).astype(x.dtype)
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])
