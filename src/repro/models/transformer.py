"""Block registry + scanned layer stacks.

A model is a sequence of STAGES; each stage is `n` repeats of one block
KIND with params stacked on a leading layer axis and iterated with
`jax.lax.scan` (small HLO, fast compiles at 40-72 layers).  Heterogeneous
architectures (DeepSeek-V3's 3 dense + 58 MoE layers, Jamba's 8-layer
Mamba/attention periods) are expressed as multiple stages / composite
period blocks rather than per-layer `switch`es.

Block kinds:
  dense      GQA attention + SwiGLU
  moe        GQA attention + expert-parallel MoE (DES routing available)
  mla_dense  MLA attention + SwiGLU            (DeepSeek-V3 first layers)
  mla_moe    MLA attention + MoE + shared exp. (DeepSeek-V3)
  rwkv       RWKV6 time mix + channel mix
  jamba      8-sublayer period: Mamba x7 + attention x1, MoE every 2nd
  enc        bidirectional attention + SwiGLU  (whisper encoder)
  xdec       causal self-attn + cross-attn + SwiGLU (whisper decoder)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shard_lib
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

def jamba_period(cfg) -> int:
    """Sublayers per Jamba period (= attention interval; paper: 8)."""
    return cfg.ssm.attn_every or 8


def jamba_attn_pos(cfg) -> int:
    return jamba_period(cfg) // 2


def jamba_sublayers(cfg) -> List[Tuple[str, bool]]:
    """(mixer kind, MoE FFN?) of each sublayer of a Jamba period:
    attention at `jamba_attn_pos`, Mamba elsewhere; MoE where the index
    is 1 modulo `moe.every` (odd sublayers at every=2), a dense SwiGLU
    elsewhere.  The engine's stack and the protocol simulator both walk
    this plan."""
    return [("attention" if i == jamba_attn_pos(cfg) else "mamba",
             bool(cfg.moe.num_experts) and i % cfg.moe.every == 1)
            for i in range(jamba_period(cfg))]


# ----------------------------------------------------------------------
# per-kind init
# ----------------------------------------------------------------------

def _attn_ffn_init(key, cfg, dtype, ffn_init):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": L.rmsnorm_init(cfg.d_model, dtype),
        "attn": A.init_gqa(k1, cfg, dtype),
        "norm2": L.rmsnorm_init(cfg.d_model, dtype),
        "ffn": ffn_init(k2),
    }


def init_block(kind: str, key, cfg: ModelConfig, dtype):
    if kind == "dense" or kind == "enc":
        return _attn_ffn_init(
            key, cfg, dtype, lambda k: L.swiglu_init(k, cfg.d_model, cfg.d_ff, dtype))
    if kind == "moe":
        return _attn_ffn_init(key, cfg, dtype, lambda k: M.init_moe(k, cfg, dtype))
    if kind in ("mla_dense", "mla_moe"):
        k1, k2 = jax.random.split(key)
        ffn = (M.init_moe(k2, cfg, dtype) if kind == "mla_moe"
               else L.swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype))
        return {
            "norm1": L.rmsnorm_init(cfg.d_model, dtype),
            "attn": A.init_mla(k1, cfg, dtype),
            "norm2": L.rmsnorm_init(cfg.d_model, dtype),
            "ffn": ffn,
        }
    if kind == "rwkv":
        k1, k2 = jax.random.split(key)
        return {
            "norm1": L.rmsnorm_init(cfg.d_model, dtype),
            "att": S.init_rwkv6(k1, cfg, dtype),
            "norm2": L.rmsnorm_init(cfg.d_model, dtype),
            "ffn": S.init_rwkv6_channel_mix(k2, cfg, dtype),
        }
    if kind == "jamba":
        subs = {}
        period = jamba_period(cfg)
        keys = jax.random.split(key, period * 2)
        for i, (kind_i, use_moe) in enumerate(jamba_sublayers(cfg)):
            km, kf = keys[2 * i], keys[2 * i + 1]
            mixer = (A.init_gqa(km, cfg, dtype) if kind_i == "attention"
                     else S.init_mamba(km, cfg, dtype))
            ffn = (M.init_moe(kf, cfg, dtype) if use_moe
                   else L.swiglu_init(kf, cfg.d_model, cfg.d_ff, dtype))
            subs[f"sub{i}"] = {
                "norm1": L.rmsnorm_init(cfg.d_model, dtype),
                "mixer": mixer,
                "norm2": L.rmsnorm_init(cfg.d_model, dtype),
                "ffn": ffn,
            }
        return subs
    if kind == "xdec":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "norm1": L.rmsnorm_init(cfg.d_model, dtype),
            "attn": A.init_gqa(k1, cfg, dtype),
            "norm_x": L.rmsnorm_init(cfg.d_model, dtype),
            "cross": A.init_cross(k2, cfg, dtype),
            "norm2": L.rmsnorm_init(cfg.d_model, dtype),
            "ffn": L.swiglu_init(k3, cfg.d_model, cfg.d_ff, dtype),
        }
    raise ValueError(f"unknown block kind {kind!r}")


# ----------------------------------------------------------------------
# per-kind caches
# ----------------------------------------------------------------------

def init_block_cache(kind: str, batch: int, max_len: int, cfg: ModelConfig,
                     dtype):
    dh = cfg.resolved_head_dim()
    if kind in ("dense", "moe", "enc"):
        return A.init_kv_cache(batch, max_len, cfg.num_kv_heads, dh, dtype)
    if kind in ("mla_dense", "mla_moe"):
        return A.init_mla_cache(batch, max_len, cfg.kv_lora_rank,
                                cfg.rope_head_dim, dtype)
    if kind == "rwkv":
        return S.init_rwkv6_state(batch, cfg, dtype)
    if kind == "jamba":
        cache = {}
        for i, (kind_i, _) in enumerate(jamba_sublayers(cfg)):
            if kind_i == "attention":
                cache[f"sub{i}"] = A.init_kv_cache(
                    batch, max_len, cfg.num_kv_heads, dh, dtype)
            else:
                cache[f"sub{i}"] = S.init_mamba_state(batch, cfg, dtype)
        return cache
    if kind == "xdec":
        return A.init_kv_cache(batch, max_len, cfg.num_kv_heads, dh, dtype)
    raise ValueError(f"unknown block kind {kind!r}")


# ----------------------------------------------------------------------
# per-kind forward
# ----------------------------------------------------------------------

def _zero_aux():
    z = jnp.zeros((), jnp.float32)
    return {"load_balance_loss": z, "router_z_loss": z,
            "experts_per_token": z, "selected_gate_mass": z,
            "dropped_frac": z, "dropped_tokens": z}


def _ffn_apply(ffn_params, h, cfg, layer_idx, is_moe, expert_costs):
    if is_moe:
        return M.moe_ffn(ffn_params, h, cfg, layer_idx, expert_costs)
    return L.swiglu(ffn_params, h), _zero_aux()


def block_forward(
    kind: str,
    params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    layer_idx,
    *,
    mode: str,                      # "full" (train/prefill) | "decode"
    cache=None,
    enc_out: Optional[jnp.ndarray] = None,
    window: int = 0,
    expert_costs: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Any, Dict]:
    """Returns (x, new_cache, aux)."""
    eps = cfg.norm_eps

    if kind in ("dense", "moe", "enc"):
        h = L.rmsnorm(x, params["norm1"], eps)
        causal = kind != "enc"
        if mode == "full":
            a, cache = A.gqa_prefill(params["attn"], h, cfg, causal=causal,
                                     window=window, cache=cache)
        else:
            a, cache = A.gqa_decode(params["attn"], h, cache, cfg,
                                    window=window)
        x = x + a
        h = L.rmsnorm(x, params["norm2"], eps)
        y, aux = _ffn_apply(params["ffn"], h, cfg, layer_idx,
                            kind == "moe", expert_costs)
        return x + y, cache, aux

    if kind in ("mla_dense", "mla_moe"):
        h = L.rmsnorm(x, params["norm1"], eps)
        if mode == "full":
            a, cache = A.mla_prefill(params["attn"], h, cfg, window=window,
                                     cache=cache)
        else:
            a, cache = A.mla_decode(params["attn"], h, cache, cfg,
                                    window=window)
        x = x + a
        h = L.rmsnorm(x, params["norm2"], eps)
        y, aux = _ffn_apply(params["ffn"], h, cfg, layer_idx,
                            kind == "mla_moe", expert_costs)
        return x + y, cache, aux

    if kind == "rwkv":
        h = L.rmsnorm(x, params["norm1"], eps)
        if mode == "full":
            a, state, x_last = S.rwkv6_mix(params["att"], h, cfg)
            new_cache = None
            if cache is not None:
                new_cache = {"state": state, "x_prev": x_last,
                             "x_prev_ffn": cache["x_prev_ffn"],
                             "idx": jnp.asarray(h.shape[1], jnp.int32)}
        else:
            a, sub = S.rwkv6_decode(
                params["att"], h,
                {"state": cache["state"], "x_prev": cache["x_prev"],
                 "idx": cache["idx"]}, cfg)
            new_cache = {**sub, "x_prev_ffn": cache["x_prev_ffn"]}
        x = x + a
        h = L.rmsnorm(x, params["norm2"], eps)
        prev_ffn = None if cache is None else (
            cache["x_prev_ffn"] if mode == "decode" else None)
        y, x_last_ffn = S.rwkv6_channel_mix(params["ffn"], h,
                                            x_prev_last=prev_ffn)
        if new_cache is not None:
            new_cache["x_prev_ffn"] = x_last_ffn
        return x + y, new_cache, _zero_aux()

    if kind == "jamba":
        new_cache = {} if cache is not None else None
        aux_acc = _zero_aux()
        n_moe = 0
        period = jamba_period(cfg)
        for i, (kind_i, use_moe) in enumerate(jamba_sublayers(cfg)):
            sub = params[f"sub{i}"]
            sub_cache = None if cache is None else cache[f"sub{i}"]
            li = layer_idx * period + i
            h = L.rmsnorm(x, sub["norm1"], eps)
            if kind_i == "attention":
                if mode == "full":
                    a, sub_cache = A.gqa_prefill(sub["mixer"], h, cfg,
                                                 causal=True, window=window,
                                                 cache=sub_cache)
                else:
                    a, sub_cache = A.gqa_decode(sub["mixer"], h, sub_cache,
                                                cfg, window=window)
            else:
                if mode == "full":
                    a, final = S.mamba_mix(sub["mixer"], h, cfg)
                    if sub_cache is not None:
                        sub_cache = {**final,
                                     "idx": jnp.asarray(h.shape[1], jnp.int32)}
                else:
                    a, sub_cache = S.mamba_decode(sub["mixer"], h, sub_cache,
                                                  cfg)
            x = x + a
            h = L.rmsnorm(x, sub["norm2"], eps)
            y, aux = _ffn_apply(sub["ffn"], h, cfg, li, use_moe, expert_costs)
            if use_moe:
                n_moe += 1
                aux_acc = jax.tree.map(lambda a_, b_: a_ + b_, aux_acc, aux)
            x = x + y
            if new_cache is not None:
                new_cache[f"sub{i}"] = sub_cache
        if n_moe:
            aux_acc = jax.tree.map(lambda a_: a_ / n_moe, aux_acc)
        return x, new_cache, aux_acc

    if kind == "xdec":
        h = L.rmsnorm(x, params["norm1"], eps)
        if mode == "full":
            a, cache = A.gqa_prefill(params["attn"], h, cfg, causal=True,
                                     window=window, cache=cache)
        else:
            a, cache = A.gqa_decode(params["attn"], h, cache, cfg,
                                    window=window)
        x = x + a
        h = L.rmsnorm(x, params["norm_x"], eps)
        x = x + A.cross_attention(params["cross"], h, enc_out, cfg)
        h = L.rmsnorm(x, params["norm2"], eps)
        return x + L.swiglu(params["ffn"], h), cache, _zero_aux()

    raise ValueError(f"unknown block kind {kind!r}")


# ----------------------------------------------------------------------
# stages
# ----------------------------------------------------------------------

def stage_plan(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """[(block_kind, n_layers_in_stage), ...] for the decoder stack."""
    if cfg.arch_type in ("dense", "vlm"):
        return [("dense", cfg.num_layers)]
    if cfg.arch_type == "moe":
        if cfg.mla:
            plan = []
            if cfg.moe.first_dense_layers:
                plan.append(("mla_dense", cfg.moe.first_dense_layers))
            plan.append(("mla_moe", cfg.num_layers - cfg.moe.first_dense_layers))
            return plan
        plan = []
        if cfg.moe.first_dense_layers:
            plan.append(("dense", cfg.moe.first_dense_layers))
        plan.append(("moe", cfg.num_layers - cfg.moe.first_dense_layers))
        return plan
    if cfg.arch_type == "ssm":
        return [("rwkv", cfg.num_layers)]
    if cfg.arch_type == "hybrid":
        period = jamba_period(cfg)
        assert cfg.num_layers % period == 0
        return [("jamba", cfg.num_layers // period)]
    if cfg.arch_type == "audio":
        return [("xdec", cfg.num_layers)]
    raise ValueError(cfg.arch_type)


def init_stack(kind: str, n: int, key, cfg: ModelConfig, dtype):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_block(kind, k, cfg, dtype))(keys)


def init_stack_cache(kind: str, n: int, batch: int, max_len: int,
                     cfg: ModelConfig, dtype):
    one = init_block_cache(kind, batch, max_len, cfg, dtype)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)), one)


def run_stack(
    kind: str,
    n: int,
    stack_params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    mode: str,
    cache=None,
    enc_out=None,
    window: int = 0,
    layer_offset: int = 0,
    expert_costs=None,
    remat: bool = False,
):
    """Scan `n` blocks over x. Returns (x, new_cache_stack, mean_aux)."""
    idxs = layer_offset + jnp.arange(n)

    def body(carry, per_layer):
        xx = shard_lib.constrain_btd(carry)
        p, c, li = per_layer
        fwd = functools.partial(
            block_forward, kind, mode=mode, enc_out=enc_out, window=window,
            expert_costs=expert_costs)
        if remat:
            fwd = jax.checkpoint(
                lambda pp, xv, cc, lv: block_forward(
                    kind, pp, xv, cfg, lv, mode=mode, enc_out=enc_out,
                    window=window, expert_costs=expert_costs),
                prevent_cse=False)
            y, new_c, aux = fwd(p, xx, c, li)
        else:
            y, new_c, aux = fwd(p, xx, cfg, li, cache=c)
        return y, (new_c, aux)

    xs = (stack_params, cache, idxs)
    x, (new_cache, auxs) = jax.lax.scan(body, x, xs)
    aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), auxs)
    return x, new_cache, aux
