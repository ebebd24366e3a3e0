"""Expert-parallel Mixture-of-Experts layer with DES routing (paper §III-C).

Dispatch follows the GShard dense-einsum pattern: tokens are grouped along
the sequence axis (``cfg.dispatch_group``), each group computes a
(token -> expert, capacity-slot) one-hot dispatch tensor, and expert FFNs
run as batched einsums with the expert axis sharded on the ``model`` mesh
axis — XLA SPMD lowers the dispatch/combine einsums to all-to-alls.

Routing modes (cfg.moe.routing):
  "topk" — centralized-MoE baseline (paper's comparison scheme);
  "des"  — the paper's technique: greedy QoS-covering selection that
           weighs gate score against a per-expert cost vector (in-situ
           experts cheap, cross-shard experts expensive) with layer-wise
           QoS z * gamma0^l  (C1) and max-expert budget D (C2);
  "dense"— all experts (debug upper bound).

Aux outputs: load-balance loss (Switch-style), router z-loss, and the
fraction of tokens dropped by capacity (all returned for logging; summed
into the train loss with cfg.moe.* weights).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import selection as sel_lib
from repro.kernels import moe_route as mr
from repro.kernels import ops as kops
from repro.models import layers as L


def init_moe(key, cfg: ModelConfig, dtype) -> Dict[str, jnp.ndarray]:
    e = cfg.moe.num_experts
    d = cfg.d_model
    f = cfg.moe.d_ff_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    params = {
        "w_gate_router": L.dense_init(ks[0], d, e, jnp.float32),
        "w1": (jax.random.normal(ks[1], (e, d, f), dtype=jnp.float32)
               / np.sqrt(d)).astype(dtype),
        "wu": (jax.random.normal(ks[2], (e, d, f), dtype=jnp.float32)
                 / np.sqrt(d)).astype(dtype),
        "w2": (jax.random.normal(ks[3], (e, f, d), dtype=jnp.float32)
               / np.sqrt(f)).astype(dtype),
    }
    if cfg.moe.num_shared_experts > 0:
        fs = f * cfg.moe.num_shared_experts
        params["shared"] = L.swiglu_init(ks[4], d, fs, dtype)
    return params


def _router(params, x, cfg: ModelConfig, layer_idx, expert_costs):
    """Returns (combine (B,S,E), mask (B,S,E), aux dict)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["w_gate_router"])
    m = cfg.moe
    qos = m.qos_z * (m.qos_gamma0 ** (layer_idx + 1))
    combine, mask = sel_lib.route(
        logits,
        routing=m.routing,
        top_k=m.top_k,
        qos=qos,
        costs=expert_costs,
        max_experts=m.max_experts or m.top_k,
        routing_kwargs=dict(m.routing_kwargs),
    )
    gates = jax.nn.softmax(logits, axis=-1)
    # Switch-style load balance: E * sum_e (frac_tokens_e * mean_gate_e)
    e = gates.shape[-1]
    frac = jnp.mean(mask, axis=(0, 1))
    mean_gate = jnp.mean(gates, axis=(0, 1))
    lb_loss = e * jnp.sum(frac * mean_gate) / jnp.maximum(
        jnp.mean(jnp.sum(mask, -1)), 1e-9)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {"load_balance_loss": lb_loss, "router_z_loss": z_loss,
           "experts_per_token": jnp.mean(jnp.sum(mask, -1)),
           "selected_gate_mass": jnp.mean(jnp.sum(gates * mask, -1))}
    return combine, mask, aux


def _dispatch_ffn_xla(params, xg, mk, cw, cap, act_dtype):
    """Historical dispatch path: one-hot dispatch/combine einsums (XLA
    SPMD lowers them to all-to-alls).  `routing_impl="xla"` — the
    default; every op below is byte-for-byte the pre-knob hot path."""
    # position of each token within its expert's capacity buffer
    pos = jnp.cumsum(mk, axis=1) * mk - 1.0              # (G, gsz, E)
    keep = (pos >= 0) & (pos < cap)
    mk_kept = mk * keep
    cw = cw * keep
    aux = {"dropped_frac": 1.0 - (jnp.sum(mk_kept) /
                                  jnp.maximum(jnp.sum(mk), 1.0)),
           "dropped_tokens": jnp.sum(mk) - jnp.sum(mk_kept)}
    pos = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    # one-hot over capacity slots — cast to the ACTIVATION dtype after the
    # f32 mask multiply: an f32 `slot` upcasts xe and then forces f32
    # copies of every expert weight in the FFN einsums (10 GB/device on
    # deepseek-v3; EXPERIMENTS.md §Perf B).
    slot = (jax.nn.one_hot(pos, cap, dtype=jnp.float32)
            * mk_kept[..., None]).astype(act_dtype)
    # dispatch: (G, gsz, E, cap) x (G, gsz, d) -> (E, G, cap, d)
    xe = jnp.einsum("gsec,gsd->egcd", slot, xg)

    # --- expert FFN (E sharded on model axis) -------------------------
    h = jnp.einsum("egcd,edf->egcf", xe, params["w1"])
    u = jnp.einsum("egcd,edf->egcf", xe, params["wu"])
    h = jax.nn.silu(h.astype(jnp.float32)).astype(act_dtype) * u
    ye = jnp.einsum("egcf,efd->egcd", h, params["w2"])

    # --- combine back (combine tensor in activation dtype: the fp32
    # variant doubled the cross-shard bytes of the combine einsum) ------
    comb_t = (jax.nn.one_hot(pos, cap, dtype=jnp.float32)
              * cw[..., None]).astype(act_dtype)
    yg = jnp.einsum("egcd,gsec->gsd", ye, comb_t)
    return yg, aux


def _drop_aux(mk, keep):
    """Capacity-overflow accounting shared by the Pallas impls: ``keep``
    already folds the mask, so kept mass is just its sum."""
    return {"dropped_frac": 1.0 - (jnp.sum(keep) /
                                   jnp.maximum(jnp.sum(mk), 1.0)),
            "dropped_tokens": jnp.sum(mk) - jnp.sum(keep)}


def _combine_weights(cw, keep, act_dtype):
    """Kept combine weights, rounded to the activation dtype as the XLA
    path's combine tensor is (the kernels widen them back exactly)."""
    return (cw * keep).astype(act_dtype)


def _dispatch_ffn_fused(params, xg, mk, cw, cap, act_dtype):
    """`routing_impl="fused"`: Pallas gather-dispatch straight into the
    (E, G, cap, d) capacity layout + fused SwiGLU FFN + weighted combine
    — the (G, gsz, E, cap) one-hot tensor is never materialized."""
    g, gsz, d = xg.shape
    e = mk.shape[-1]
    pos, keep = mr.capacity_positions(mk, cap)
    aux = _drop_aux(mk, keep)
    cwk = _combine_weights(cw, keep, act_dtype)
    xe = mr.capacity_dispatch(xg, pos, keep, cap)        # (E, G, cap, d)
    ye = kops.moe_expert_ffn(xe.reshape(e, g * cap, d), params["w1"],
                             params["wu"], params["w2"])
    yg = mr.capacity_combine(ye.reshape(e, g, cap, d), cwk, pos, keep,
                             out_dtype=act_dtype)
    return yg, aux


def _dispatch_ffn_grouped(params, xg, mk, cw, cap, act_dtype):
    """`routing_impl="grouped"`: ragged layout (tokens sorted by expert
    id at block-aligned per-expert offsets) + the scalar-prefetch ragged
    FFN, which skips segment-padding blocks entirely — the win over the
    dense capacity grid when token→expert loads are skewed."""
    pos, keep = mr.capacity_positions(mk, cap)
    aux = _drop_aux(mk, keep)
    cwk = _combine_weights(cw, keep, act_dtype)
    layout = mr.grouped_layout(pos, keep, cap)
    xs = mr.grouped_dispatch(xg, layout)                 # (total, d)
    ys = mr.moe_expert_ffn_ragged(xs, layout, params["w1"],
                                  params["wu"], params["w2"])
    yg = mr.grouped_scatter(ys, layout, cwk, pos, keep,
                            out_dtype=act_dtype)
    return yg, aux


_DISPATCH_IMPLS = {"xla": _dispatch_ffn_xla, "fused": _dispatch_ffn_fused,
                   "grouped": _dispatch_ffn_grouped}


def moe_ffn(params, x, cfg: ModelConfig, layer_idx,
            expert_costs: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """MoE FFN. x: (B, S, d) -> (B, S, d), aux losses.

    layer_idx may be a traced int32 (inside lax.scan over layers) — the
    QoS schedule gamma0**(l+1) stays traceable.  The token-dispatch
    implementation is selected by `cfg.moe.routing_impl` ("xla" one-hot
    einsums by default; "fused"/"grouped" take the Pallas kernel family
    in `repro.kernels.moe_route`).
    """
    b, s, d = x.shape
    m = cfg.moe
    e = m.num_experts
    combine, mask, aux = _router(params, x, cfg, layer_idx, expert_costs)

    # --- group tokens for dispatch ------------------------------------
    # tokens are flattened over (B, S): for training (S >> group) groups
    # stay within a sequence exactly as before; for DECODE (S=1) this
    # puts the whole token batch in one group — with per-token groups the
    # dense dispatch tensor is (E, B, 1, d), a tokens-x-experts cross
    # product that cost 54 GB/step of all-gather on deepseek-v3
    # decode_32k (EXPERIMENTS.md §Perf B).
    tot = b * s
    gsz = min(cfg.dispatch_group, tot)
    while tot % gsz != 0:     # static: tot, gsz are python ints
        gsz -= 1
    g = tot // gsz
    cap = int(np.ceil(gsz * max(m.top_k, m.max_experts or 0)
                      * m.capacity_factor / e))
    cap = max(cap, 1)

    xg = x.reshape(g, gsz, d)
    mk = mask.reshape(g, gsz, e)
    cw = combine.reshape(g, gsz, e)

    impl = mr.check_routing_impl(getattr(m, "routing_impl", "xla"))
    yg, drop_aux = _DISPATCH_IMPLS[impl](params, xg, mk, cw, cap, x.dtype)
    aux.update(drop_aux)
    y = yg.reshape(b, s, d).astype(x.dtype)

    if m.num_shared_experts > 0:
        y = y + L.swiglu(params["shared"], x)
    return y, aux
