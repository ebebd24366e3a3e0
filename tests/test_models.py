"""Model correctness: chunked==naive attention, prefill+decode == full
forward for every cached family, MLA absorbed decode, MoE invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
from repro.models import Model, attention as A
from repro.models.model import forward


def test_chunked_equals_naive_attention():
    key = jax.random.PRNGKey(0)
    b, s, hkv, r, dh = 2, 37, 2, 3, 8
    q = jax.random.normal(key, (b, s, hkv, r, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, dh))
    pos = jnp.arange(s)
    for causal in (True, False):
        for win in (0, 8):
            out_c = A.chunked_attention(q, k, v, q_pos=pos, kv_pos=pos,
                                        causal=causal, window=win,
                                        q_chunk=16, kv_chunk=8)
            bias = A._mask_bias(pos, pos, causal=causal, window=win)[None]
            out_n = A._sdpa(q, k, v, bias, 1.0 / np.sqrt(dh))
            np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_n),
                                       atol=1e-5, rtol=1e-5)


def test_chunked_attention_grad_finite():
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 20, 2, 1, 8))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 20, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(5), (1, 20, 2, 8))
    pos = jnp.arange(20)

    def f(q_):
        return jnp.sum(A.chunked_attention(q_, k, v, q_pos=pos, kv_pos=pos,
                                           causal=True, window=0,
                                           q_chunk=8, kv_chunk=8))

    g = jax.grad(f)(q)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


def _consistency(cfg, atol=5e-4):
    m = Model(cfg)
    key = jax.random.PRNGKey(1)
    params = m.init(key)
    B, S = 2, 12
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    logits_full, _, _ = forward(params, {"tokens": toks}, cfg, mode="full")
    caches = m.init_caches(B, 32)
    lg_pf, caches = m.prefill(params, {"tokens": toks[:, : S - 1]}, caches)
    np.testing.assert_allclose(np.asarray(lg_pf),
                               np.asarray(logits_full[:, S - 2]),
                               atol=atol, rtol=1e-2)
    lg_dec, caches = m.decode_step(params, toks[:, S - 1], caches)
    np.testing.assert_allclose(np.asarray(lg_dec),
                               np.asarray(logits_full[:, S - 1]),
                               atol=atol, rtol=1e-2)


def test_decode_consistency_dense():
    _consistency(ModelConfig(
        arch_type="dense", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=128, dtype="float32",
        param_dtype="float32"))


def test_decode_consistency_mla_moe():
    _consistency(ModelConfig(
        arch_type="moe", num_layers=3, d_model=64, num_heads=4,
        num_kv_heads=4, vocab_size=128, dtype="float32",
        param_dtype="float32", mla=True, q_lora_rank=32, kv_lora_rank=16,
        rope_head_dim=8, nope_head_dim=16, v_head_dim=16,
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                      num_shared_experts=1, first_dense_layers=1,
                      capacity_factor=8.0)))


def test_decode_consistency_rwkv():
    _consistency(ModelConfig(
        arch_type="ssm", num_layers=2, d_model=64, vocab_size=128,
        d_ff=128, dtype="float32", param_dtype="float32",
        ssm=SSMConfig(kind="rwkv6", head_dim=16)))


def test_decode_consistency_jamba():
    _consistency(ModelConfig(
        arch_type="hybrid", num_layers=8, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=128, dtype="float32",
        param_dtype="float32",
        ssm=SSMConfig(kind="mamba", d_state=8, attn_every=8),
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64, every=2,
                      capacity_factor=8.0)), atol=1e-3)


def test_decode_consistency_jamba_no_rope_inner_norms():
    """Jamba as published: no rotary positions, RMSNorms on Mamba's dt,
    B and C; the cached decode applies both as the full pass does."""
    _consistency(ModelConfig(
        arch_type="hybrid", num_layers=8, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=128, dtype="float32",
        param_dtype="float32", rope=False, norm_eps=1e-6,
        ssm=SSMConfig(kind="mamba", d_state=8, attn_every=8,
                      inner_norms=True),
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64, every=2,
                      capacity_factor=8.0)), atol=1e-3)


def test_sliding_window_consistency():
    cfg = ModelConfig(arch_type="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                      dtype="float32", param_dtype="float32")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(2))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, 128)
    lf, _, _ = forward(params, {"tokens": toks}, cfg, mode="full", window=4)
    caches = m.init_caches(2, 32)
    lp, caches = m.prefill(params, {"tokens": toks[:, :11]}, caches,
                           window=4)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lf[:, 10]),
                               atol=2e-4, rtol=1e-3)
    ld, _ = m.decode_step(params, toks[:, 11], caches, window=4)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lf[:, 11]),
                               atol=2e-4, rtol=1e-3)


def test_moe_qos_constraint_satisfied():
    """With DES routing and a generous capacity, selected gate mass must
    meet z*gamma0^l at every layer (C1) and <= D experts (C2)."""
    cfg = ModelConfig(
        arch_type="moe", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, d_ff=128, vocab_size=128, dtype="float32",
        param_dtype="float32",
        moe=MoEConfig(num_experts=8, top_k=4, d_ff_expert=64, routing="des",
                      qos_z=1.0, qos_gamma0=0.5, max_experts=4,
                      capacity_factor=8.0))
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
    _, _, aux = forward(params, {"tokens": toks}, cfg, mode="full")
    a = aux["stage0"]
    assert float(a["experts_per_token"]) <= 4.0 + 1e-6
    # layer-mean QoS: gamma0=0.5 -> thresholds 0.5, 0.25 -> mean 0.375
    assert float(a["selected_gate_mass"]) >= 0.3


def test_moe_capacity_drops_reported():
    cfg = ModelConfig(
        arch_type="moe", num_layers=1, d_model=32, num_heads=2,
        num_kv_heads=2, d_ff=64, vocab_size=64, dtype="float32",
        param_dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                      capacity_factor=0.25))
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    _, _, aux = forward(params, {"tokens": toks}, cfg, mode="full")
    assert float(aux["stage0"]["dropped_frac"]) > 0.0


def test_mtp_loss_finite_and_contributes():
    """DeepSeek-style MTP: loss includes the t+2 head; grads reach it."""
    from repro.models.model import loss_fn, init_params

    cfg = ModelConfig(
        arch_type="moe", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, vocab_size=128, dtype="float32",
        param_dtype="float32", mla=True, q_lora_rank=32, kv_lora_rank=16,
        rope_head_dim=8, nope_head_dim=16, v_head_dim=16, mtp=True,
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                      first_dense_layers=1, capacity_factor=8.0))
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert "mtp" in params
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
    batch = {"tokens": toks, "labels": toks}

    def f(p):
        return loss_fn(p, batch, cfg, remat=False)

    (loss, metrics), grads = jax.value_and_grad(f, has_aux=True)(params)
    assert "mtp_ce" in metrics and jnp.isfinite(metrics["mtp_ce"])
    g_mtp = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads["mtp"])))
    assert float(g_mtp) > 0.0
    # without MTP, loss is strictly smaller (positive-weighted CE added)
    cfg2 = cfg.with_overrides(mtp=False)
    params2 = {k: v for k, v in params.items() if k != "mtp"}
    loss2, _ = loss_fn(params2, batch, cfg2, remat=False)
    assert float(loss) > float(loss2) - 1e-6
