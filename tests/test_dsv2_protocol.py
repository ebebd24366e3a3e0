"""DeepSeek-V2 on the DMoE protocol path (`DMoESimulator` over node
groups: MLA with YaRN, a leading dense layer, shared experts, the
published top-k within the selected nodes) against the plain reference
`bench/reference/deepseek_ref.py`, at the config's `smoke()` size in
float32: 1 dense + 2 MoE layers, 16 experts in 4 groups = 4 edge nodes,
top-3 within D = 2 nodes."""

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, get_smoke_config
from repro.models import attention as A
from repro.models import layers as L
from repro.serving import DMoESimulator

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from reference import deepseek_ref  # noqa: E402

#: Both sides compute in float32 and differ only in the order of their
#: sums (the program's fused einsums against the reference's products
#: at HIGHEST precision, expert block by expert block), about 1e-6
#: relative; the program in bfloat16 moves them by 1e-3 and more.
LOGIT_TOL = 1e-4
GATE_TOL = 1e-5


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("deepseek-v2")


def _dims(cfg, held_nodes=None):
    m = cfg.moe
    nodes = list(range(m.n_group)) if held_nodes is None else held_nodes
    group = m.num_experts // m.n_group
    return deepseek_ref.Dims.from_config({
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.nope_head_dim,
        "qk_rope_head_dim": cfg.rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "factor": cfg.yarn_factor,
            "original_max_position_embeddings": cfg.yarn_original_max_pos,
            "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
            "mscale": cfg.yarn_mscale,
            "mscale_all_dim": cfg.yarn_mscale_all_dim},
        "first_k_dense_replace": m.first_dense_layers,
        "num_hidden_layers": cfg.num_layers, "n_group": m.n_group,
        "reduced": {"n_routed_experts": {"published": m.num_experts}},
        "held_nodes": nodes, "n_routed_experts": len(nodes) * group,
        "num_experts_per_tok": m.top_k,
        "routed_scaling_factor": m.routed_scaling_factor,
        "norm_topk_prob": m.norm_topk_prob})


def _serve(cfg, tokens, held_nodes=None, seed=3):
    sim = DMoESimulator(cfg, scheme="jesa", seed=seed, held_nodes=held_nodes)
    res = sim.serve(tokens)
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), sim.params)
    want, gates = deepseek_ref.forward(
        f32, tokens, [rs.alpha for rs in res.schedules], res.expert_scores,
        _dims(cfg, held_nodes))
    return sim, res, np.asarray(want), [np.asarray(g) for g in gates]


@pytest.fixture(scope="module")
def served(cfg):
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16))
    return (tokens,) + _serve(cfg, tokens)


def test_yarn_frequencies_and_scale():
    """DeepSeek-V2's YaRN: freq / 40 below correction dim 10, the freq
    above 23, a linear ramp between (beta 32 and 1 at 4,096 original
    positions, rope dim 64); cos and sin x 1; softmax scale 192^-0.5 x
    mscale(40, 0.707)^2 = 1.5897 / sqrt(192).  DeepSeek-V3's, with
    mscale_all_dim 1: x (1 + 0.1 ln 40)^2."""
    cfg = get_config("deepseek-v2")
    yarn = (cfg.yarn_factor, cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
            cfg.yarn_beta_slow)
    got = np.asarray(L.rope_frequencies(64, 1e4, yarn), np.float64)
    base = 1e4 ** -(np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / (23 - 10), 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got, deepseek_ref.yarn_frequencies(
        _dims(cfg)), rtol=1e-6)
    assert np.allclose(got[:10], base[:10]) and np.allclose(
        got[23:], base[23:] / 40)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale ** 2 == pytest.approx(1.5897, abs=1e-4)
    assert A.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert L.yarn_mscale(40, 0.707) / L.yarn_mscale(40, 0.707) == 1.0
    v3 = get_config("deepseek-v3-671b")
    assert A.mla_softmax_scale(v3) == pytest.approx(
        192 ** -0.5 * (1 + 0.1 * math.log(40)) ** 2)
    # no YaRN: plain rotary positions and 1/sqrt(qk head dim)
    plain = cfg.with_overrides(yarn_factor=1.0)
    assert A.mla_softmax_scale(plain) == pytest.approx(192 ** -0.5)
    np.testing.assert_allclose(
        np.asarray(L.rope_frequencies(64, 1e4)), base, rtol=1e-6)


def test_plan_walks_dense_then_moe(cfg, served):
    _, sim, res, _, _ = served
    assert sim.plan == [("stage0", [("mla", False)], 1),
                        ("stage1", [("mla", True)], 2)]
    assert sim.k == 4 and sim.group == 4 and sim.held == slice(0, 16)
    assert sim.rounds_per_pass == len(res.schedules) == 2
    assert sim.max_experts == 2 and sim.top_k == 2


def test_prefill_logits_match_reference(served):
    """Teacher-forced on the program's alpha and router scores."""
    _, sim, res, want, _ = served
    assert res.logits.shape == want.shape == (4, 16, sim.cfg.vocab_size)
    err = np.asarray(deepseek_ref.position_errors(res.logits, want))
    assert err.max() < LOGIT_TOL


def test_scores_match_reference_and_node_scores_sum_to_one(served):
    _, sim, res, _, gates = served
    for scores, rs, g in zip(res.expert_scores, res.schedules, gates,
                             strict=True):
        assert scores.shape == (4, 16, 16) and scores.dtype == np.float32
        assert np.abs(scores - g).max() < GATE_TOL
        node = sim.node_scores(scores)
        assert node.shape == (4, 16, 4)
        np.testing.assert_allclose(node.sum(-1), 1.0, atol=1e-6)
        np.testing.assert_array_equal(
            node, scores.astype(np.float64).reshape(4, 16, 4, 4).sum(-1))
        assert rs.alpha.sum(-1).max() <= sim.max_experts


def test_experts_from_selected_nodes_weighted_unnormalised(cfg, served):
    """Each token's top-3 experts lie in the nodes alpha selected, are
    the top-3 of its scores there, and weigh score x 16, unnormalised."""
    _, sim, res, _, _ = served
    dims = _dims(cfg)
    for scores, rs, chosen in zip(res.expert_scores, res.schedules,
                                  res.routed, strict=True):
        assert chosen.shape == (4, 16, 3)
        node = chosen // sim.group
        assert np.take_along_axis(rs.alpha, node, -1).all()
        np.testing.assert_array_equal(
            np.sort(chosen, -1),
            np.sort(deepseek_ref.top_experts(scores, rs.alpha, dims), -1))
        w, top, held = sim._routed_weights(
            jnp.asarray(rs.alpha, jnp.float32), jnp.asarray(scores))
        np.testing.assert_array_equal(np.asarray(top), chosen)
        want = np.zeros((4, 16, 16), np.float32)
        np.put_along_axis(want, chosen, 16.0 * np.take_along_axis(
            scores, chosen, -1), -1)
        np.testing.assert_allclose(np.asarray(w), want, rtol=1e-6)
        assert float(np.asarray(w).sum(-1).max()) > 1.5   # not renormalised
    assert res.routed_pairs == 2 * 4 * 16 * 3


def test_shared_and_dense_paths(cfg, served):
    """The dense layer's step is x + SwiGLU(RMSNorm(x)); the MoE layer's
    FFN step returns the held experts' outputs and the shared experts'
    SwiGLU, which the combine adds whatever alpha selects."""
    _, sim, _, _, _ = served
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 16, cfg.d_model))
    st0, st1 = (sim.params["stages"][k] for k in ("stage0", "stage1"))
    p0 = jax.tree.map(lambda a: a[0], st0)
    h = L.rmsnorm(x, p0["norm2"], cfg.norm_eps)
    np.testing.assert_allclose(
        np.asarray(sim._dense_ffn(st0, np.int32(0), x)),
        np.asarray(x + L.swiglu(p0["ffn"], h)), rtol=1e-5, atol=1e-5)
    ye, shared = sim._ffn(st1, np.int32(1), x)
    assert ye.shape == (4, 16, 16, cfg.d_model)
    sp = jax.tree.map(lambda a: a[1], st1["ffn"]["shared"])
    assert sp["w_gate"].shape == (cfg.d_model, 2 * cfg.moe.d_ff_expert)
    np.testing.assert_allclose(np.asarray(shared),
                               np.asarray(L.swiglu(sp, x)), rtol=1e-5,
                               atol=1e-6)
    none = jnp.zeros((4, 16, 4), jnp.float32)
    gates = jnp.full((4, 16, 16), 1 / 16, jnp.float32)
    out, _, pairs = sim._combine(x, (ye, shared), none, gates)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x + shared),
                               rtol=1e-6, atol=1e-6)
    assert int(pairs) == 0


def test_node_shares_add_up_to_the_uncut_layer(cfg):
    """The guide's share test: the MoE layer's output of every node share
    (2 nodes each), less what every share computes alike (the residual
    and the shared experts, counted once), adds up to the uncut layer;
    and a share's whole pass equals the reference given that share."""
    full = DMoESimulator(cfg, scheme="jesa", seed=5)
    shares = [DMoESimulator(cfg, scheme="jesa", seed=5, held_nodes=nodes)
              for nodes in ([0, 1], [2, 3])]
    st = full.params["stages"]["stage1"]
    for s, start in zip(shares, (0, 8), strict=True):
        assert s.held == slice(start, start + 8)
        np.testing.assert_array_equal(s.params["stages"]["stage1"]["ffn"]["w1"],
                                      st["ffn"]["w1"][:, start:start + 8])
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 16, cfg.d_model))
    p = np.int32(0)
    x1, h, gates = full._mixer(st, p, x, mixer="mla", gate=True)
    alpha = jnp.asarray(np.random.default_rng(9).random((4, 16, 4)) < 0.5,
                        jnp.float32).at[..., 0].set(1.0)
    whole, _, pairs = full._combine(x1, full._ffn(st, p, h), alpha, gates)
    ye, shared = full._ffn(st, p, h)
    parts, counted = x1 + shared, 0
    for s in shares:
        out, _, n = s._combine(x1, s._ffn(s.params["stages"]["stage1"], p,
                                          h), alpha, gates)
        parts = parts + (out - x1 - shared)
        counted += int(n)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert counted == int(pairs) == 4 * 16 * 3

    tokens = np.random.default_rng(10).integers(0, cfg.vocab_size, (4, 16))
    _, res, want, _ = _serve(cfg, tokens, held_nodes=[2, 3], seed=5)
    assert np.asarray(deepseek_ref.position_errors(res.logits,
                                                   want)).max() < LOGIT_TOL
    held = sum(int(((c >= 8) & (c < 16)).sum()) for c in res.routed)
    assert res.routed_pairs == held < 2 * 4 * 16 * 3


def test_bfloat16_program_fails_tolerance(cfg):
    """The same pass computed in bfloat16 where the config states
    float32 reads outside the logits' tolerance."""
    low = cfg.with_overrides(dtype="bfloat16", param_dtype="bfloat16")
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16))
    _, res, want, _ = _serve(low, tokens)
    assert np.asarray(deepseek_ref.position_errors(res.logits,
                                                   want)).max() > LOGIT_TOL


def test_float8_control_fails_tolerance(cfg, served):
    tokens, sim, res, want, gates = served
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), sim.params)
    low, low_gates = deepseek_ref.forward(
        f32, tokens, [rs.alpha for rs in res.schedules], res.expert_scores,
        _dims(cfg), "fp8")
    assert np.asarray(deepseek_ref.position_errors(low, want)).max() > LOGIT_TOL
    assert max(np.abs(np.asarray(a) - b).max()
               for a, b in zip(low_gates, gates)) > GATE_TOL


def test_engine_moe_refuses_node_groups(cfg):
    """Device-limited routing is served by the simulator only: the
    engine's MoE FFN, which routes a plain top-k, refuses a grouped
    config instead of serving it as another model."""
    from repro.models import moe as M
    x = jnp.zeros((1, 2, cfg.d_model), jnp.float32)
    with pytest.raises(AssertionError, match="n_group"):
        M.moe_ffn({}, x, cfg, 0)
