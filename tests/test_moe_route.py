"""Differential kernel-parity harness for the fused Pallas routing
family (`repro.kernels.moe_route`).

Every claim the fused path makes is checked against the repo's own XLA
reference — the pre-existing one-hot einsum pipeline in
`repro.models.moe._dispatch_ffn_xla` — never against a re-derivation:

  * `fused_route` (softmax + policy mask + top-k + Eq.-8 renormalize)
    vs `repro.core.selection.route`, fuzzed over every in-graph policy
    mask (des-greedy, dense, channel-aware, siftmoe), shapes, and seeds;
  * the full fused route→dispatch→FFN→combine pipeline vs the one-hot
    einsum reference, fp32 and bf16, with pinned tolerances;
  * capacity overflow / token-drop, all-masked rows, and non-multiple
    shapes (padding) as explicit edge cases;
  * the grouped/ragged layout BIT-EQUAL to the capacity layout after
    scatter-back (np.array_equal, not allclose);
  * dropped-token accounting surfaced in the router aux dict and
    identical between the three `routing_impl`s;
  * backend auto-detection: `default_interpret()` keeps CPU CI in
    interpret mode and the per-call knob stays overridable.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.configs.base import get_smoke_config
from repro.core import selection as sel_lib
from repro.kernels import moe_route as mr
from repro.kernels import ops
from repro.models import moe as moe_mod

# the four in-graph policy masks the fused path must compose with
POLICY_MASKS = ["des", "dense", "channel-aware", "siftmoe"]


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-4, rtol=1e-3)


def _route_ref(logits, routing, top_k, costs, qos=0.5, max_experts=3):
    return sel_lib.route(logits, routing=routing, top_k=top_k, qos=qos,
                         costs=costs, max_experts=max_experts)


def _rand_problem(seed, g, gsz, e, d, f, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(g, gsz, d)), dtype=dtype)
    w1 = jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d), dtype=dtype)
    wu = jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d), dtype=dtype)
    w2 = jnp.asarray(rng.normal(size=(e, f, d)) / np.sqrt(f), dtype=dtype)
    logits = jnp.asarray(rng.normal(size=(g * gsz, e)).astype(np.float32))
    costs = jnp.asarray(rng.uniform(0.1, 1.0, size=(e,)).astype(np.float32))
    return x, {"w1": w1, "wu": wu, "w2": w2}, logits, costs


def _pipelines(params, xg, mk, cw, cap, dtype):
    """(xla, fused, grouped) outputs + aux of the three production
    dispatch impls on identical routed inputs."""
    y_x, a_x = moe_mod._dispatch_ffn_xla(params, xg, mk, cw, cap, dtype)
    y_f, a_f = moe_mod._dispatch_ffn_fused(params, xg, mk, cw, cap, dtype)
    y_g, a_g = moe_mod._dispatch_ffn_grouped(params, xg, mk, cw, cap, dtype)
    return (y_x, a_x), (y_f, a_f), (y_g, a_g)


# ----------------------------------------------------------------------
# fused_route vs selection.route
# ----------------------------------------------------------------------

def test_fused_route_topk_in_kernel():
    """No policy mask: the in-kernel stable-tie top-k must reproduce
    `selection.topk_mask` semantics exactly, combine weights to fp32
    rounding."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(96, 8)).astype(np.float32))
    cb, mk = ops.fused_route(logits, top_k=2, block_t=32)
    cb_ref, mk_ref = sel_lib.route(logits, routing="topk", top_k=2)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mk_ref))
    np.testing.assert_allclose(np.asarray(cb), np.asarray(cb_ref),
                               atol=2e-6, rtol=1e-5)


def test_fused_route_topk_tie_breaking():
    """Duplicate gate values: ties must break by LOWER expert index,
    exactly like `selection.topk_mask`'s stable argsort."""
    logits = jnp.asarray([[1.0, 2.0, 2.0, 2.0],
                          [0.5, 0.5, 0.5, 0.5],
                          [3.0, 1.0, 3.0, 0.0]], dtype=jnp.float32)
    cb, mk = ops.fused_route(logits, top_k=2, block_t=4)
    _, mk_ref = sel_lib.route(logits, routing="topk", top_k=2)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mk_ref))


@settings(max_examples=15, deadline=None)
@given(routing=st.sampled_from(POLICY_MASKS),
       e=st.sampled_from([4, 8, 16]),
       t=st.integers(5, 200),
       top_k=st.integers(1, 3),
       seed=st.integers(0, 10_000))
def test_fused_route_policy_mask_parity(routing, e, t, top_k, seed):
    """Any registry policy's route_mask feeds the fused kernel as the
    input mask; combine weights must match `selection.route` on the
    same mask (padding exercised by non-multiple t)."""
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(t, e)).astype(np.float32))
    costs = jnp.asarray(rng.uniform(0.1, 1.0, size=(e,)).astype(np.float32))
    cb_ref, mk_ref = _route_ref(logits, routing, top_k, costs)
    cb, mk = ops.fused_route(logits, mk_ref, top_k=top_k, block_t=64)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mk_ref))
    np.testing.assert_allclose(np.asarray(cb), np.asarray(cb_ref),
                               atol=2e-6, rtol=1e-5)


def test_fused_route_all_masked_row():
    """A row whose policy mask selects nothing must yield zero combine
    weights (the Eq.-8 epsilon guards the 0/0), matching the
    reference."""
    logits = jnp.asarray(np.random.default_rng(3).normal(
        size=(8, 4)).astype(np.float32))
    mask = jnp.ones((8, 4), dtype=jnp.float32).at[2].set(0.0).at[5].set(0.0)
    cb, mk = ops.fused_route(logits, mask, top_k=2, block_t=8)
    gates = jax.nn.softmax(logits, axis=-1)
    ref = mask * gates
    ref = ref / (jnp.sum(ref, axis=-1, keepdims=True) + 1e-9)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mask))
    np.testing.assert_allclose(np.asarray(cb), np.asarray(ref),
                               atol=2e-6, rtol=1e-5)
    assert np.all(np.asarray(cb)[2] == 0.0)
    assert np.all(np.asarray(cb)[5] == 0.0)


# ----------------------------------------------------------------------
# full pipeline parity: fused / grouped vs the one-hot einsum reference
# ----------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(routing=st.sampled_from(POLICY_MASKS),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       gsz=st.sampled_from([16, 32, 50]),
       cap=st.integers(2, 8),
       seed=st.integers(0, 10_000))
def test_pipeline_parity_fuzz(routing, dtype, gsz, cap, seed):
    """Fused and grouped dispatch pipelines vs the XLA one-hot einsum
    reference on identical (mask, combine) inputs, across all four
    policy masks and both dtypes; grouped must equal fused (capacity)
    BITWISE after scatter-back."""
    g, e, d, f = 2, 8, 16, 24
    x, params, logits, costs = _rand_problem(seed, g, gsz, e, d, f, dtype)
    cb, mk = _route_ref(logits, routing, 2, costs)
    mk = mk.reshape(g, gsz, e)
    cw = cb.reshape(g, gsz, e).astype(jnp.float32)
    (y_x, a_x), (y_f, a_f), (y_g, a_g) = _pipelines(
        params, x, mk, cw, cap, dtype)
    np.testing.assert_allclose(np.asarray(y_f, np.float32),
                               np.asarray(y_x, np.float32), **_tol(dtype))
    assert np.array_equal(np.asarray(y_g), np.asarray(y_f)), \
        "grouped scatter-back must be bit-equal to the capacity layout"
    for k in ("dropped_frac", "dropped_tokens"):
        np.testing.assert_allclose(np.asarray(a_f[k]), np.asarray(a_x[k]),
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(a_g[k]),
                                      np.asarray(a_f[k]))


def test_pipeline_capacity_overflow_token_drop():
    """cap=1 with top-2 routing forces overflow: all three impls must
    drop the SAME tokens, agree on the output, and report identical
    nonzero dropped-token counts."""
    g, gsz, e, d, f = 2, 32, 4, 8, 16
    x, params, logits, costs = _rand_problem(7, g, gsz, e, d, f)
    cb, mk = _route_ref(logits, "des", 2, costs)
    mk = mk.reshape(g, gsz, e)
    cw = cb.reshape(g, gsz, e).astype(jnp.float32)
    (y_x, a_x), (y_f, a_f), (y_g, a_g) = _pipelines(
        params, x, mk, cw, cap=1, dtype=jnp.float32)
    assert float(a_x["dropped_tokens"]) > 0, "cap=1 must overflow"
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_x),
                               **_tol(jnp.float32))
    assert np.array_equal(np.asarray(y_g), np.asarray(y_f))
    np.testing.assert_allclose(float(a_f["dropped_tokens"]),
                               float(a_x["dropped_tokens"]), atol=1e-6)
    assert float(a_g["dropped_tokens"]) == float(a_f["dropped_tokens"])


def test_pipeline_all_masked_rows():
    """Tokens with an all-zero mask row (e.g. churn killed every
    selected expert) must contribute nothing and produce zero output in
    every impl."""
    g, gsz, e, d, f = 1, 16, 4, 8, 16
    x, params, logits, costs = _rand_problem(11, g, gsz, e, d, f)
    cb, mk = _route_ref(logits, "dense", 2, costs)
    mk = mk.reshape(g, gsz, e).at[0, 3].set(0.0).at[0, 9].set(0.0)
    cw = (cb.reshape(g, gsz, e) * mk).astype(jnp.float32)
    (y_x, _), (y_f, _), (y_g, _) = _pipelines(
        params, x, mk, cw, cap=gsz, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_x),
                               **_tol(jnp.float32))
    assert np.array_equal(np.asarray(y_g), np.asarray(y_f))
    assert np.all(np.asarray(y_f)[0, 3] == 0.0)
    assert np.all(np.asarray(y_f)[0, 9] == 0.0)


def test_pipeline_padding_shapes():
    """Shapes that are NOT multiples of the kernel blocks (gsz=50,
    f=100, cap=3) exercise every padding branch."""
    g, gsz, e, d, f = 3, 50, 4, 8, 100
    x, params, logits, costs = _rand_problem(13, g, gsz, e, d, f)
    cb, mk = _route_ref(logits, "channel-aware", 2, costs)
    mk = mk.reshape(g, gsz, e)
    cw = cb.reshape(g, gsz, e).astype(jnp.float32)
    (y_x, _), (y_f, _), (y_g, _) = _pipelines(
        params, x, mk, cw, cap=3, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_x),
                               **_tol(jnp.float32))
    assert np.array_equal(np.asarray(y_g), np.asarray(y_f))


# ----------------------------------------------------------------------
# kernel-level invariants
# ----------------------------------------------------------------------

def test_capacity_dispatch_is_bitwise_gather():
    """The gather-dispatch kernel is pure data movement: its output must
    equal the one-hot dispatch einsum BITWISE (same tokens, same
    slots)."""
    rng = np.random.default_rng(17)
    g, gsz, e, d, cap = 2, 24, 4, 8, 5
    x = jnp.asarray(rng.normal(size=(g, gsz, d)).astype(np.float32))
    mask = jnp.asarray((rng.uniform(size=(g, gsz, e)) < 0.4)
                       .astype(np.float32))
    pos, keep = mr.capacity_positions(mask, cap)
    xe = mr.capacity_dispatch(x, pos, keep, cap)
    slot = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
    xe_ref = jnp.einsum("gsec,gsd->egcd", slot, x)
    np.testing.assert_array_equal(np.asarray(xe), np.asarray(xe_ref))


def test_grouped_layout_invariants():
    """Segment offsets are block-aligned, counts match the kept mask,
    and every live block maps to the expert that owns its segment."""
    rng = np.random.default_rng(19)
    g, gsz, e, cap, bc = 2, 24, 4, 5, 8
    mask = jnp.asarray((rng.uniform(size=(g, gsz, e)) < 0.5)
                       .astype(np.float32))
    pos, keep = mr.capacity_positions(mask, cap)
    layout = mr.grouped_layout(pos, keep, cap, block_c=bc)
    offs = np.asarray(layout.offsets)
    assert np.all(offs % layout.block_c == 0)
    np.testing.assert_array_equal(
        np.asarray(layout.counts),
        np.asarray(jnp.sum(keep > 0, axis=(0, 1))))
    be = np.asarray(layout.block_expert)
    act = np.asarray(layout.block_active)
    starts = np.arange(be.size) * layout.block_c
    for b in range(be.size):
        if act[b]:
            assert offs[be[b]] <= starts[b] < offs[be[b]] + g * cap + \
                layout.block_c
    # the scratch tail block is always dead
    assert act[-1] == 0


def test_ragged_ffn_matches_capacity_ffn_rows():
    """Per-row bit-equality of the ragged FFN vs `moe_expert_ffn` at
    equal block shapes — the property the layouts' bit-contract rests
    on."""
    rng = np.random.default_rng(23)
    g, gsz, e, d, f, cap, bc = 2, 16, 4, 8, 32, 4, 8
    x = jnp.asarray(rng.normal(size=(g, gsz, d)).astype(np.float32))
    mask = jnp.asarray((rng.uniform(size=(g, gsz, e)) < 0.5)
                       .astype(np.float32))
    w1 = jnp.asarray(rng.normal(size=(e, d, f)).astype(np.float32))
    wu = jnp.asarray(rng.normal(size=(e, d, f)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(e, f, d)).astype(np.float32))
    pos, keep = mr.capacity_positions(mask, cap)
    xe = mr.capacity_dispatch(x, pos, keep, cap)
    ye = ops.moe_expert_ffn(xe.reshape(e, g * cap, d), w1, wu, w2,
                            block_c=bc, block_f=16)
    layout = mr.grouped_layout(pos, keep, cap, block_c=bc)
    xs = mr.grouped_dispatch(x, layout)
    ys = mr.moe_expert_ffn_ragged(xs, layout, w1, wu, w2, block_f=16)
    ye_np = np.asarray(ye)
    ys_np = np.asarray(ys)
    for ei in range(e):
        seg = ys_np[ei * layout.seg_pad: ei * layout.seg_pad + g * cap]
        np.testing.assert_array_equal(seg, ye_np[ei])


# ----------------------------------------------------------------------
# moe_ffn-level: routing_impl knob + aux accounting
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_moe():
    cfg = get_smoke_config("mixtral-8x7b")
    params = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model),
                          dtype=jnp.float32)
    return cfg, params, x


def test_routing_impl_default_is_xla(smoke_moe):
    cfg, _, _ = smoke_moe
    assert cfg.moe.routing_impl == "xla"
    assert mr.ROUTING_IMPLS == ("xla", "fused", "grouped")
    with pytest.raises(ValueError, match="routing_impl"):
        mr.check_routing_impl("bogus")


@pytest.mark.parametrize("impl", ["fused", "grouped"])
def test_moe_ffn_impl_parity(smoke_moe, impl):
    """`moe_ffn` under routing_impl="fused"/"grouped" vs the default
    "xla" path on the real smoke config (des routing, overflow-prone
    capacity): outputs allclose, dropped-token aux identical."""
    cfg, params, x = smoke_moe
    y0, a0 = jax.jit(lambda p, xx: moe_mod.moe_ffn(p, xx, cfg, 0))(
        params, x)
    cfg_i = cfg.with_overrides(moe_routing_impl=impl)
    y1, a1 = jax.jit(lambda p, xx: moe_mod.moe_ffn(p, xx, cfg_i, 0))(
        params, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               **_tol(jnp.float32))
    np.testing.assert_allclose(float(a1["dropped_tokens"]),
                               float(a0["dropped_tokens"]), atol=1e-6)
    np.testing.assert_allclose(float(a1["dropped_frac"]),
                               float(a0["dropped_frac"]), atol=1e-6)


def test_dropped_tokens_surfaced_in_aux(smoke_moe):
    """Capacity overflow accounting (satellite): a capacity_factor small
    enough to overflow must surface a positive integral dropped-token
    count in aux for every impl, and the counts must agree."""
    cfg, params, x = smoke_moe
    cfg_tight = cfg.with_overrides(moe_capacity_factor=0.25)
    counts = {}
    for impl in ("xla", "fused", "grouped"):
        c = cfg_tight.with_overrides(moe_routing_impl=impl)
        _, aux = jax.jit(lambda p, xx, c=c: moe_mod.moe_ffn(p, xx, c, 0))(
            params, x)
        assert "dropped_tokens" in aux and "dropped_frac" in aux
        counts[impl] = float(aux["dropped_tokens"])
    assert counts["xla"] > 0
    assert counts["xla"] == counts["fused"] == counts["grouped"]
    assert counts["xla"] == int(counts["xla"]), "token counts are integral"


# ----------------------------------------------------------------------
# backend auto-detection (interpret default)
# ----------------------------------------------------------------------

def test_default_interpret_cpu():
    """CPU CI must auto-detect interpret mode (no Mosaic lowering off
    TPU); the regression this pins: `moe_expert_ffn` used to hardcode
    interpret=True, now it resolves via `default_interpret()`."""
    assert jax.default_backend() != "tpu"
    assert mr.default_interpret() is True


@pytest.mark.parametrize("backend, expected",
                         [("cpu", True), ("gpu", True), ("tpu", False)])
def test_resolve_interpret_follows_backend(monkeypatch, backend, expected):
    """None lowers through Mosaic on a TPU only; an explicit bool wins."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert mr.resolve_interpret(None) is expected
    assert mr.resolve_interpret(not expected) is (not expected)


@pytest.mark.parametrize("name", ["flash_attention", "flash_decode",
                                  "wkv_chunked", "moe_expert_ffn",
                                  "fused_route"])
def test_ops_wrappers_default_to_backend_detection(name):
    """No wrapper forces interpret mode on a TPU caller."""
    sig = inspect.signature(getattr(ops, name))
    assert sig.parameters["interpret"].default is None


def test_interpret_knob_overridable():
    """interpret=None (auto) and interpret=True must agree bitwise on
    CPU — and the explicit knob must stay accepted by every entry
    point."""
    rng = np.random.default_rng(29)
    e, c, d, f = 2, 8, 4, 8
    x = jnp.asarray(rng.normal(size=(e, c, d)).astype(np.float32))
    w1 = jnp.asarray(rng.normal(size=(e, d, f)).astype(np.float32))
    wu = jnp.asarray(rng.normal(size=(e, d, f)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(e, f, d)).astype(np.float32))
    y_auto = ops.moe_expert_ffn(x, w1, wu, w2)
    y_expl = ops.moe_expert_ffn(x, w1, wu, w2, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_auto), np.asarray(y_expl))
    lg = jnp.asarray(rng.normal(size=(8, e)).astype(np.float32))
    cb_auto, _ = ops.fused_route(lg, top_k=1)
    cb_expl, _ = ops.fused_route(lg, top_k=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(cb_auto), np.asarray(cb_expl))
