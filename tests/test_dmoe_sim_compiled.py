"""The protocol round's compiled steps (`DMoESimulator`): each jitted
step traces once per shape, the served logits are the model's own math
layer by layer, one layer plan serves the plain MoE block and the Jamba
hybrid, and the expert FFN is dispatched before the scheduler."""

import hashlib
import warnings

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.analysis.sanitizers import recompile_guard
from repro.configs.base import get_smoke_config
from repro.schedulers import SchedulerPolicy, get_policy
from repro.serving import DMoESimulator

STEPS = ("_embed_step", "_mixer_step", "_ffn_step", "_combine_step",
         "_unembed_step")


@pytest.fixture(scope="module")
def cfg():
    c = get_smoke_config("mixtral-8x7b")
    return c.with_overrides(num_layers=2, moe_num_experts=4)


class Recording(SchedulerPolicy):
    """The registry policy, with every round's context kept."""

    def __init__(self, inner):
        self.inner, self.name, self.contexts = inner, inner.name, []

    def schedule(self, ctx):
        self.contexts.append(ctx)
        return self.inner.schedule(ctx)


def _spans(log_dir):
    """Every `dmoe.*` span of the trace in `log_dir`: (name, start_ns,
    end_ns, metadata), in time order."""
    path = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    spans = []
    with warnings.catch_warnings():
        # jaxlib's stats type warns of its own missing __module__.
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           dict(ev.stats)) for ev in line.events
                          if ev.name.startswith("dmoe.")]
    return sorted(spans, key=lambda s: s[1])


def _pass_compiles(log_dir):
    """The `compiles` metadata of each `dmoe.pass` span, in time order."""
    return [s[3]["compiles"] for s in _spans(log_dir) if s[0] == "dmoe.pass"]


def test_steps_trace_once_per_shape(cfg, tmp_path):
    rng = np.random.default_rng(0)
    wave = rng.integers(0, cfg.vocab_size, size=(4, 6))
    longer = rng.integers(0, cfg.vocab_size, size=(4, 7))
    sim = DMoESimulator(cfg, scheme="jesa", seed=1)
    assert sim.compiles == 0

    jax.profiler.start_trace(str(tmp_path))
    try:
        with recompile_guard({s: 1 for s in STEPS}):
            sim.serve(wave)
        first = sim.compiles
        # The same (K, N): every step hits jit's cache, nothing compiles.
        with recompile_guard() as log:
            sim.serve(wave)
        assert log.counts == {}
        assert sim.compiles == first
        # A new N is a new shape: every step traces again, once.
        with recompile_guard({s: 1 for s in STEPS}):
            sim.serve(longer)
    finally:
        jax.profiler.stop_trace()

    assert first == len(STEPS)
    assert sim.compiles == 2 * len(STEPS)
    assert _pass_compiles(tmp_path) == [len(STEPS), 0, len(STEPS)]


def _rmsnorm(x, w, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, H, Dh), positions 0..S-1, halves rotated."""
    s, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, dh, 2) / dh)
    ang = np.arange(s)[:, None] * freqs                   # (S, Dh/2)
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x, cfg):
    """Causal GQA: query head j reads kv head j // (H / Hkv)."""
    s = x.shape[1]
    q = _rope(np.einsum("bsd,dhe->bshe", x, p["wq"]), cfg.rope_theta)
    k = _rope(np.einsum("bsd,dhe->bshe", x, p["wk"]), cfg.rope_theta)
    v = np.einsum("bsd,dhe->bshe", x, p["wv"])
    rep = cfg.num_heads // cfg.num_kv_heads
    k, v = np.repeat(k, rep, axis=2), np.repeat(v, rep, axis=2)
    scores = np.einsum("bqhe,bkhe->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.einsum("bhqk,bkhe->bqhe", probs, v)
    return np.einsum("bshe,hed->bsd", out, p["wo"])


def _reference(params, tokens, alphas, cfg):
    """The pass in float64 NumPy from the simulator's float32 weights,
    layer by layer and expert by expert, each round's combine
    teacher-forced on the selection the scheduler made: (logits, gates
    of every layer)."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    stack, eps = p["stages"]["stage0"], cfg.norm_eps
    x = p["embed"][tokens]
    gates = []
    for layer, alpha in enumerate(alphas):
        lp = jax.tree.map(lambda a: a[layer], stack)
        x = x + _attention(lp["attn"], _rmsnorm(x, lp["norm1"], eps), cfg)
        h = _rmsnorm(x, lp["norm2"], eps)
        logits = h @ lp["ffn"]["w_gate_router"]
        g = np.exp(logits - logits.max(-1, keepdims=True))
        g /= g.sum(-1, keepdims=True)
        gates.append(g)
        w = alpha * g
        w /= np.maximum(w.sum(-1, keepdims=True), 1e-9)      # Eq. 8
        for e in range(w.shape[-1]):
            f = lp["ffn"]
            a = h @ f["w1"][e]
            y = (a / (1.0 + np.exp(-a))) * (h @ f["wu"][e]) @ f["w2"][e]
            x = x + w[..., e:e + 1] * y
    x = _rmsnorm(x, p["final_norm"], eps)
    return x @ p["unembed"].T, gates


def test_served_logits_match_layerwise_reference(cfg):
    assert cfg.dtype == "float32" and not cfg.tie_embeddings
    tokens = np.random.default_rng(2).integers(0, cfg.vocab_size, (4, 6))
    policy = Recording(get_policy("jesa"))
    sim = DMoESimulator(cfg, policy=policy, seed=4)
    res = sim.serve(tokens)
    want, want_gates = _reference(sim.params, tokens,
                                  [rs.alpha for rs in res.schedules], cfg)
    np.testing.assert_allclose(res.logits, want, rtol=1e-4, atol=1e-5)
    for ctx, g in zip(policy.contexts, want_gates, strict=True):
        np.testing.assert_allclose(ctx.gate_scores, g, rtol=1e-4, atol=1e-6)


#: Each served config's layer plan: (mixer kind, protocol round?) per
#: sublayer of a period.  A plain MoE block is a period of one.
PLANS = {
    "mixtral": [("attention", True)],
    "jamba": [("mamba", False), ("mamba", True), ("mamba", False),
              ("mamba", True), ("attention", False), ("mamba", True),
              ("mamba", False), ("mamba", True)],
}

#: SHA-256 of one traced pass (`_digest`) per config, recorded on an
#: 8-core CPU host before the two configs shared one layer loop.  XLA's
#: CPU dots split their sums over the host's threads, so a host with
#: another core count computes other last bits of the logits.
DIGESTS = {
    "mixtral":
        "3db5c21a29b5900ee3d0bac2d546f19e9f913f2af69e2174f714f2428115f845",
    "jamba":
        "647c50dc0a042952e4f6596ce4bebc7ebf53116b374d02580363d71f83640280",
}


@pytest.fixture(scope="module", params=sorted(PLANS))
def traced_pass(request, cfg, tmp_path_factory):
    """One pass of each config, served under the profiler: (name, the
    simulator, its result, its `dmoe.*` spans)."""
    name = request.param
    c = cfg if name == "mixtral" else get_smoke_config("jamba2-mini")
    sim = DMoESimulator(c, scheme="jesa", seed=11)
    tokens = np.random.default_rng(12).integers(0, c.vocab_size, (sim.k, 8))
    log_dir = tmp_path_factory.mktemp(name)
    jax.profiler.start_trace(str(log_dir))
    try:
        res = sim.serve(tokens)
    finally:
        jax.profiler.stop_trace()
    return name, sim, res, _spans(log_dir)


def test_plan_sets_the_rounds(traced_pass):
    name, sim, res, _ = traced_pass
    assert sim.plan == PLANS[name]
    assert sim.rounds_per_pass == len(res.schedules)
    assert sim.rounds_per_pass == res.selection_hist.shape[0]


def test_expert_ffn_dispatched_before_gate_wait(traced_pass):
    """The alpha-independent expert FFN step is dispatched before the
    round blocks on its gate scores and runs the scheduler."""
    _, sim, _, spans = traced_pass
    rounds = [s for s in spans if s[0] == "dmoe.round"]
    assert len(rounds) == sim.rounds_per_pass
    for r in rounds:
        inside = {s[0]: s[1] for s in spans
                  if r[1] <= s[1] and s[2] <= r[2] and s is not r}
        assert inside["dmoe.expert_ffn"] < inside["dmoe.gate_d2h"]
        assert inside["dmoe.gate_d2h"] < inside["dmoe.schedule"]


def _digest(res) -> str:
    """SHA-256 of a pass's logits and every round's alpha, beta and B&B
    node count."""
    h = hashlib.sha256(np.ascontiguousarray(res.logits).tobytes())
    for rs in res.schedules:
        h.update(np.ascontiguousarray(rs.alpha).tobytes())
        h.update(np.ascontiguousarray(rs.beta).tobytes())
        h.update(str(int(rs.des_nodes)).encode())
    return h.hexdigest()


def test_pass_digest(traced_pass):
    name, _, res, _ = traced_pass
    assert _digest(res) == DIGESTS[name]
