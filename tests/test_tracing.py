"""The served protocol pass's profiler spans (`dmoe.*`): one `dmoe.pass`
per `DMoESimulator.serve`, every span nested where docs/serving.md says,
the counters on `dmoe.des` and `dmoe.assign` equal to what the
schedules report, and a traced pass bit-identical to an untraced one."""

import warnings

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import get_smoke_config
from repro.core import subcarrier as sc_lib
from repro.core.gating import QoSSchedule
from repro.schedulers import SchedulerPolicy, get_policy
from repro.serving import DMoESimulator

#: Each span and the span it sits in (None: outermost).
PARENT = {
    "dmoe.pass": None,
    "dmoe.embed": "dmoe.pass",
    "dmoe.round": "dmoe.pass",
    "dmoe.unembed": "dmoe.pass",
    "dmoe.logits_d2h": "dmoe.pass",
    "dmoe.params": "dmoe.round",
    "dmoe.attn_gate": "dmoe.round",
    "dmoe.expert_ffn": "dmoe.round",
    "dmoe.gate_d2h": "dmoe.round",
    "dmoe.schedule": "dmoe.round",
    "dmoe.combine": "dmoe.round",
    "dmoe.account": "dmoe.round",
    "dmoe.des": "dmoe.schedule",
    "dmoe.assign": "dmoe.schedule",
}

#: A QoS under which some rows have no D experts that meet it
#: (Remark-2 fallback) and others do.
QOS = QoSSchedule(z=0.75, gamma0=0.85)


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


def _span_lines(log_dir):
    """The `dmoe.*` spans of each host thread line of the trace in
    `log_dir`: (name, start_ns, end_ns, metadata) per span."""
    path = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    lines = []
    with warnings.catch_warnings():
        # jaxlib's stats type warns of its own missing __module__.
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                          dict(ev.stats)) for ev in line.events
                         if ev.name.startswith("dmoe.")]
                if spans:
                    lines.append(spans)
    return lines


def _parent(span, line):
    """The innermost other span of the same line that contains `span`."""
    inside = [o for o in line if o is not span
              and o[1] <= span[1] and span[2] <= o[2]]
    return min(inside, key=lambda o: o[2] - o[1]) if inside else None


@pytest.mark.parametrize("scheme", ["jesa", "sharded-des", "async-des"])
def test_served_pass_spans(cfg, scheme, tmp_path):
    rng = np.random.default_rng(4)
    waves = [rng.integers(0, cfg.vocab_size, size=(4, 6)) for _ in range(2)]
    plain = DMoESimulator(cfg, scheme=scheme, seed=9, qos=QOS)
    want = [plain.serve(t) for t in waves]

    policy = Recording(get_policy(scheme))
    sim = DMoESimulator(cfg, policy=policy, seed=9, qos=QOS)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        got = [sim.serve(t) for t in waves]
    finally:
        jax.profiler.stop_trace()

    # Tracing changes no result.
    for w, g in zip(want, got):
        assert np.array_equal(w.logits, g.logits)
        assert w.summary == g.summary
        for ws, gs in zip(w.schedules, g.schedules, strict=True):
            assert np.array_equal(ws.alpha, gs.alpha)
            assert np.array_equal(ws.beta, gs.beta)
            assert ws.energy == gs.energy
            assert ws.energy_trace == gs.energy_trace

    lines = _span_lines(tmp_path)
    spans = [(s, line) for line in lines for s in line]
    assert {s[0] for s, _ in spans} == set(PARENT)
    for s, line in spans:
        parent = _parent(s, line)
        assert (parent and parent[0]) == PARENT[s[0]], s

    passes = [s for s, _ in spans if s[0] == "dmoe.pass"]
    assert [p[3]["pass"] for p in passes] == [1, 2]
    # The logits' copy: one device buffer of K·N·V float32 a pass.
    k, n = waves[0].shape
    assert [s[3] for s, _ in spans if s[0] == "dmoe.logits_d2h"] == [
        {"buffers": 1, "bytes": k * n * cfg.vocab_size * 4}] * 2
    for p in passes:
        rounds = sorted((s for s, line in spans if s[0] == "dmoe.round"
                         and _parent(s, line) == p), key=lambda s: s[1])
        assert [r[3] for r in rounds] == [
            {"pass": p[3]["pass"], "layer": layer}
            for layer in range(1, cfg.num_layers + 1)]

    schedules = [rs for g in got for rs in g.schedules]
    steps = sum(rs.iterations for rs in schedules)
    des = [s for s, _ in spans if s[0] == "dmoe.des"]
    assert len(des) == steps
    assign = sorted((s for s, _ in spans if s[0] == "dmoe.assign"),
                    key=lambda s: s[1])
    assert len(assign) == steps
    assert sum(s[3]["nodes"] for s in des) == sum(
        rs.des_nodes for rs in schedules)

    # A round's last assignment is its beta: `links` counts the links it
    # serves, `solver` is 0 where Theorem 1's fast path took them.
    last = np.cumsum([rs.iterations for rs in schedules]) - 1
    for ctx, rs, i in zip(policy.contexts, schedules, last, strict=True):
        links = np.argwhere(rs.beta.sum(axis=-1) > 0)
        fast = sc_lib.max_rate_assignment(ctx.rates, links) is not None
        assert assign[i][3] == {"links": len(links), "solver": int(not fast)}
    assert {s[3]["solver"] for s in assign} <= {0, 1}

    # Remark-2 rows, counted apart from the solver: no D experts reach
    # the round's QoS.  Each alpha step of the round solves them again.
    fallback = 0
    for ctx, rs in zip(policy.contexts, schedules, strict=True):
        top_d = np.sort(ctx.gate_scores, axis=-1)[..., -ctx.max_experts:]
        fallback += int((top_d.sum(axis=-1) < rs.qos).sum()) * rs.iterations
    rows = ctx.gate_scores.shape[0] * ctx.gate_scores.shape[1]
    assert 0 < fallback < rows * steps
    assert sum(s[3]["fallback"] for s in des) == fallback


def test_hybrid_pass_spans(tmp_path):
    """The Jamba plan's spans: `dmoe.mixer` (kind mamba or attention) and
    `dmoe.dense_ffn` at every sublayer that is no protocol round, the
    mixer inside `dmoe.round` where it is one; `dmoe.pass` carries the
    chips and the bytes moved between them (none on one device), and
    `dmoe.logits_d2h` the buffers and bytes of the logits' copy."""
    cfg = get_smoke_config("jamba2-mini")
    sim = DMoESimulator(cfg, scheme="jesa", seed=2)
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, (4, 8))
    jax.profiler.start_trace(str(tmp_path))
    try:
        sim.serve(tokens)
    finally:
        jax.profiler.stop_trace()
    (line,) = [line for line in _span_lines(tmp_path)
               if any(s[0] == "dmoe.pass" for s in line)]
    by_name = {}
    for s in line:
        by_name.setdefault(s[0], []).append(s)
    (p,) = by_name["dmoe.pass"]
    assert p[3]["chips"] == 1 and p[3]["xchip_bytes"] == 0
    (d2h,) = by_name["dmoe.logits_d2h"]
    assert d2h[3] == {"buffers": 1, "bytes": 4 * 8 * cfg.vocab_size * 4}
    mixers = sorted(by_name["dmoe.mixer"], key=lambda s: s[1])
    assert [m[3]["kind"] for m in mixers] == [
        "attention" if i == 4 else "mamba" for i in range(8)]
    parents = [_parent(m, line)[0] for m in mixers]
    assert parents == ["dmoe.pass", "dmoe.round"] * 4
    assert len(by_name["dmoe.dense_ffn"]) == 4
    assert all(_parent(s, line)[0] == "dmoe.pass"
               for s in by_name["dmoe.dense_ffn"])
    assert [r[3]["layer"] for r in by_name["dmoe.round"]] == [1, 2, 3, 4]
    assert "dmoe.attn_gate" not in by_name and "dmoe.params" not in by_name
    for name in ("dmoe.expert_ffn", "dmoe.gate_d2h", "dmoe.schedule",
                 "dmoe.combine", "dmoe.account"):
        assert len(by_name[name]) == 4
        assert all(_parent(s, line)[0] == "dmoe.round" for s in by_name[name])


def test_group_pass_spans(tmp_path):
    """DeepSeek-V2's plan over node groups: the leading dense layer's
    `dmoe.mixer` (kind mla) and `dmoe.dense_ffn` in `dmoe.pass`, each MoE
    round's gated `dmoe.mixer` of kind mla; `dmoe.expert_ffn` carries the
    held routed experts and the shared experts, `dmoe.pass` the pairs
    the combines weighed from the held experts, as the result counts
    them."""
    cfg = get_smoke_config("deepseek-v2")
    sim = DMoESimulator(cfg, scheme="jesa", seed=2, held_nodes=[1, 2])
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, (4, 8))
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = sim.serve(tokens)
    finally:
        jax.profiler.stop_trace()
    (line,) = [line for line in _span_lines(tmp_path)
               if any(s[0] == "dmoe.pass" for s in line)]
    by_name = {}
    for s in line:
        by_name.setdefault(s[0], []).append(s)
    (p,) = by_name["dmoe.pass"]
    held = sum(int(((c >= 4) & (c < 12)).sum()) for c in res.routed)
    assert p[3]["routed_pairs"] == res.routed_pairs == held
    assert 0 < held < sum(c.size for c in res.routed)
    mixers = sorted(by_name["dmoe.mixer"], key=lambda s: s[1])
    assert [m[3]["kind"] for m in mixers] == ["mla"] * 3
    assert [_parent(m, line)[0] for m in mixers] == [
        "dmoe.pass", "dmoe.round", "dmoe.round"]
    (dense,) = by_name["dmoe.dense_ffn"]
    assert _parent(dense, line)[0] == "dmoe.pass"
    ffn = by_name["dmoe.expert_ffn"]
    assert [s[3] for s in ffn] == [{"held": 8, "shared": 2}] * 2
    assert "dmoe.attn_gate" not in by_name and "dmoe.params" not in by_name
