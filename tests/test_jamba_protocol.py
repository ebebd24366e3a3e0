"""Jamba2-Mini on the DMoE protocol path (`DMoESimulator` walking the
hybrid plan) and on the engine path, against the plain reference
`bench/reference/jamba_ref.py`, at the config's `smoke()` size in
float32: one whole period (7 Mamba + 1 attention, MoE on the odd
sublayers), tiny widths, 4 experts = 4 edge nodes.  Also the same
simulator over a 4-device node mesh against one device."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import model as model_lib
from repro.serving import DMoESimulator

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from reference import jamba_ref  # noqa: E402

#: Both sides compute in float32; they differ only in the order of
#: their sums (the program's chunked associative scan and fused
#: einsums against the reference's token-by-token scan), about 1e-6
#: relative; a float8 rounding of every product's operands (the
#: control) moves them by 1e-2 and more.
LOGIT_TOL = 1e-4
GATE_TOL = 1e-5


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("jamba2-mini")


def _dims(cfg):
    return jamba_ref.Dims.from_config({
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "num_experts": cfg.moe.num_experts, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "num_hidden_layers": cfg.num_layers,
        "attn_layer_offset": 4, "attn_layer_period": 8,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "mamba_d_state": cfg.ssm.d_state})


@pytest.fixture(scope="module")
def served(cfg):
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16))
    sim = DMoESimulator(cfg, scheme="jesa", seed=3)
    res = sim.serve(tokens)
    with jax.default_matmul_precision("highest"):
        want, gates = jamba_ref.forward(
            sim.params, tokens, [rs.alpha for rs in res.schedules],
            _dims(cfg))
    return sim, tokens, res, np.asarray(want), [np.asarray(g) for g in gates]


def test_plan_is_published_period(cfg):
    sim = DMoESimulator(cfg, scheme="topk", seed=0)
    assert sim.plan == [("mamba", False), ("mamba", True),
                        ("mamba", False), ("mamba", True),
                        ("attention", False), ("mamba", True),
                        ("mamba", False), ("mamba", True)]
    assert sim.rounds_per_pass == 4 and sim.chips == 1


def test_simulator_matches_reference(served):
    sim, tokens, res, want, gates = served
    assert res.logits.shape == want.shape == (4, 16, sim.cfg.vocab_size)
    assert len(res.schedules) == len(gates) == 4
    # the QoS l counts protocol rounds
    assert [rs.qos for rs in res.schedules] == [
        pytest.approx(sim.qos.qos(r)) for r in (1, 2, 3, 4)]
    err = np.asarray(jamba_ref.position_errors(res.logits, want))
    assert err.max() < LOGIT_TOL
    assert all(rs.alpha.sum(-1).max() <= 2 for rs in res.schedules)


def test_gates_match_reference(cfg, served):
    sim, tokens, _, _, gates = served

    class Keep:
        name = "keep"

        def __init__(self):
            from repro.schedulers import get_policy
            self.inner, self.seen = get_policy("jesa"), []

        def schedule(self, ctx):
            self.seen.append(ctx.gate_scores)
            return self.inner.schedule(ctx)

    keep = Keep()
    again = DMoESimulator(cfg, policy=keep, seed=3)
    again.serve(tokens)
    for got, want in zip(keep.seen, gates, strict=True):
        assert np.abs(got - want).max() < GATE_TOL


def test_float8_control_fails_tolerance(cfg, served):
    sim, tokens, res, want, gates = served
    low, low_gates = jamba_ref.forward(
        sim.params, tokens, [rs.alpha for rs in res.schedules], _dims(cfg),
        "fp8")
    assert np.asarray(jamba_ref.position_errors(low, want)).max() > LOGIT_TOL
    assert max(np.abs(np.asarray(a) - b).max()
               for a, b in zip(low_gates, gates)) > GATE_TOL


def test_engine_jamba_matches_reference(cfg):
    """The engine's Jamba block (`models/transformer.py`, kind jamba) with
    dropless top-2 routing equals the reference with dense top-k masks."""
    c = cfg.with_overrides(moe_routing="topk", moe_routing_kwargs=(),
                           moe_capacity_factor=8.0)
    params = model_lib.init_params(jax.random.PRNGKey(5), c)
    tokens = np.random.default_rng(1).integers(0, c.vocab_size, (2, 24))
    logits, _, _ = model_lib.forward(params, {"tokens": tokens}, c)
    with jax.default_matmul_precision("highest"):
        want = jamba_ref.routed_forward(params, tokens, _dims(c), top_k=2)
    err = np.asarray(jamba_ref.position_errors(np.asarray(logits), want))
    assert err.max() < LOGIT_TOL


_MESH_SCRIPT = r"""
import json, re, sys, tempfile, warnings
from pathlib import Path
import numpy as np
from repro.configs.base import get_smoke_config
from repro.models import layers as L
from repro.serving import DMoESimulator
from repro.serving.dmoe_sim import node_mesh
import jax
from jax.profiler import ProfileData


def traced(sim, tokens):
    # One traced pass: its result, the unembed step's arguments and
    # output, the step's all-gather result shapes, and the metadata of
    # every `dmoe.logits_d2h` span.
    step, seen = sim._unembed, []

    def keep(*args):
        seen.append((args, step(*args)))
        return seen[-1][1]

    sim._unembed = keep
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            res = sim.serve(tokens)
        finally:
            jax.profiler.stop_trace()
        path = sorted(Path(d).glob("plugins/profile/*/*.xplane.pb"))[-1]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            meta = [dict(ev.stats)
                    for plane in ProfileData.from_file(str(path)).planes
                    for line in plane.lines for ev in line.events
                    if ev.name == "dmoe.logits_d2h"]
    sim._unembed = step
    (args, out), = seen
    hlo = step.lower(*args).compile().as_text()
    gathers = [[int(n) for n in dims.split(",")] for dims in re.findall(
        r"= f32\[([0-9,]+)\]\S* all-gather\(", hlo)]
    return res, args, out, gathers, meta

cfg = get_smoke_config("jamba2-mini")
tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16))
one = DMoESimulator(cfg, scheme="jesa", seed=3,
                    mesh=node_mesh(jax.devices()[:1]))
four = DMoESimulator(cfg, scheme="jesa", seed=3, mesh=node_mesh())
a, b = one.serve(tokens), four.serve(tokens)
w1 = four.params["stages"]["stage0"]["sub1"]["ffn"]["w1"]
c, args, out4, gathers4, meta4 = traced(four, tokens)
d, _, out1, gathers1, meta1 = traced(one, tokens)
# The same pass's logits left split on K, as a step without the gather
# returns them.
split = jax.jit(lambda n, t, x: four._placed(
    L.unembed(L.rmsnorm(x, n, cfg.norm_eps), t), 0))(*args)
extra = {
    "vocab": cfg.vocab_size,
    "replicated": [out1.is_fully_replicated, out4.is_fully_replicated],
    "unembed_devices": [len(out1.sharding.device_set),
                        len(out4.sharding.device_set)],
    "gathers": [gathers1, gathers4],
    "d2h": [meta1, meta4],
    "split": [split.is_fully_replicated, len(split.addressable_shards)],
    "split_equal": bool(np.array_equal(np.asarray(split), c.logits)),
    "logits": [str(c.logits.dtype), list(c.logits.shape)],
    "fresh": not (np.shares_memory(c.logits, b.logits)
                  or np.shares_memory(d.logits, a.logits)),
}
print(json.dumps({
    "devices": len(jax.devices()), "chips": [one.chips, four.chips],
    "xchip": [one.xchip_bytes(4, 16), four.xchip_bytes(4, 16)],
    "expert_shard": list(w1.sharding.shard_shape(w1.shape)),
    "expert_shape": list(w1.shape),
    "schedules": all(np.array_equal(x.alpha, y.alpha)
                     and np.array_equal(x.beta, y.beta)
                     and x.energy == y.energy
                     for x, y in zip(a.schedules, b.schedules, strict=True)),
    "logit_err": float(np.abs(a.logits - b.logits).max()
                       / np.abs(a.logits).max()),
    **extra}))
"""


@pytest.fixture(scope="module")
def mesh_run():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in ("src", os.environ.get("PYTHONPATH", "")) if p))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, cwd=ROOT,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mesh_of_four_matches_one_device(mesh_run):
    """Nodes spread over 4 devices (experts split on E, queries on K):
    the same schedules, and logits within bf16 rounding of one device's
    (float32 sums taken across devices in another order)."""
    assert mesh_run["devices"] == 4
    assert mesh_run["schedules"] is True
    assert mesh_run["logit_err"] < 2.0 ** -8
    assert mesh_run["chips"] == [1, 4]
    # experts split on E: one per device at 4 nodes
    assert mesh_run["expert_shard"][1] * 4 == mesh_run["expert_shape"][1]


def test_xchip_bytes_from_shapes(cfg, mesh_run):
    """Each of the 4 rounds sends every token's float32 FFN input to the
    3 other devices and the 3 other devices' float32 sums back."""
    d = cfg.d_model
    assert mesh_run["xchip"] == [0, 4 * 3 * 4 * 16 * d * (4 + 4)]


def test_mesh_logits_land_from_one_buffer(mesh_run):
    """On the 4-device mesh the unembed step computes its logits split on
    K and gathers them on the device (the gathered shape is the logits',
    not the hidden state's), so the host copies one buffer of
    K·N·V·4 bytes; on one device it copies one buffer too."""
    v = mesh_run["vocab"]
    assert mesh_run["replicated"] == [True, True]
    assert mesh_run["gathers"][1] == [[4, 16, v]]
    want = {"buffers": 1, "bytes": 4 * 16 * v * 4}
    for meta in mesh_run["d2h"]:
        assert [{k: m[k] for k in want} for m in meta] == [want]


def test_mesh_logits_equal_the_split_fetch(mesh_run):
    """The gathered logits equal, bit for bit, the same pass's logits
    fetched split on K from 4 buffers, and every pass lands a fresh
    float32 (K, N, V) array."""
    assert mesh_run["split"] == [False, 4]
    assert mesh_run["split_equal"] is True
    assert mesh_run["logits"] == ["float32", [4, 16, mesh_run["vocab"]]]
    assert mesh_run["fresh"] is True


def test_one_device_mesh_keeps_the_unembed_step(mesh_run):
    """A mesh of one device places and constrains nothing: the unembed
    step's output lives on that device and its HLO gathers nothing."""
    assert mesh_run["unembed_devices"] == [1, 4]
    assert mesh_run["gathers"][0] == []
