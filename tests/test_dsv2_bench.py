"""The benchmark's DeepSeek-V2 cell (`deepseek-v2.proto-n64`) at CPU
widths: whole runs of its driver (`bench/drivers/protocol_group.py`)
through `run.run_cell`, planted faults that the comparison with
`bench/reference/deepseek_ref.py` has to catch, and the readers of its
per-layer metrics on hand-made trace planes."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import common  # noqa: E402
import program_spans  # noqa: E402

CELL = "deepseek-v2.proto-n64"
#: CPU widths: every width cut; the depth (1 dense + 4 MoE layers), the
#: 160 routed experts in 8 groups (K=8 nodes, 2 held: 40 experts), top-6
#: within D=3 nodes, 2 shared experts, YaRN and the bf16 types kept.
CPU_WIDTHS = {"hidden_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 4, "q_lora_rank": 64,
              "kv_lora_rank": 32, "qk_nope_head_dim": 32,
              "qk_rope_head_dim": 16, "v_head_dim": 32,
              "intermediate_size": 256, "moe_intermediate_size": 64,
              "vocab_size": 256}
PROGRAM_KEYS = {"hidden_size": ["d_model"],
                "num_attention_heads": ["num_heads"],
                "num_key_value_heads": ["num_kv_heads"],
                "q_lora_rank": ["q_lora_rank"],
                "kv_lora_rank": ["kv_lora_rank"],
                "qk_nope_head_dim": ["nope_head_dim"],
                "qk_rope_head_dim": ["rope_head_dim"],
                "v_head_dim": ["v_head_dim"], "intermediate_size": ["d_ff"],
                "moe_intermediate_size": ["moe_d_ff_expert"],
                "vocab_size": ["vocab_size"]}
#: The check's limits at CPU widths, where the bf16 program reads higher
#: than at the cell's (`bench/limits/<cell>.json` is set on the chip).
#: From `run.run_cell` over 12 seeds each: the program (seeds
#: 3000019301-312) read at most logit_err 0.0727 and gate_err 0.0525,
#: the float8 control (seeds 3000019401-412) at least 0.744 and 0.556;
#: each limit lies between, with room on both sides.
CPU_LIMITS = {"logit_err": {"limit": 0.25}, "gate_err": {"limit": 0.15},
              "sched_faults": {"limit": 0}, "route_faults": {"limit": 0}}


def cpu_config(**program):
    spec = common.load_json(ROOT / "BENCHMARK.json")
    entry = {c["name"]: c for c in spec["configs"]}["deepseek-v2-l5"]
    config = common.load_json(ROOT / entry["file"])
    config.update(CPU_WIDTHS)
    config["overrides"] = dict(
        config["overrides"], **program,
        **{p: v for k, v in CPU_WIDTHS.items() for p in PROGRAM_KEYS[k]})
    return config


@pytest.fixture
def compile_cache_restored():
    """`run.run_cell` turns on the persistent compile cache for its
    process; put this worker's settings back after the test."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def _drop_shared(monkeypatch):
    """An expert-FFN step that leaves the shared experts out."""
    from repro.serving.dmoe_sim import DMoESimulator

    step = DMoESimulator._ffn_step

    def without(self, sub, period, h):
        ye, _ = step(self, sub, period, h)
        return ye, None

    monkeypatch.setattr(DMoESimulator, "_ffn_step", without)


def _ignore_alpha(monkeypatch):
    """Each token's top-6 taken over every node, as if alpha selected
    them all."""
    import jax.numpy as jnp
    from repro.serving.dmoe_sim import DMoESimulator

    weights = DMoESimulator._routed_weights
    monkeypatch.setattr(
        DMoESimulator, "_routed_weights",
        lambda self, alpha, gates: weights(self, jnp.ones_like(alpha), gates))


def _flat_router(monkeypatch):
    """A router that answers 1/E for every routed expert."""
    import jax.numpy as jnp
    from repro.serving.dmoe_sim import DMoESimulator

    step = DMoESimulator._mixer_step

    def flat(self, sub, period, x, *, mixer, gate):
        out = step(self, sub, period, x, mixer=mixer, gate=gate)
        if not gate:
            return out
        x, h, gates = out
        return x, h, jnp.full_like(gates, 1.0 / gates.shape[-1])

    monkeypatch.setattr(DMoESimulator, "_mixer_step", flat)


#: Each planted fault: the program's overrides, and what to patch.
FAULTS = {
    "none": ({}, None),
    "yarn": ({"yarn_factor": 1.0}, None),
    "scaling": ({"moe_routed_scaling_factor": 1.0}, None),
    "shared": ({}, _drop_shared),
    "all_nodes": ({}, _ignore_alpha),
    "flat_router": ({}, _flat_router),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_reads_not_correct(fault, monkeypatch,
                                         compile_cache_restored):
    """YaRN dropped (plain rotary positions and 1/sqrt(192)), the x16 of
    the routed weights dropped, the shared experts dropped, the top-6
    taken over all nodes ignoring alpha, or a flat router, each reads
    `correct` false by
    the CPU widths' limits; the sound program reads it true, with a
    whole result line and no compile inside the window."""
    import run as bench_run

    program, patch = FAULTS[fault]
    if patch is not None:
        patch(monkeypatch)
    spec = common.load_json(ROOT / "BENCHMARK.json")
    result = bench_run.run_cell(spec, CELL, 2**31 + 47, 0.5, False,
                                require_tpu=False,
                                config=cpu_config(**program),
                                limits=CPU_LIMITS)
    checks = result["checks"]
    assert checks["sched_faults"]["value"] == 0
    assert result["correct"] is (fault == "none"), checks
    if fault == "flat_router":
        # a wrong router fails `gate_err` by the chip's limit too
        chip = common.load_json(ROOT / "bench" / "limits" / f"{CELL}.json")
        assert checks["gate_err"]["value"] > max(
            CPU_LIMITS["gate_err"]["limit"], chip["gate_err"]["limit"])
    if fault == "all_nodes":
        assert checks["route_faults"]["value"] > 0
    else:
        assert checks["route_faults"]["value"] == 0
    if fault == "none":
        assert list(result)[:5] == ["correct", "attempted", "failed",
                                    "metrics", "device"]
        assert result["window_compiles"] == 0
        assert result["attempted"] > 0 and result["attempted"] % 8 == 0
        assert set(result["metrics"]) == {"tok_s", "lat_p95_ms", "setup_s"}
        assert set(checks) == set(CPU_LIMITS)


def _ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start),
              duration_ns=float(end - start), stats=list(stats.items()))


def test_readers_of_the_dsv2_metrics(monkeypatch):
    """`expert_use.dsv2` reads `routed_pairs` over tokens times `held`,
    `mla_ms.dsv2` the MLA mixer and dense-FFN spans, `ffn_ms.dsv2` the
    FFN step's module on the first device, `ffn_roofline.dsv2` its
    least time over that; the readers shared with the `.proto` cell
    read as there (`dispatch_ms.dsv2` with the MLA mixer and dense-FFN
    steps); without the program's counters and spans (a program
    before them) each reads None."""
    host = [_ev("bench.window", 0, 1000),
            _ev("bench.pass", 0, 450), _ev("bench.pass", 500, 950),
            _ev("dmoe.pass", 0, 400, **{"pass": 1}, routed_pairs=300),
            _ev("dmoe.embed", 0, 10),
            _ev("dmoe.mixer", 10, 30, kind="mla"),
            _ev("dmoe.dense_ffn", 30, 35),
            _ev("dmoe.round", 40, 300, **{"pass": 1, "layer": 1}),
            _ev("dmoe.mixer", 40, 60, kind="mla"),
            _ev("dmoe.expert_ffn", 60, 70, held=40, shared=2),
            _ev("dmoe.gate_d2h", 70, 90),
            _ev("dmoe.schedule", 90, 250),
            _ev("dmoe.des", 95, 100, nodes=7, fallback=3),
            _ev("dmoe.assign", 200, 220),
            _ev("dmoe.combine", 250, 260),
            _ev("dmoe.account", 260, 270),
            _ev("dmoe.unembed", 370, 380),
            _ev("dmoe.logits_d2h", 380, 400),
            _ev("dmoe.pass", 500, 900, **{"pass": 2}, routed_pairs=500),
            _ev("dmoe.mixer", 510, 570, kind="mla"),
            _ev("dmoe.mixer", 570, 580, kind="attention"),
            _ev("dmoe.expert_ffn", 580, 590, held=40, shared=2),
            _ev("dmoe.logits_d2h", 860, 900)]
    modules = NS(name="XLA Modules", events=[
        _ev("jit__ffn_step(12)", 60, 160), _ev("jit__combine_step(3)",
                                                 250, 260),
        _ev("jit__ffn_step(12)", 580, 700), _ev("jit__ffn_step(12)",
                                                 990, 1100)])
    ops = NS(name="XLA Ops", events=[_ev("%fusion.1 = bf16[4]", 60, 160)])
    device = NS(name="/device:TPU:0", lines=[ops, modules])
    planes = [NS(name="/host:CPU", lines=[NS(name="main", events=host)]),
              device]
    summary = program_spans.reduce_planes(planes)
    monkeypatch.setattr(program_spans, "summary", lambda: summary)

    def load(name):
        return common.load_module(ROOT / "bench" / "metrics" / f"{name}.py")

    obs = {"tokens_per_pass": 10, "sched_s": [0.5, 0.7],
           "required_flops": 4e12, "window_s": 2.0, "chips": 1,
           "peak_flops_bf16": 1e12, "trace": {"idle_share": 0.9},
           "ffn_step": {"flops": 197e12 * 1e-10, "bytes": 819e9 * 2e-10,
                        "per_pass": 1, "device_kind": "TPU v5 lite"}}
    # (300 + 500) / 2 pairs over 10 tokens x 40 held a pass
    assert load("expert_use.dsv2").read(obs) == pytest.approx(
        100 * 400 / (10 * 40))
    assert load("mla_ms.dsv2").reduce_planes(planes) == pytest.approx(
        (20 + 5 + 20 + 60) * 1e-6 / 2)
    ffn = load("ffn_ms.dsv2")
    # 100 + 120 + 10 (clipped at the window's end) over 2 passes
    assert ffn.reduce_planes(planes) == pytest.approx(230 * 1e-6 / 2)
    monkeypatch.setattr(ffn, "read", lambda obs: 115e-6)
    roof = load("ffn_roofline.dsv2")
    monkeypatch.setattr(roof, "_ffn_ms", ffn)
    # the bytes bound it: 2e-10 s of least time over 115e-9 s measured
    assert roof.read(obs) == pytest.approx(100 * 2e-10 / 115e-9)
    assert load("des_ms.dsv2").read(obs) == pytest.approx(5 * 1e-6 / 2)
    assert load("logits_d2h_ms.dsv2").read(obs) == pytest.approx(
        60 * 1e-6 / 2)
    assert load("sched_ms.dsv2").read(obs) == pytest.approx(600.0)
    assert load("mfu.dsv2").read(obs) == pytest.approx(200.0)
    assert load("idle_share.dsv2").read(obs) == pytest.approx(90.0)
    # the self time of embed, the mixers, dense FFN, expert FFNs, combine
    # and unembed: 10+20+5+20+10+10+10 and 60+10+10, over 2 passes
    assert load("dispatch_ms.dsv2").read(obs) == pytest.approx(
        165 * 1e-6 / 2)
    obs["des_nodes"] = [7, 9]
    for name in ("assign_ms", "des_nodes", "fallback_rows", "gate_wait_ms",
                 "host_other_ms", "idle_sched_ms", "idle_serve_ms"):
        got = load(f"{name}.dsv2").read(obs)
        assert got is not None and got == load(f"{name}.proto").read(obs), \
            name

    before = [_ev("bench.window", 0, 1000), _ev("bench.pass", 0, 450),
              _ev("dmoe.pass", 0, 400, **{"pass": 1}),
              _ev("dmoe.mixer", 10, 30, kind="attention")]
    planes = [NS(name="/host:CPU", lines=[NS(name="main", events=before)]),
              NS(name="/device:TPU:0", lines=[ops])]
    summary = program_spans.reduce_planes(planes)
    assert load("expert_use.dsv2").read(obs) is None
    assert load("mla_ms.dsv2").reduce_planes(planes) is None
    assert load("ffn_ms.dsv2").reduce_planes(planes) is None
    assert load("des_ms.dsv2").read(obs) is None
    assert load("gate_wait_ms.dsv2").read(obs) is None
    assert load("ffn_roofline.dsv2").read({}) is None
