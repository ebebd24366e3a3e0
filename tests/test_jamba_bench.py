"""The benchmark's Jamba cell (`jamba2-mini.proto-mesh-n64`) at CPU
widths: a whole run of its driver (`bench/drivers/protocol_mesh.py`)
over 4 host devices through `run.run_cell`, planted faults that the
comparison with `bench/reference/jamba_ref.py` has to catch (one of
them in the exchange between devices), and the readers of its per-layer
metrics on hand-made trace planes."""

import copy
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import common  # noqa: E402
import program_spans  # noqa: E402

CELL = "jamba2-mini.proto-mesh-n64"
#: CPU widths: every width cut, the period, the 16 experts (K=16 nodes,
#: 4 per device), the heads' ratio and the bf16 types kept; dt's rank
#: stays d_model / 16, as published and as the program derives it.
CPU_WIDTHS = {"hidden_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 2, "intermediate_size": 128,
              "vocab_size": 256, "mamba_d_state": 8, "mamba_dt_rank": 8}
PROGRAM_KEYS = {"hidden_size": ["d_model"],
                "num_attention_heads": ["num_heads"],
                "num_key_value_heads": ["num_kv_heads"],
                "intermediate_size": ["d_ff", "moe_d_ff_expert"],
                "vocab_size": ["vocab_size"],
                "mamba_d_state": ["ssm_d_state"], "mamba_dt_rank": []}
#: The check's limits at CPU widths, where the bf16 program reads higher
#: than at the cell's (`bench/limits/<cell>.json` is set on the chip).
#: From `run.run_cell` over 12 seeds each on 4 host devices: the program
#: (seeds 3000015301-312) read at most logit_err 0.123 and gate_err
#: 0.0501, the float8 control (seeds 3000015401-412) at least 0.797 and
#: 0.284; each limit lies between, with room on both sides.
CPU_LIMITS = {"logit_err": {"limit": 0.3}, "gate_err": {"limit": 0.12},
              "sched_faults": {"limit": 0}}


def cpu_config(**program):
    spec = common.load_json(ROOT / "BENCHMARK.json")
    entry = {c["name"]: c for c in spec["configs"]}["jamba2-mini-p1"]
    config = common.load_json(ROOT / entry["file"])
    config.update(CPU_WIDTHS)
    config["overrides"] = dict(
        config["overrides"], **program,
        **{p: v for k, v in CPU_WIDTHS.items() for p in PROGRAM_KEYS[k]})
    return config


def one_chip_spec():
    """The benchmark with the cell on one chip: the faults need no mesh."""
    spec = copy.deepcopy(common.load_json(ROOT / "BENCHMARK.json"))
    for cell in spec["workloads"]:
        if cell["name"] == CELL:
            cell["chips"] = 1
    return spec


def _drop_dt_norm(monkeypatch):
    """A Mamba mixer that skips the RMSNorm on dt's low-rank input and
    keeps those on B and C."""
    from repro.models import ssm

    bcdt = ssm._mamba_bcdt

    def without(params, xc, cfg):
        b, c, dt = bcdt(params, xc, cfg.with_overrides(
            ssm_inner_norms=False))
        return (ssm.L.rmsnorm(b, params["b_norm"], cfg.norm_eps),
                ssm.L.rmsnorm(c, params["c_norm"], cfg.norm_eps), dt)

    monkeypatch.setattr(ssm, "_mamba_bcdt", without)


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


@pytest.mark.parametrize("fault", ["none", "rope", "dt_norm"])
def test_planted_fault_reads_not_correct(fault, monkeypatch,
                                         compile_cache_restored):
    """Rotary positions applied where Jamba has none, or Mamba's dt norm
    dropped, read `correct` false by the CPU widths' limits; the sound
    program reads it true."""
    import run as bench_run

    program = {"rope": True} if fault == "rope" else {}
    if fault == "dt_norm":
        _drop_dt_norm(monkeypatch)
    result = bench_run.run_cell(one_chip_spec(), CELL, 2**31 + 41, 0.5,
                                False, require_tpu=False,
                                config=cpu_config(**program),
                                limits=CPU_LIMITS)
    assert result["checks"]["sched_faults"]["value"] == 0
    assert result["correct"] is (fault == "none"), result["checks"]


_REHEARSAL = r"""
import json, sys
sys.path.insert(0, "bench")
sys.path.insert(0, "tests")
import common, run
from test_jamba_bench import CELL, CPU_LIMITS, cpu_config
if sys.argv[1] == "local_combine":
    # Step 5 without the exchange: each token weighs only the experts of
    # its own device, as if the other devices' sums never came back.
    import jax.numpy as jnp
    from repro.serving.dmoe_sim import DMoESimulator

    combine = DMoESimulator._combine_step

    def local_combine(self, x, ye, alpha, gates):
        per = alpha.shape[0] // self.chips
        home = jnp.arange(alpha.shape[0])[:, None, None] // per
        held = jnp.arange(alpha.shape[-1]) // per
        return combine(self, x, ye, alpha * (home == held), gates)

    DMoESimulator._combine_step = local_combine
spec = common.load_json(common.ROOT / "BENCHMARK.json")
result = run.run_cell(spec, CELL, 2**31 + 43, 1.0, False, require_tpu=False,
                      config=cpu_config(), limits=CPU_LIMITS)
print(json.dumps(result))
"""


def _rehearse(fault: str) -> dict:
    """One run of the cell over 4 host devices, in its own process."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in ("src", os.environ.get("PYTHONPATH", "")) if p))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run([sys.executable, "-c", _REHEARSAL, fault], env=env,
                          capture_output=True, text=True, cwd=ROOT,
                          timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_protocol_mesh_rehearsal():
    """A whole run of the cell over 4 host devices: the result line's
    keys, its end-to-end metrics, no compile inside the window, correct
    by the CPU widths' limits."""
    result = _rehearse("none")
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert result["correct"] is True, result["checks"]
    assert result["window_compiles"] == 0
    assert result["device"]["count"] == 4
    assert result["attempted"] > 0 and result["attempted"] % 16 == 0
    assert set(result["metrics"]) == {"tok_s", "lat_p95_ms", "setup_s"}
    assert set(result["checks"]) == {"logit_err", "gate_err", "sched_faults"}


def test_exchange_fault_on_four_devices_reads_not_correct():
    """The cross-device exchange planted away on the 4-device mesh (each
    token combines only the experts its own device holds) reads
    `correct` false; the schedule is untouched by it."""
    result = _rehearse("local_combine")
    assert result["device"]["count"] == 4
    assert result["checks"]["sched_faults"]["value"] == 0
    assert result["correct"] is False, result["checks"]
    assert (result["checks"]["logit_err"]["value"]
            > CPU_LIMITS["logit_err"]["limit"])


def _ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start),
              duration_ns=float(end - start), stats=list(stats.items()))


def test_readers_of_the_jamba_metrics(monkeypatch):
    """`mixer_ms.jamba` sums `dmoe.mixer` and `dmoe.dense_ffn` per pass,
    `xchip_mb.jamba` reads `xchip_bytes` on `dmoe.pass`, `xchip_ms.jamba`
    the collectives of the device planes; the readers it shares with the
    `.proto` cell read as there; without the spans (a program before
    them) each reads None."""
    host = [_ev("bench.window", 0, 1000),
            _ev("bench.pass", 0, 450), _ev("bench.pass", 500, 950),
            _ev("dmoe.pass", 0, 400, **{"pass": 1}, chips=4,
                xchip_bytes=3_000_000),
            _ev("dmoe.mixer", 10, 30, kind="mamba"),
            _ev("dmoe.dense_ffn", 30, 35),
            _ev("dmoe.round", 40, 300, **{"pass": 1, "layer": 1}),
            _ev("dmoe.mixer", 40, 60, kind="mamba"),
            _ev("dmoe.schedule", 90, 250),
            _ev("dmoe.des", 95, 100, nodes=7, fallback=3),
            _ev("dmoe.assign", 100, 200),
            _ev("dmoe.logits_d2h", 380, 400),
            _ev("dmoe.pass", 500, 900, **{"pass": 2}, chips=4,
                xchip_bytes=3_000_000),
            _ev("dmoe.mixer", 510, 570, kind="attention"),
            _ev("dmoe.logits_d2h", 860, 900)]

    def ops(*events):
        return NS(name="XLA Ops", events=list(events))

    devices = [
        NS(name="/device:TPU:0", lines=[ops(
            _ev("%fusion.1 = bf16[4] fusion(...)", 0, 90),
            _ev("%all-gather.5 = bf16[16,64,4096] all-gather(...)", 300, 310),
            _ev("%all-reduce-start.2 = f32[16] all-reduce-start(...)",
                305, 330),
            _ev("%all-reduce-done.2 = f32[16] all-reduce-done(...)", 330, 340),
            _ev("%fusion.9 = f32[4] fusion(...)", 340, 380))]),
        NS(name="/device:TPU:1", lines=[ops(
            _ev("%all-gather.5 = bf16[16,64,4096] all-gather(...)", 990,
                1100))])]
    planes = [NS(name="/host:CPU", lines=[NS(name="main", events=host)])]
    summary = program_spans.reduce_planes(planes + devices)
    monkeypatch.setattr(program_spans, "summary", lambda: summary)

    def read(name, obs=None):
        path = ROOT / "bench" / "metrics" / f"{name}.py"
        return common.load_module(path).read(obs or {})

    assert read("mixer_ms.jamba") == pytest.approx((20 + 5 + 20 + 60)
                                                   * 1e-6 / 2)
    assert read("xchip_mb.jamba") == pytest.approx(3.0)
    assert read("assign_ms.jamba") == pytest.approx(100 * 1e-6 / 2)
    assert read("des_ms.jamba") == pytest.approx(5 * 1e-6 / 2)
    assert read("fallback_rows.jamba") == pytest.approx(1.5)
    assert read("logits_d2h_ms.jamba") == pytest.approx(60 * 1e-6 / 2)
    # the first device idles 90-300 and 380-1000: 160 ns of it under
    # `dmoe.schedule` (90-250), the rest of the two passes outside it
    assert read("idle_sched_ms.jamba") == pytest.approx(160 * 1e-6 / 2)
    assert read("idle_serve_ms.jamba") == pytest.approx(
        (50 + 20 + 400) * 1e-6 / 2)
    xchip = common.load_module(ROOT / "bench" / "metrics" / "xchip_ms.jamba.py")
    # chip 0: the union 300-340; chip 1: 990-1000 inside the window
    assert xchip.reduce_planes(planes + devices) == pytest.approx(
        (40 + 10) / 2 * 1e-6 / 2)
    assert xchip.reduce_planes(planes) is None
    assert xchip.reduce_planes(planes + devices[1:2] + [NS(
        name="/device:TPU:2", lines=[ops(_ev("%fusion.1 = f32[4]", 0, 9))])]
                               ) == pytest.approx(10 / 2 * 1e-6 / 2)
    obs = {"sched_s": [0.5, 0.7], "des_nodes": [10, 30],
           "required_flops": 4e12, "window_s": 2.0, "chips": 4,
           "peak_flops_bf16": 1e12, "trace": {"idle_share": 0.9}}
    assert read("sched_ms.jamba", obs) == pytest.approx(600.0)
    assert read("des_nodes.jamba", obs) == 20
    assert read("mfu.jamba", obs) == pytest.approx(50.0)
    assert read("idle_share.jamba", obs) == pytest.approx(90.0)

    before = [_ev("bench.window", 0, 1000),
              _ev("dmoe.pass", 0, 400, **{"pass": 1})]
    summary = program_spans.reduce_planes(
        [NS(name="/host:CPU", lines=[NS(name="main", events=before)])])
    assert read("mixer_ms.jamba") is None
    assert read("xchip_mb.jamba") is None
    assert read("des_ms.jamba") is None
    assert read("logits_d2h_ms.jamba") is None
    assert read("fallback_rows.jamba") is None
    assert read("idle_sched_ms.jamba") is None
