"""Smoke run of the served path on a TPU, at Mixtral-8x7B widths.

Run from the root of a checkout:

    python chip_smoke.py             # one chip: protocol round + engine
    python chip_smoke.py --chips 4   # four chips: sharded DES sweep only

One chip, phase after phase in this one process (it starts no children):

1. Device check.  A default JAX backend other than TPU is an error.
2. DMoE protocol path.  `DMoESimulator(cfg, scheme="jesa")` serves a few
   seeded (8, 64) token batches: logits finite with shape (8, 64, 32000),
   every round's alpha within the C2 budget, energies finite.  Every
   round's gate scores and rates are then replayed through "sharded-des"
   and "async-des", whose schedules must be bit-identical to jesa's
   (alpha, beta, energy, B&B node count): the device pre-work runs in
   float64, which the TPU emulates.
3. Serving engine.  `ServingEngine(cfg)` prefills 4 seeded requests and
   decodes 16 tokens each through the KV cache with the config's
   in-graph des-greedy routing; then the same requests with
   ``routing_impl="fused"``, whose prefill must hold Pallas TPU kernels
   and agree with the "xla" prefill logits within the bf16 tolerance of
   tests/test_moe_route.py.
4. Report: compile and steady times and peak device memory per phase.

Four chips run only the K=8, N=256, 3-layer JESA alpha-step sweep of
`benchmarks.des_complexity` over a 4-device batch mesh, through the
sharded and the async tiers, each compared bit for bit with the host
`des_select_batch`.

`cfg` is `get_config("mixtral-8x7b")` at its published widths with only
`num_layers` cut, from 32 to 2: about 6.3 GB of bf16 weights (32 layers
would be about 93 GB).  Weights are random, from `--seed`.  Times are
those of a smoke run, not benchmark numbers.  Any failed check raises;
the last line of standard output,
``{"ok": true, "device": {"platform", "kind", "count"}}``, is printed
only when every phase passed.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "mixtral-8x7b"
NUM_LAYERS = 2
SIM_TOKENS = (8, 64)              # (K nodes, N tokens per query)
SIM_CALLS = 3
N_REQUESTS, PROMPT_MAX, NEW_TOKENS = 4, 128, 16
#: `tests/test_moe_route.py::_tol(jnp.bfloat16)`
BF16_TOL = dict(atol=5e-2, rtol=5e-2)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's default backend is {dev.platform!r}")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


# ----------------------------------------------------------------------
# phase 2: the DMoE protocol round
# ----------------------------------------------------------------------

def same_schedule(a, b) -> bool:
    return (np.array_equal(a.alpha, b.alpha) and np.array_equal(a.beta, b.beta)
            and a.energy == b.energy and a.des_nodes == b.des_nodes)


def protocol_phase(cfg, seed: int) -> None:
    from repro.schedulers import SchedulerPolicy, get_policy
    from repro.serving import DMoESimulator

    class Recorder(SchedulerPolicy):
        """jesa, keeping a copy of every round's context for replay."""

        def __init__(self):
            self.inner = get_policy("jesa")
            self.name = self.inner.name
            self.contexts = []

        def schedule(self, ctx):
            self.contexts.append(copy.deepcopy(ctx))
            return self.inner.schedule(ctx)

    recorder = Recorder()
    t0 = time.perf_counter()
    sim = DMoESimulator(cfg, policy=recorder, seed=seed)
    say(f"protocol: init_s={time.perf_counter() - t0:.3f} {memory()}")
    rng = np.random.default_rng(seed)
    budget = cfg.moe.max_experts or cfg.moe.top_k
    schedules, times = [], []
    for _ in range(SIM_CALLS):
        tokens = rng.integers(0, cfg.vocab_size, SIM_TOKENS, dtype=np.int32)
        t0 = time.perf_counter()
        res = sim.serve(tokens)          # logits come back as host numpy
        times.append(time.perf_counter() - t0)
        check(res.logits.shape == (*SIM_TOKENS, cfg.vocab_size),
              f"logits shape {res.logits.shape}")
        check(bool(np.isfinite(res.logits).all()), "non-finite logits")
        for rs in res.schedules:
            check(int(rs.alpha.sum(axis=-1).max()) <= budget,
                  f"layer {rs.layer}: more than D={budget} experts a token")
            check(bool(np.isfinite(rs.energy)), f"layer {rs.layer} energy")
        check(bool(np.isfinite(res.summary["total_energy_j"])),
              "round accounting energy")
        schedules.extend(res.schedules)
    say(f"protocol: first_serve_s={times[0]:.3f} (compiles included) "
        f"steady_serve_s={np.mean(times[1:]):.3f} over {SIM_CALLS - 1} "
        f"calls of {SIM_TOKENS} tokens, {NUM_LAYERS} rounds each "
        f"(host scheduler included) {memory()}")
    del sim
    gc.collect()

    for name in ("sharded-des", "async-des"):
        policy = get_policy(name)
        t0 = time.perf_counter()
        try:
            for ctx, ref in zip(recorder.contexts, schedules):
                got = policy.schedule(copy.deepcopy(ctx))
                check(same_schedule(got, ref),
                      f"{name} layer {ref.layer}: schedule differs from "
                      f"jesa on this device")
        finally:
            getattr(policy, "close", lambda: None)()
        say(f"protocol: {name} bit-identical to jesa on "
            f"{len(schedules)} rounds ({time.perf_counter() - t0:.3f} s)")


# ----------------------------------------------------------------------
# phase 3: the serving engine
# ----------------------------------------------------------------------

def make_requests(cfg, seed: int):
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    lens = [PROMPT_MAX, *rng.integers(PROMPT_MAX // 4, PROMPT_MAX,
                                      N_REQUESTS - 1)]
    return [Request(uid=i, max_new_tokens=NEW_TOKENS,
                    prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32))
            for i, n in enumerate(lens)]


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def engine_phase(cfg, seed: int, routing_impl: str) -> np.ndarray:
    """Prefill + cached decode of the seeded requests; returns the
    prefill's last-position logits (host float32)."""
    from repro.serving import ServingEngine

    t0 = time.perf_counter()
    engine = ServingEngine(cfg, max_batch=N_REQUESTS, seed=seed,
                           routing_impl=routing_impl)
    tag = f"engine[{routing_impl}]"
    say(f"{tag}: init_s={time.perf_counter() - t0:.3f}")
    (logits, _), first_prefill = timed(lambda: engine.prefill(
        make_requests(cfg, seed)))
    (logits, _), prefill = timed(lambda: engine.prefill(
        make_requests(cfg, seed)))
    logits = np.asarray(logits, dtype=np.float32)
    check(logits.shape == (N_REQUESTS, cfg.vocab_size),
          f"{tag}: prefill logits shape {logits.shape}")
    check(bool(np.isfinite(logits).all()), f"{tag}: non-finite logits")
    if routing_impl == "fused":
        reqs = make_requests(cfg, seed)
        # the prefill program the engine runs, lowered for this device
        batch, caches = engine.prefill_inputs(reqs)
        hlo = engine._prefill.lower(engine.params, batch, caches).as_text()
        check("tpu_custom_call" in hlo,
              f"{tag}: prefill holds no Pallas TPU kernel")

    outputs = []
    serve_s = []
    for _ in range(2):                   # first call compiles the decode
        reqs = make_requests(cfg, seed)
        t0 = time.perf_counter()
        engine.serve(reqs)               # ends on host copies of tokens
        serve_s.append(time.perf_counter() - t0)
        for r in reqs:
            check(r.output is not None and r.output.shape == (NEW_TOKENS,),
                  f"{tag}: request {r.uid} output")
            check(bool(((r.output >= 0) & (r.output < cfg.vocab_size)).all()),
                  f"{tag}: request {r.uid} token out of vocabulary")
        outputs.append(np.stack([r.output for r in reqs]))
    check(np.array_equal(outputs[0], outputs[1]),
          f"{tag}: greedy outputs differ between two identical calls")
    say(f"{tag}: first_prefill_s={first_prefill:.3f} (compile included) "
        f"prefill_s={prefill:.4f} first_serve_s={serve_s[0]:.3f} "
        f"serve_s={serve_s[1]:.3f} decode_step_s="
        f"{(serve_s[1] - prefill) / NEW_TOKENS:.4f} (derived: serve minus "
        f"prefill over {NEW_TOKENS} steps; {N_REQUESTS} requests, prompts "
        f"<= {PROMPT_MAX}) {memory()}")
    del engine
    gc.collect()
    return logits


# ----------------------------------------------------------------------
# four chips: the sharded scheduler sweep
# ----------------------------------------------------------------------

def sharded_sweep_phase(chips: int) -> None:
    from benchmarks.des_complexity import run_async_sweep, run_sharded_sweep

    for run in (run_sharded_sweep, run_async_sweep):
        t0 = time.perf_counter()
        res = run(k=8, n_tokens=256, num_layers=3, reps=1, verbose=True)
        check(res["bit_identical"],
              f"{run.__name__}: differs from des_select_batch")
        devices = res["n_devices"]
        check(devices == chips,
              f"{run.__name__}: ran on {devices} devices, not {chips}")
        say(f"sweep: {run.__name__} over {devices} devices bit-identical "
            f"to des_select_batch ({time.perf_counter() - t0:.3f} s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SmokeFailure(f"{ROOT} is not a checkout of this repository")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from repro.launch.compile_cache import use_compile_cache

    device = device_info(args.chips)
    say(f"device: {device['kind']} x{device['count']}; compile cache: "
        f"{use_compile_cache()}; times below are a smoke run, not a "
        f"benchmark")
    if args.chips == 4:
        sharded_sweep_phase(args.chips)
    else:
        from repro.configs.base import get_config

        cfg = get_config(ARCH).with_overrides(num_layers=NUM_LAYERS)
        say(f"config: {ARCH} d_model={cfg.d_model} heads={cfg.num_heads}/"
            f"{cfg.num_kv_heads} d_ff_expert={cfg.moe.d_ff_expert} "
            f"experts={cfg.moe.num_experts} vocab={cfg.vocab_size} "
            f"{cfg.param_dtype}; num_layers cut 32 -> {NUM_LAYERS}")
        protocol_phase(cfg, args.seed)
        ref = engine_phase(cfg, args.seed, "xla")
        fused = engine_phase(cfg, args.seed, "fused")
        err = float(np.max(np.abs(fused - ref)))
        check(bool(np.allclose(fused, ref, **BF16_TOL)),
              f"fused prefill logits differ from xla: max abs {err}")
        say(f"engine: fused prefill logits match xla (max abs diff {err})")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
