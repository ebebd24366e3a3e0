import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production mesh; record memory/cost analysis + roofline terms.

The two lines above MUST run before any other import — jax locks the
device count at first initialization.  Do not set this flag globally:
smoke tests and benchmarks must see 1 real device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes
"""

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import (INPUT_SHAPES, ModelConfig, all_arch_names,
                                get_config)
from repro.distributed import sharding as sh
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rl
from repro.launch import steps
from repro.models import model as model_lib
from repro.optim import AdamWConfig, OptState, init_opt_state, apply_updates

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# (arch, shape) pairs that are skipped BY DESIGN (see DESIGN.md §5)
SKIPS = {
    ("whisper-base", "long_500k"):
        "whisper decoder is architecturally capped at 448 text positions; "
        "a 500k-token decode has no semantic meaning (DESIGN.md §5)",
}


def window_for(cfg: ModelConfig, shape_name: str) -> int:
    """Sub-quadratic policy: long_500k uses sliding-window attention for
    every arch that has attention layers (SSM archs need none)."""
    if shape_name == "long_500k":
        return cfg.sliding_window or 8192
    return 0


def dtype_policy(cfg: ModelConfig, shape_name: str) -> ModelConfig:
    """bf16 params/activations for dry-runs; bf16 optimizer moments for the
    >300B archs (noted in EXPERIMENTS.md)."""
    return cfg


def moments_dtype(cfg: ModelConfig) -> str:
    big = cfg.name.startswith(("deepseek-v3", "jamba-1.5-large"))
    return "bfloat16" if big else "float32"



def _rule_overrides(opts):
    """opts["expert_axes"]="ep_all" -> pure expert parallelism: the expert
    axis sharded over (data x model) = every chip owns E/256 experts;
    no weight gathering, tokens move via all-to-all instead."""
    if opts.get("expert_axes") == "ep_all":
        return {r"ffn/(w1|wu|w2)$": (("data", "model"), None, None)}
    return None

def build_train(cfg, shape, mesh, opts=None):
    opts = opts or {}
    opt_cfg = AdamWConfig(moment_dtype=moments_dtype(cfg))
    n_micro, grad_dtype = steps.microbatch_plan(cfg)
    n_micro = int(opts.get("n_micro", n_micro))

    params_shape = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    opt_shape = jax.eval_shape(lambda: init_opt_state(params_shape, opt_cfg))
    batch_sds = model_lib.input_specs(cfg, shape.global_batch, shape.seq_len,
                                      "train")

    fsdp = int(opts.get("fsdp_bytes", 32 * 1024 * 1024))
    ro = _rule_overrides(opts)
    pspec = sh.param_specs(mesh, params_shape, fsdp_bytes=fsdp,
                           rule_overrides=ro)
    ospec = sh.param_specs(mesh, opt_shape, fsdp_bytes=fsdp,
                           rule_overrides=ro)
    bspec = sh.batch_specs(mesh, batch_sds)

    # microbatch-loop sharding constraints (see steps.make_train_step):
    # without them GSPMD replicates the whole microbatch per device.
    from jax.sharding import PartitionSpec as P
    mb_shardings = None
    if n_micro > 1 and not bool(opts.get("no_mb_constraint", False)):
        mb_shardings = sh.named(mesh, jax.tree.map(
            lambda spec: P(None, *tuple(spec)), bspec,
            is_leaf=lambda x: isinstance(x, P)))
    grad_shardings = None
    if n_micro > 1 and not bool(opts.get("no_grad_constraint", False)):
        grad_shardings = sh.named(mesh, pspec)
    train_step = steps.make_train_step(
        cfg, opt_cfg, n_micro=n_micro, grad_dtype=grad_dtype,
        microbatch_shardings=mb_shardings, grad_shardings=grad_shardings)

    in_sh = (sh.named(mesh, pspec), sh.named(mesh, ospec),
             sh.named(mesh, bspec))
    out_sh = (in_sh[0], in_sh[1], None)
    args = (params_shape, opt_shape, batch_sds)
    tokens = shape.global_batch * shape.seq_len
    mf = rl.model_flops(params_shape, cfg, tokens=tokens, kind="train")
    return train_step, args, in_sh, out_sh, mf, params_shape


def build_prefill(cfg, shape, mesh, opts=None):
    opts = opts or {}
    model = model_lib.Model(cfg)
    win = window_for(cfg, shape.name)

    def prefill_step(params, batch, caches):
        return model_lib.prefill(params, batch, cfg, caches, window=win)

    params_shape = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    batch_sds = model_lib.input_specs(cfg, shape.global_batch, shape.seq_len,
                                      "prefill")
    cache_shape = jax.eval_shape(
        lambda: model_lib.init_caches(cfg, shape.global_batch, shape.seq_len))
    fsdp = int(opts.get("fsdp_bytes", 32 * 1024 * 1024))
    pspec = sh.param_specs(mesh, params_shape, fsdp_bytes=fsdp,
                           rule_overrides=_rule_overrides(opts))
    bspec = sh.batch_specs(mesh, batch_sds)
    cspec = sh.cache_specs(mesh, cache_shape, batch=shape.global_batch,
                           seq_on_model=bool(opts.get("seq_on_model", True)))
    in_sh = (sh.named(mesh, pspec), sh.named(mesh, bspec),
             sh.named(mesh, cspec))
    out_sh = (None, in_sh[2])
    args = (params_shape, batch_sds, cache_shape)
    tokens = shape.global_batch * shape.seq_len
    mf = rl.model_flops(params_shape, cfg, tokens=tokens, kind="prefill")
    return prefill_step, args, in_sh, out_sh, mf, params_shape


def build_decode(cfg, shape, mesh, opts=None):
    opts = opts or {}
    # decode default: expert-resident layout (no per-step weight gathers)
    # whenever the expert count divides the whole mesh — §Perf B: 16.8x
    # on the collective term for deepseek-v3.
    if ("expert_axes" not in opts and cfg.moe.num_experts
            and cfg.moe.num_experts % mesh.size == 0):
        opts = {**opts, "expert_axes": "ep_all"}
    model = model_lib.Model(cfg)
    win = window_for(cfg, shape.name)

    def serve_step(params, token, caches):
        return model_lib.decode_step(params, token, caches, cfg, window=win)

    params_shape = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    token_sds = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
    cache_shape = jax.eval_shape(
        lambda: model_lib.init_caches(cfg, shape.global_batch, shape.seq_len))
    fsdp = int(opts.get("fsdp_bytes", 32 * 1024 * 1024))
    pspec = sh.param_specs(mesh, params_shape, fsdp_bytes=fsdp,
                           rule_overrides=_rule_overrides(opts))
    cspec = sh.cache_specs(mesh, cache_shape, batch=shape.global_batch,
                           seq_on_model=bool(opts.get("seq_on_model", True)))
    tspec = sh.batch_specs(mesh, token_sds)
    in_sh = (sh.named(mesh, pspec), sh.named(mesh, tspec),
             sh.named(mesh, cspec))
    out_sh = (None, in_sh[2])
    args = (params_shape, token_sds, cache_shape)
    tokens = shape.global_batch  # one token per sequence
    mf = rl.model_flops(params_shape, cfg, tokens=tokens, kind="decode")
    return serve_step, args, in_sh, out_sh, mf, params_shape


BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            save: bool = True, verbose: bool = True,
            overrides: dict = None, variant: str = "") -> dict:
    """overrides: cfg fields (passed to cfg.with_overrides) plus the
    launcher knobs n_micro / fsdp_bytes / seq_on_model.  `variant` tags
    the artifact filename so hillclimb runs don't clobber baselines."""
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if (arch, shape_name) in SKIPS:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "status": "skipped", "reason": SKIPS[(arch, shape_name)]}
        if save:
            _save(result)
        if verbose:
            print(f"[SKIP] {arch} x {shape_name}: {result['reason']}")
        return result

    cfg = get_config(arch)
    opts = dict(overrides or {})
    launcher_keys = {"n_micro", "fsdp_bytes", "seq_on_model", "expert_axes",
                     "no_act_constraint", "no_mb_constraint",
                     "no_grad_constraint"}
    cfg_over = {k: v for k, v in opts.items() if k not in launcher_keys}
    if cfg_over:
        cfg = cfg.with_overrides(**cfg_over)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh, mf, params_shape = BUILDERS[shape.kind](
            cfg, shape, mesh, opts)
        act_mesh = None if opts.get("no_act_constraint") else mesh
        with mesh, sh.activation_mesh(act_mesh):
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            memstats = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        roof = rl.analyze(
            arch=arch, shape=shape_name, mesh_name=mesh_name,
            n_devices=mesh.size,
            device_kind=mesh_lib.PRODUCTION_DEVICE_KIND, cost=cost,
            memstats=memstats,
            hlo_text=hlo, model_flops=mf)
        from repro.launch import hlo_cost
        hc = hlo_cost.analyze_hlo(hlo)
        kinds = hc.collective_ops
        coll_ops = []
        result = {
            "status": "ok",
            "variant": variant,
            **roof.to_dict(),
            "xla_flops_raw": float(cost.get("flops", 0.0)),
            "xla_bytes_raw": float(cost.get("bytes accessed", 0.0)),
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "collective_op_counts": kinds,
            "n_collective_ops": len(coll_ops),
            "memory_analysis": {
                "argument_size_in_bytes": roof.arg_bytes,
                "temp_size_in_bytes": roof.temp_bytes,
                "output_size_in_bytes": int(getattr(
                    memstats, "output_size_in_bytes", 0) or 0),
            },
            "fits_hbm": (roof.arg_bytes + roof.temp_bytes)
            <= mesh_lib.chip_peaks(
                mesh_lib.PRODUCTION_DEVICE_KIND).hbm_bytes,
        }
        if verbose:
            print(f"[OK] {arch} x {shape_name} @ {mesh_name}: "
                  f"args {roof.arg_bytes/1e9:.2f} GB + temp "
                  f"{roof.temp_bytes/1e9:.2f} GB / device; "
                  f"flops/dev {roof.hlo_flops:.3e}; "
                  f"bottleneck {roof.bottleneck}; "
                  f"compile {t_compile:.0f}s")
    except Exception as e:  # noqa: BLE001 — record the failure
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "status": "error", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
        if verbose:
            print(f"[FAIL] {arch} x {shape_name} @ {mesh_name}: "
                  f"{type(e).__name__}: {str(e)[:300]}")
    if save:
        _save(result)
    return result


def _save(result: dict):
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    v = result.get("variant", "")
    suffix = f"_{v}" if v else ""
    name = f"{result['arch']}_{result['shape']}_{result['mesh']}{suffix}.json"
    name = name.replace("/", "_")
    (ARTIFACTS / name).write_text(json.dumps(result, indent=2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(all_arch_names())
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    if not (args.all or args.arch):
        ap.error("pass --arch or --all")

    failures = 0
    for mp in meshes:
        for arch in archs:
            for shp in shapes:
                r = run_one(arch, shp, multi_pod=mp)
                if r["status"] == "error":
                    failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
