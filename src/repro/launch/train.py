"""Trainer: synthetic-data training loop with checkpointing and DES/topk
routing.  CPU-runnable at smoke scale; the same step function lowers to
the production mesh in dryrun.py.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b --smoke \
      --steps 200 --batch 8 --seq 128 [--routing des] [--ckpt-dir /tmp/ck]
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (get_config, get_smoke_config,
                                resolve_routing_policy)
from repro.data import DataConfig, lm_batch
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as model_lib
from repro.optim import AdamWConfig, init_opt_state
from repro import checkpoint as ckpt_lib


def train(arch: str, *, smoke: bool = True, steps: int = 100,
          batch: int = 8, seq: int = 128, lr: float = 3e-4,
          routing: str = None, ckpt_dir: str = None, ckpt_every: int = 100,
          log_every: int = 10, seed: int = 0, resume: bool = False):
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if routing:
        cfg = cfg.with_overrides(moe_routing=routing)
    if cfg.enc_dec:
        raise SystemExit("use serve.py for the enc-dec arch (audio)")

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                          global_batch=batch, seed=seed)
    opt_cfg = AdamWConfig(lr=lr, total_steps=steps,
                          warmup_steps=max(steps // 20, 5))

    # the routing policy supplies its in-graph cost vector (None for
    # policies that route on gate scores alone)
    expert_costs = None
    if cfg.moe.num_experts:
        expert_costs = resolve_routing_policy(cfg).in_graph_costs(
            cfg.moe.num_experts)

    params = model_lib.init_params(jax.random.PRNGKey(seed), cfg)
    opt_state = init_opt_state(params, opt_cfg)
    start_step = 0
    if resume and ckpt_dir and ckpt_lib.latest_step(ckpt_dir) is not None:
        (params, opt_state), meta = ckpt_lib.restore(
            ckpt_dir, (params, opt_state))
        start_step = meta["step"]
        print(f"resumed from step {start_step}")

    step_fn = jax.jit(steps_lib.make_train_step(
        cfg, opt_cfg, expert_costs=expert_costs))

    history = []
    t0 = time.time()
    for step in range(start_step, steps):
        b = lm_batch(data_cfg, step)
        batch_j = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch_j)
        if step % log_every == 0 or step == steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            history.append({"step": step, **m})
            print(f"step {step:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
                  f"lr {m['lr']:.2e} gnorm {m['grad_norm']:.2f} "
                  f"({time.time()-t0:.0f}s)")
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            ckpt_lib.save(ckpt_dir, step + 1, (params, opt_state),
                          metadata={"arch": cfg.name})
    if ckpt_dir:
        ckpt_lib.save(ckpt_dir, steps, (params, opt_state),
                      metadata={"arch": cfg.name})
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--routing", default=None,
                    choices=[None, "topk", "des", "des-greedy", "dense"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    _, history = train(
        args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
        seq=args.seq, lr=args.lr, routing=args.routing,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume, seed=args.seed)
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    main()
