"""Serving launcher: batched requests through the ServingEngine, or the
full DMoE edge protocol via --edge.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
      --requests 16 --new-tokens 8
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
      --edge --scheme jesa
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.configs.base import get_config, get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.schedulers import available_policies
from repro.serving import DMoESimulator, Request, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--edge", action="store_true",
                    help="run the DMoE wireless-edge protocol simulator")
    ap.add_argument("--scheme", default="jesa",
                    choices=list(available_policies()))
    ap.add_argument("--tokens-per-query", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = np.random.default_rng(args.seed)

    if args.edge:
        if not cfg.moe.num_experts:
            raise SystemExit("--edge needs a MoE arch (expert nodes)")
        sim = DMoESimulator(cfg, scheme=args.scheme, seed=args.seed)
        tokens = rng.integers(0, cfg.vocab_size,
                              size=(cfg.moe.num_experts,
                                    args.tokens_per_query))
        res = sim.serve(tokens)
        s = res.summary
        print(f"scheme={args.scheme} layers={s['layers']} "
              f"E_comm={s['comm_energy_j']:.4e} J "
              f"E_comp={s['comp_energy_j']:.4e} J "
              f"E/token={s['energy_per_token_j']:.4e} J "
              f"mean_selected={s['mean_selected']:.2f}")
        return

    engine = ServingEngine(cfg, max_batch=args.max_batch,
                           max_len=args.prompt_len + args.new_tokens + 8,
                           seed=args.seed)
    reqs = [
        Request(uid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    size=rng.integers(
                                        4, args.prompt_len + 1)).astype(
                                            np.int32),
                max_new_tokens=args.new_tokens)
        for i in range(args.requests)
    ]
    stats = engine.serve(reqs)
    done = sum(r.output is not None for r in reqs)
    print(f"served {done}/{len(reqs)} requests in {stats.batches} batches; "
          f"prefill {stats.prefill_tokens} tok, decode "
          f"{stats.decode_tokens} tok, {stats.decode_tok_per_s:.1f} tok/s")


if __name__ == "__main__":
    main()
