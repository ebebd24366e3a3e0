"""Small widths of the benchmark's configurations for CPU runs, the way
into `bench/run.py` without its look for a chip, and a schedule fault
that only the optimality check can see."""

from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import common  # noqa: E402
import numpy as np  # noqa: E402

#: Widths cut for the CPU; depth, experts per layer, heads' ratio and
#: the bf16 types stay as the cell runs them.
SMOKE = {"hidden_size": 256, "num_attention_heads": 8,
         "num_key_value_heads": 2, "intermediate_size": 256,
         "vocab_size": 512}
PROGRAM_KEYS = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
                "num_key_value_heads": "num_kv_heads",
                "intermediate_size": "moe_d_ff_expert",
                "vocab_size": "vocab_size"}


def spec() -> dict:
    return common.load_json(common.ROOT / "BENCHMARK.json")


def smoke_config(workload: str) -> dict:
    s = spec()
    cell = {w["name"]: w for w in s["workloads"]}[workload]
    entry = {c["name"]: c for c in s["configs"]}[cell["config"]]
    config = common.load_json(common.ROOT / entry["file"])
    config.update(SMOKE)
    config["overrides"] = dict(
        config["overrides"], **{PROGRAM_KEYS[k]: v for k, v in SMOKE.items()})
    return config


def run(workload: str, seed: int, seconds: float = 1.0, trace: bool = False,
        control: bool = False):
    import run as bench_run

    return bench_run.run_cell(spec(), workload, seed, seconds, trace,
                              require_tpu=False,
                              config=smoke_config(workload), control=control)


def costlier_selection(ctx, alpha, beta):
    """`alpha` with one token of source 0 moved to the subset of at most
    D experts that meets the QoS at the highest finite cost under beta's
    prices: C1 and C2 still hold, but the selection is not P1's optimum.
    None where no token of source 0 has such a subset (under a QoS that
    no D experts meet, every token takes Remark 2's fallback)."""
    from reference import schedule_ref as ref

    costs = ref.selection_costs(ref.link_rates(ctx.rates, beta), beta,
                                np.asarray(ctx.comp_coeff), ctx.s0,
                                ctx.p0)[0]
    sub = ref._subsets(costs.size, ctx.max_experts)
    sub_cost = np.where(sub > 0, costs, 0.0).sum(-1)
    for n in range(alpha.shape[1]):
        g = ctx.gate_scores[0, n]
        ok = (sub @ g >= ctx.qos) & np.isfinite(sub_cost)
        if not ok.any():
            continue
        worst = int(np.argmax(np.where(ok, sub_cost, -np.inf)))
        chosen = np.where(alpha[0, n] > 0, costs, 0.0).sum()
        if sub_cost[worst] > chosen * (1 + 1e-6):
            alpha = alpha.copy()
            alpha[0, n] = sub[worst].astype(alpha.dtype)
            return alpha
    return None
