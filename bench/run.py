"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is an entry of `workloads` in
`BENCHMARK.json`; everything else is found by name under `bench/`:

    configs/<config>.json    sizes of the configuration as run, its source
    traffic/<traffic>.json   parameters of the traffic mix; `driver` names
    drivers/<driver>.py      the served entry that runs it
    metrics/<metric>.py      one reader per per-layer metric
    limits/<workload>.json   the limit of each number the check compares

Order of a run: device check (no TPU, or fewer chips than the cell asks
for, is an error: exit 2 and no result), compile cache, set-up (weights,
the program, warm-up of the cell's shapes), the measured window of
`--seconds` (traced with `--trace 1`), the peak memory, then the
program's state is freed and the reference checks the window's answers.
The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of that object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402
from common import BENCH, ROOT, BenchError  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def load_cell(spec: dict, workload: str) -> dict:
    """The cell's entries and data files, found by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = common.load_json(ROOT / configs[cell["config"]]["file"])
    traffic = common.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True,
             config: dict | None = None, limits: dict | None = None,
             control: bool = False) -> dict:
    """One run of one cell; returns the result object.  `config` and
    `limits` replace the cell's files (the CPU tests run smaller widths,
    and rehearse a driver that has no cell yet).  With `control`, the
    reference in the precision below the configuration's stands in the
    program's place for the comparison, which it has to fail."""
    c = load_cell(spec, workload)
    if config is not None:
        c["config"] = config
    chips = c["cell"]["chips"]
    device = common.device_record(chips, require_tpu=require_tpu)
    common.use_program()
    common.use_compile_cache()
    if limits is None:
        limits = common.load_json(BENCH / "limits" / f"{workload}.json")
    driver_mod = common.load_module(
        BENCH / "drivers" / f"{c['traffic']['driver']}.py")
    driver = driver_mod.Driver(c["cell"], c["config"], c["traffic"], seed)

    import jax

    compiles = common.CompileCounter()
    driver.setup()
    setup_s = time.perf_counter() - T_START

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1     # the benchmark's own spans
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    compiles.active = True
    try:
        out = driver.window(seconds)
    finally:
        compiles.active = False
        if trace:
            jax.profiler.stop_trace()
    compiles.close()
    device["memory_peak_bytes"] = common.memory_peak_bytes(chips)
    driver.release()

    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if trace:
        from trace_reduce import find_trace, reduce_trace

        summary = reduce_trace(find_trace(TRACE_DIR))
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        obs = dict(out["obs"], trace=summary, chips=chips,
                   peak_flops_bf16=common.peaks(device["kind"])[
                       "bf16_flops_per_s"])
        for m in c["per_layer"]:
            reader = common.load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        for m in c["end_to_end"]:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = out["metrics"][m["name"]]
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    readings = driver.readings(control=control)
    checks = {name: {"value": readings[name], "limit": limits[name]["limit"]}
              for name in limits}
    result["window_compiles"] = compiles.count
    result["correct"] = all(v["value"] <= v["limit"]
                            for v in checks.values())
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = common.load_json(ROOT / "BENCHMARK.json")
    try:
        result = run_cell(spec, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, v in result["checks"].items():
        print(f"check {name} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
