"""Readings that the check's limits are set from, for one cell.

    python3 bench/calibrate.py --workload <name> --seeds 11 12 ... \
        --control-seeds 21 22 23 --seconds 5

In one process, each seed is one whole run of the cell through
`run.run_cell`: set-up, a window of `--seconds` at the cell's own load,
and the comparison that decides `correct`.  The program runs on
`--seeds`; on `--control-seeds` the control (the reference in the
precision below the configuration's) stands in the program's place and
has to come out not correct.  One JSON line per run on standard output.
The lower reading of a number is the largest the program gives, the
upper the smallest the control gives; `limits/<cell>.json` records both
beside the limit set between them.  It needs the chip the cell needs,
as every run does.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402
from run import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    runs = ([(s, False) for s in args.seeds]
            + [(s, True) for s in args.control_seeds])
    try:
        for seed, control in runs:
            result = run_cell(spec, args.workload, seed, args.seconds, False,
                              control=control)
            row = {"seed": seed, "control": control,
                   "correct": result["correct"],
                   "attempted": result["attempted"],
                   "device": result["device"]["kind"]}
            row.update({k: v["value"] for k, v in result["checks"].items()})
            print(json.dumps(row), flush=True)
            del result
            gc.collect()
    except common.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
