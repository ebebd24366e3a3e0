"""CPU rehearsal of the benchmark's drivers at small widths: what a run
prints, that a measurement run refuses the CPU and a directory without
the program, and that nothing compiles inside the window."""

from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

import smoke

CELLS = [w["name"] for w in smoke.spec()["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _bench_cmd(root, workload):
    return [sys.executable, str(root / "bench" / "run.py"), "--workload",
            workload, "--seed", str(2**31 + 3), "--seconds", "1",
            "--trace", "0"]


@pytest.mark.parametrize("cell", CELLS)
def test_result_line(cell):
    spec = smoke.spec()
    result = smoke.run(cell, seed=2**31 + 5, seconds=1.0)
    assert list(result)[: len(KEYS)] == KEYS
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["window_compiles"] == 0
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert json.loads(json.dumps(result)) == result


def test_measurement_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(_bench_cmd(smoke.common.ROOT, CELLS[0]), env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_refuses_a_directory_without_the_program(tmp_path):
    root = smoke.common.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(_bench_cmd(tmp_path, CELLS[0]), env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_engine_driver_rehearsal():
    """The engine driver, which has no cell yet, through a whole run."""
    import run as bench_run

    spec = copy.deepcopy(smoke.spec())
    name = "mixtral-8x7b.engine-b8"
    spec["workloads"].append({"name": name, "config": "mixtral-8x7b-l2",
                              "traffic": "engine-b8", "chips": 1})
    result = bench_run.run_cell(
        spec, name, 2**31 + 7, 1.0, False, require_tpu=False,
        config=smoke.smoke_config("mixtral-8x7b.proto-n64"),
        limits={"served_gap": {"limit": float("inf")}})
    assert result["window_compiles"] == 0
    assert set(result["metrics"]) == {"tok_s", "lat_p95_ms", "setup_s"}
    assert result["attempted"] % 8 == 0 and result["attempted"] > 0
    assert 0 <= result["checks"]["served_gap"]["value"] < float("inf")
