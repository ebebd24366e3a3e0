"""Pieces every driver of the benchmark shares: where the checkout is,
loading the data files by name, the compile cache, the device record,
the peak table, percentiles and a count of compiles.

Nothing here imports the program under test.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: JAX's own variable for the persistent compilation cache directory.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: The cache used where that variable is unset: a fixed directory in
#: the checkout (its path is part of every entry's key).
CHECKOUT_CACHE = ROOT / ".jax_cache"

#: Lowering a jaxpr to MLIR happens once per compile request, whether
#: the persistent cache then hits or misses.
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark (a driver, a metric reader, a
    count of operations) by its path; its name may hold dots."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path.relative_to(ROOT)}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_program() -> None:
    """Put the program's package on the import path, or fail: a
    directory with only the benchmark's files has no system to run."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"{ROOT} holds no program (src/repro is missing)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache: the directory that
    `JAX_COMPILATION_CACHE_DIR` names, else `.jax_cache/` in the
    checkout.  Every program is cached, however fast it compiled, so
    that a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_record(chips: int, *, require_tpu: bool = True) -> dict:
    """Platform, kind and count of the devices JAX sees.  A backend other
    than TPU, or fewer chips than the cell asks for, is an error."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX's default backend is {dev.platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first `chips` devices
    (0 where the backend keeps no statistics)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def peaks(device_kind: str) -> dict:
    """The peak rates of one chip of `device_kind` from `peaks.json`; a
    kind that is not in the table is an error, never a default."""
    table = load_json(BENCH / "peaks.json")["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r};"
                       f" known: {sorted(table)}")
    return table[device_kind]


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation (numpy's default),
    over the finite values; 0.0 for none.  Copied from the program's
    `serving/frontend.py::latency_percentiles`."""
    import numpy as np

    xs = np.asarray([v for v in values if np.isfinite(v)], dtype=np.float64)
    if xs.size == 0:
        return 0.0
    return float(np.percentile(xs, q))


class CompileCounter:
    """Counts compile requests (persistent-cache hits included) while
    `active` is set; the window runs with it set and must count none."""

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event == _LOWERING_EVENT:
            self.count += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_event)
