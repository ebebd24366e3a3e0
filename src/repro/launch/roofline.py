"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms, all in seconds, per device of kind `device_kind` (peaks
from `repro.launch.mesh.PEAKS`):

    compute    = HLO_FLOPs / peak_FLOP/s          (cost_analysis is
                                                   already per-partition)
    memory     = HLO_bytes_accessed / HBM_bw
    collective = collective_bytes / ICI_link_bw

collective_bytes is parsed from the post-SPMD HLO text: for every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
we sum the moved bytes with ring-algorithm factors:

    all-reduce      2 * size * (n-1)/n
    all-gather      size_out * (n-1)/n
    reduce-scatter  size_in  * (n-1)/n
    all-to-all      size * (n-1)/n
    collective-permute  size

MODEL_FLOPS uses the 6·N_active·D convention (2·N·D for inference) so the
ratio MODEL_FLOPS / HLO_FLOPs exposes remat/dispatch overheads.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.launch import mesh as mesh_lib

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(.+?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Tuple[float, List[Dict]]:
    """Returns (total collective bytes per device, per-op breakdown)."""
    ops: List[Dict] = []
    total = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done" in line:
            continue
        result_type, kind = m.group(1), m.group(2)
        size = _type_bytes(result_type)
        gm = _GROUPS_RE.search(line)
        n = int(gm.group(2)) if gm else 2
        frac = (n - 1) / max(n, 1)
        if kind == "all-reduce":
            moved = 2.0 * size * frac
        elif kind == "all-gather":
            moved = size * frac
        elif kind == "reduce-scatter":
            moved = size * n * frac  # result is the scattered shard
        elif kind == "all-to-all":
            moved = size * frac
        else:  # collective-permute
            moved = float(size)
        total += moved
        ops.append({"kind": kind, "bytes": size, "group_size": n,
                    "moved": moved, "line": line.strip()[:160]})
    return total, ops


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    hlo_flops: float                 # per device
    hlo_bytes: float                 # per device
    collective_bytes: float          # per device
    model_flops: float               # global, 6·N_active·D convention
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    useful_flops_ratio: float
    arg_bytes: int
    temp_bytes: int

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze(
    *, arch: str, shape: str, mesh_name: str, n_devices: int,
    device_kind: str, cost: Dict, memstats, hlo_text: str,
    model_flops: float,
) -> Roofline:
    # trip-count-aware re-analysis: XLA's cost_analysis counts while-loop
    # (lax.scan) bodies once, grossly under-reporting scanned-layer
    # programs — hlo_cost multiplies bodies by known_trip_count.
    from repro.launch import hlo_cost

    hc = hlo_cost.analyze_hlo(hlo_text)
    flops = float(hc.flops)
    byts = float(hc.bytes_accessed)
    coll = float(hc.collective_bytes)
    peaks = mesh_lib.chip_peaks(device_kind)
    compute_s = flops / peaks.flops_bf16
    memory_s = byts / peaks.hbm_bw
    coll_s = coll / peaks.ici_bw_per_link
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    total_hlo_flops = flops * n_devices
    ratio = model_flops / total_hlo_flops if total_hlo_flops else 0.0
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, n_devices=n_devices,
        hlo_flops=flops, hlo_bytes=byts, collective_bytes=coll,
        model_flops=model_flops, compute_s=compute_s, memory_s=memory_s,
        collective_s=coll_s, bottleneck=bottleneck,
        useful_flops_ratio=ratio,
        arg_bytes=int(getattr(memstats, "argument_size_in_bytes", 0) or 0),
        temp_bytes=int(getattr(memstats, "temp_size_in_bytes", 0) or 0),
    )


# ----------------------------------------------------------------------
# model FLOPs (6·N_active·D convention)
# ----------------------------------------------------------------------

def active_param_count(params_shape, cfg) -> float:
    """Parameter count with routed-expert weights scaled by top_k/E
    (embeddings excluded per convention)."""
    import jax

    total = 0.0
    flat = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    e = max(cfg.moe.num_experts, 1)
    act_frac = (cfg.moe.top_k / e) if cfg.moe.num_experts else 1.0
    for path, leaf in flat:
        key = "/".join(p.key if hasattr(p, "key") else str(p) for p in path)
        n = float(np.prod(leaf.shape))
        if re.search(r"(^|/)(embed|unembed)$", key):
            continue
        if re.search(r"ffn/(w1|wu|w2)$", key):
            n *= act_frac
        total += n
    return total


def model_flops(params_shape, cfg, *, tokens: float, kind: str) -> float:
    n_active = active_param_count(params_shape, cfg)
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * tokens


def format_table(rows: List[Dict]) -> str:
    hdr = (f"{'arch':<22}{'shape':<13}{'mesh':<10}{'compute_s':>11}"
           f"{'memory_s':>11}{'collect_s':>11}{'bottleneck':>12}"
           f"{'useful%':>9}{'temp_GB':>9}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['arch']:<22}{r['shape']:<13}{r['mesh']:<10}"
            f"{r['compute_s']:>11.3e}{r['memory_s']:>11.3e}"
            f"{r['collective_s']:>11.3e}{r['bottleneck']:>12}"
            f"{100*r['useful_flops_ratio']:>8.1f}%"
            f"{r['temp_bytes']/1e9:>9.2f}")
    return "\n".join(lines)
