"""From a profiler trace (`.xplane.pb`) to the device's busy time, its
idle share, the operations that took the most time, and the longest idle
gaps labelled by what the host was doing.

The benchmark marks its own host spans with `jax.profiler.TraceAnnotation`
(`bench.window` around the measured window, `bench.pass` around each
served pass, `bench.schedule` around each scheduler call), so they share
the device trace's clock.  Only what lies inside `bench.window` counts.

- busy: the union of the intervals of the device's operations (the
  `XLA Ops` line of each `/device:TPU:<n>` plane), averaged over the
  devices in the trace;
- device_ops: seconds per operation name, summed within the window and
  averaged over the devices, largest first;
- idle_gaps: the gaps between busy intervals on the first device, each
  named by the innermost benchmark span that covers its middle (or
  `host` where none does), longest first.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."


def find_trace(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def host_spans(planes):
    """(name, start_ns, end_ns) of every benchmark span on the host."""
    spans = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            spans.extend(ev for ev in _events(line)
                         if ev[0].startswith(SPAN_PREFIX))
    return spans


def reduce_planes(planes, top: int = 10) -> dict:
    """The reduction over already loaded planes (see `reduce_trace`)."""
    planes = list(planes)
    spans = host_spans(planes)
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    w0, w1 = windows[0][1], windows[0][2]
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    busy_per_dev, op_time, first_busy = [], {}, None
    for plane in devices:
        lines = [line for line in plane.lines if line.name == OPS_LINE]
        if not lines:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line; its "
                             f"lines: {[line.name for line in plane.lines]}")
        ops = [ev for line in lines for ev in _events(line)]
        clipped = [(max(s, w0), min(e, w1), name) for name, s, e in ops
                   if e > w0 and s < w1]
        for s, e, name in clipped:
            op_time[name] = op_time.get(name, 0.0) + (e - s) * 1e-9
        merged = _union([(s, e) for s, e, _ in clipped])
        busy_per_dev.append(sum(e - s for s, e in merged) * 1e-9)
        if first_busy is None:
            first_busy = merged
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    n_dev = len(devices)
    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy_per_dev) / n_dev
    if busy_s <= 0:
        raise ValueError("no device operation inside the window")

    gaps, cursor = [], w0
    for s, e in first_busy + [[w1, w1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    inner = [sp for sp in spans if sp[0] != WINDOW]

    def label(g0, g1):
        mid = (g0 + g1) / 2
        covering = [sp for sp in inner if sp[1] <= mid <= sp[2]]
        if not covering:
            return "host"
        return min(covering, key=lambda sp: sp[2] - sp[1])[0]

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": n_dev,
        "device_ops": [[name, t / n_dev] for name, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(g0, g1), (g1 - g0) * 1e-9]
                      for g0, g1 in gaps[:top]],
    }


def reduce_trace(path: Path, top: int = 10) -> dict:
    """Busy and window seconds, idle share, top device operations and
    longest idle gaps of the trace at `path`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return reduce_planes(data.planes, top=top)
