"""From a profiler trace to the program's own spans per protocol pass:
the host time of each `dmoe.*` span, the sums of their metadata, and the
device's idle time attributed to them.

`DMoESimulator.serve` and the host schedulers mark each layer boundary
of a served pass with `jax.profiler.TraceAnnotation` (`dmoe.pass`
around a pass, `dmoe.round` around each layer, `dmoe.schedule` around
the scheduler, `dmoe.des` and `dmoe.assign` inside it, ...), so the
spans share the device trace's clock.  Only what lies inside the
benchmark's `bench.window` counts, clipped to it, and every number is a
mean per pass: divided by the number of `dmoe.pass` spans there.

- total: the summed duration of each span name;
- self: the duration less the part covered by the spans nested in it.
  Nesting is containment on one host thread line, so a span of another
  thread is never a child;
- stats: each span name's metadata, summed;
- idle: the gaps between busy intervals of the first device, each piece
  of a gap given to the innermost span over it, by interval
  intersection, among the spans of the thread line that carries
  `dmoe.pass`.  Pieces inside `dmoe.schedule` (or a span nested in it)
  are idle in the scheduler; the other pieces inside `dmoe.pass` are
  idle in the rest of `serve()`.

It imports nothing of the program.  The trace is the one a `--trace 1`
run leaves in `.bench_trace/`, read once per process.
"""

from __future__ import annotations

import bisect
import warnings
from pathlib import Path

import trace_reduce
from common import ROOT

TRACE_DIR = ROOT / ".bench_trace"
PREFIX = "dmoe."
PASS = "dmoe.pass"
SCHEDULE = "dmoe.schedule"

_loaded: dict = {}


class _Span:
    __slots__ = ("name", "start", "end", "stats", "parent", "children")

    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end = name, start, end
        self.stats = stats
        self.parent, self.children = None, []

    @property
    def dur(self):
        return self.end - self.start

    def within(self, name) -> bool:
        span = self
        while span is not None:
            if span.name == name:
                return True
            span = span.parent
        return False


def _nest(spans):
    """Link each span to the innermost span of the same line that
    contains it."""
    stack = []
    for span in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and span.end > stack[-1].end:
            stack.pop()
        if stack:
            span.parent = stack[-1]
            stack[-1].children.append(span)
        stack.append(span)
    return spans


def _gap_measure(planes, w0, w1):
    """The idle time of the first device between two instants, as a
    function of them; None where the trace holds no device."""
    devices = [p for p in planes if trace_reduce.DEVICE_PLANE.match(p.name)]
    if not devices:
        return None
    busy = trace_reduce._union(
        (max(s, w0), min(e, w1))
        for line in devices[0].lines if line.name == trace_reduce.OPS_LINE
        for _, s, e in trace_reduce._events(line) if e > w0 and s < w1)
    gaps, cursor = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    starts = [s for s, _ in gaps]
    below = [0.0]
    for s, e in gaps:
        below.append(below[-1] + e - s)

    def before(t):
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        s, e = gaps[i - 1]
        return below[i - 1] + min(t, e) - s

    return lambda t0, t1: before(t1) - before(t0)


def reduce_planes(planes) -> dict | None:
    """The reduction over already loaded planes; None where the trace
    has no `bench.window` or no `dmoe.pass` inside it."""
    planes = list(planes)
    windows = [s for s in trace_reduce.host_spans(planes)
               if s[0] == trace_reduce.WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0][1], windows[0][2]
    lines = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            spans = [_Span(ev.name, max(ev.start_ns, w0),
                           min(ev.start_ns + ev.duration_ns, w1),
                           dict(getattr(ev, "stats", ())))
                     for ev in line.events if ev.name.startswith(PREFIX)
                     and ev.start_ns + ev.duration_ns > w0
                     and ev.start_ns < w1]
            if spans:
                lines.append(_nest(spans))
    passes = [sum(s.name == PASS for s in line) for line in lines]
    n = sum(passes)
    if not n:
        return None

    total, self_, count, stats = {}, {}, {}, {}
    for span in (s for line in lines for s in line):
        own = span.dur - sum(c.dur for c in span.children)
        total[span.name] = total.get(span.name, 0.0) + span.dur
        self_[span.name] = self_.get(span.name, 0.0) + own
        count[span.name] = count.get(span.name, 0) + 1
        sums = stats.setdefault(span.name, {})
        for key, value in span.stats.items():
            if isinstance(value, (int, float)):
                sums[key] = sums.get(key, 0) + value

    def ms(table):
        return {k: v * 1e-6 / n for k, v in table.items()}

    out = {"passes": n, "total_ms": ms(total), "self_ms": ms(self_),
           "count": {k: v / n for k, v in count.items()},
           "stats": {k: {s: v / n for s, v in d.items()}
                     for k, d in stats.items() if d},
           "idle_ms": None, "idle_sched_ms": None, "idle_serve_ms": None}

    idle = _gap_measure(planes, w0, w1)
    if idle is not None:
        main = lines[passes.index(max(passes))]
        by_name, sched, serve = {}, 0.0, 0.0
        for span in main:
            own = idle(span.start, span.end) - sum(
                idle(c.start, c.end) for c in span.children)
            by_name[span.name] = by_name.get(span.name, 0.0) + own
            if span.within(SCHEDULE):
                sched += own
            elif span.within(PASS):
                serve += own
        out["idle_ms"] = ms(by_name)
        out["idle_sched_ms"] = sched * 1e-6 / n
        out["idle_serve_ms"] = serve * 1e-6 / n
    return out


def summary(trace_dir: Path | None = None) -> dict | None:
    """The reduction of the trace in `trace_dir` (default: the traced
    run's `.bench_trace/`), loaded once per process; None where there is
    no trace or no `dmoe.pass` span in its window."""
    try:
        path = trace_reduce.find_trace(trace_dir or TRACE_DIR)
    except FileNotFoundError:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _loaded:
        from jax.profiler import ProfileData

        with warnings.catch_warnings():
            # jaxlib's type for event stats warns of its own missing
            # __module__ the first time it is made.
            warnings.simplefilter("ignore", DeprecationWarning)
            _loaded[key] = reduce_planes(
                ProfileData.from_file(str(path)).planes)
    return _loaded[key]


def per_pass(table: str, names) -> float | None:
    """The sum over `names` of one per-pass table of the summary
    (`total_ms`, `self_ms`, `idle_ms`); None where there is no summary,
    no such table or none of the names in it."""
    s = summary()
    got = None if s is None else s[table]
    if not got or not any(name in got for name in names):
        return None
    return sum(got.get(name, 0.0) for name in names)
