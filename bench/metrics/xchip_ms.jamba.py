"""Device milliseconds a protocol pass of the Jamba cell spends in the
collectives that carry the expert exchange between its chips: the
operations of the device trace whose HLO instruction is an all-gather,
all-reduce, reduce-scatter, all-to-all or collective-permute (with
their -start and -done halves), the union of their intervals inside
`bench.window` on each chip, averaged over the chips and divided by
the `bench.pass` spans of the window.  None where the trace holds no
device plane or no collective."""

import re
import warnings

import program_spans
import trace_reduce

COLLECTIVE = re.compile(r"^%?(all-gather|all-reduce|reduce-scatter"
                        r"|all-to-all|collective-permute)\b")

_loaded: dict = {}


def reduce_planes(planes) -> float | None:
    planes = list(planes)
    spans = trace_reduce.host_spans(planes)
    windows = [s for s in spans if s[0] == trace_reduce.WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0][1], windows[0][2]
    passes = sum(1 for name, s, e in spans
                 if name == "bench.pass" and s < w1 and e > w0)
    devices = [p for p in planes if trace_reduce.DEVICE_PLANE.match(p.name)]
    if not devices or not passes:
        return None
    per_chip = []
    for plane in devices:
        merged = trace_reduce._union(
            (max(s, w0), min(e, w1))
            for line in plane.lines if line.name == trace_reduce.OPS_LINE
            for name, s, e in trace_reduce._events(line)
            if e > w0 and s < w1 and COLLECTIVE.match(name))
        per_chip.append(sum(e - s for s, e in merged))
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) * 1e-6 / passes


def read(obs):
    try:
        path = trace_reduce.find_trace(program_spans.TRACE_DIR)
    except FileNotFoundError:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _loaded:
        from jax.profiler import ProfileData

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            _loaded[key] = reduce_planes(
                ProfileData.from_file(str(path)).planes)
    return _loaded[key]
