"""Device milliseconds a protocol pass of the DeepSeek-V2 cell spends in
the expert-FFN step: the events of the compiled module of
`DMoESimulator._ffn_step` (`jit__ffn_step`, every held expert and the
shared experts on every token) on the `XLA Modules` line of the first
device plane, inside `bench.window`, over the `bench.pass` spans of the
window.  None where the trace holds no device plane or no such module."""

import re
import warnings

import program_spans
import trace_reduce

MODULES_LINE = "XLA Modules"
FFN_MODULE = re.compile(r"^jit__ffn_step\b")

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
    busy = [(max(s, w0), min(e, w1))
            for line in devices[0].lines if line.name == MODULES_LINE
            for name, s, e in trace_reduce._events(line)
            if e > w0 and s < w1 and FFN_MODULE.match(name)]
    if not busy:
        return None
    return sum(e - s for s, e in busy) * 1e-6 / passes


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
