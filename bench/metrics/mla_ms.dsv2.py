"""Host milliseconds a protocol pass of the DeepSeek-V2 cell spends in
its in-situ steps: the program's `dmoe.mixer` spans of kind `mla` (the
MLA step of every layer, with the gate at a MoE layer) and
`dmoe.dense_ffn` (the leading dense layer's SwiGLU)
(`serving/dmoe_sim.py`), inside `bench.window`, mean per `dmoe.pass`
of the window.  None where the trace holds no such span."""

import warnings

import program_spans
import trace_reduce

_loaded: dict = {}


def reduce_planes(planes) -> float | None:
    planes = list(planes)
    windows = [s for s in trace_reduce.host_spans(planes)
               if s[0] == trace_reduce.WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0][1], windows[0][2]
    passes, total, found = 0, 0.0, False
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= w0 or s >= w1:
                    continue
                if ev.name == "dmoe.pass":
                    passes += 1
                elif (ev.name == "dmoe.dense_ffn"
                      or (ev.name == "dmoe.mixer"
                          and dict(getattr(ev, "stats", ())).get("kind")
                          == "mla")):
                    total += min(e, w1) - max(s, w0)
                    found = True
    if not found or not passes:
        return None
    return total * 1e-6 / passes


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
