"""The device's idle share over the traced window of protocol passes:
1 - (union of the device's operation intervals) / (window), from the
profiler trace (`bench/trace_reduce.py`), in percent."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("sched_s"):
        return None
    return 100.0 * trace["idle_share"]
