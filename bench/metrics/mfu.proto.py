"""The protocol passes' share of the chips' bf16 peak: the operations
the passes of the window require (`bench/flops/protocol.py`), over the
window's seconds times the peak of the chips used, in percent."""


def read(obs):
    flops = obs.get("required_flops")
    if not flops or not obs.get("sched_s"):
        return None
    peak = obs["peak_flops_bf16"] * obs["chips"]
    return 100.0 * flops / (obs["window_s"] * peak)
