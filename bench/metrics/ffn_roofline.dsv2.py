"""The expert-FFN step's share of its roofline on the DeepSeek-V2 cell:
the least time the chip could take for the steps of a pass -- per step
the larger of its operations over the bf16 peak and its bytes over the
HBM bandwidth (`bench/flops/protocol_group.py::ffn_step_cost`, the
peaks of `bench/peaks.json`) -- over the steps' device time a pass
(`ffn_ms.dsv2`), in percent.  None where that time is not read."""

import common

_ffn_ms = common.load_module(common.BENCH / "metrics" / "ffn_ms.dsv2.py")


def read(obs):
    step = obs.get("ffn_step")
    measured = _ffn_ms.read(obs) if step else None
    if not measured:
        return None
    peak = common.peaks(step["device_kind"])
    least = max(step["flops"] / peak["bf16_flops_per_s"],
                step["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least * step["per_pass"] / (measured * 1e-3)
