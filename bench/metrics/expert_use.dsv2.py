"""The share of the expert-FFN step's outputs that the DeepSeek-V2 cell's
combines use: the (token, expert) pairs weighed from the held experts
(metadata `routed_pairs` of the program's `dmoe.pass`) over the outputs
the dense step computes, tokens a pass times the held experts of each
round (metadata `held` of `dmoe.expert_ffn`, summed over the pass), per
pass of the traced window, in percent.  None where the program has no
such counters."""

import program_spans


def read(obs):
    s = program_spans.summary()
    if s is None or not obs.get("tokens_per_pass"):
        return None
    pairs = s["stats"].get("dmoe.pass", {}).get("routed_pairs")
    held = s["stats"].get("dmoe.expert_ffn", {}).get("held")
    if pairs is None or not held:
        return None
    return 100.0 * pairs / (obs["tokens_per_pass"] * held)
