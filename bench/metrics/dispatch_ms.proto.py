"""Host milliseconds a protocol pass spends handing work to the device:
the self time of the program's spans `dmoe.embed`, `dmoe.params`,
`dmoe.attn_gate`, `dmoe.expert_ffn`, `dmoe.combine` and `dmoe.unembed`
(`serving/dmoe_sim.py`), mean per pass of the traced window
(`bench/program_spans.py`)."""

import program_spans

SPANS = ("dmoe.embed", "dmoe.params", "dmoe.attn_gate", "dmoe.expert_ffn",
         "dmoe.combine", "dmoe.unembed")


def read(obs):
    return program_spans.per_pass("self_ms", SPANS)
