"""Host milliseconds a protocol pass of the DeepSeek-V2 cell spends
handing work to the device: the self time of the program's spans
`dmoe.embed`, `dmoe.mixer` (MLA, with the gate at a MoE layer),
`dmoe.dense_ffn`, `dmoe.expert_ffn`, `dmoe.combine` and `dmoe.unembed`
(`serving/dmoe_sim.py`), mean per pass of the traced window
(`bench/program_spans.py`): `dispatch_ms.proto` with the gated MLA and
dense steps in place of the gated attention step."""

import program_spans

SPANS = ("dmoe.embed", "dmoe.mixer", "dmoe.dense_ffn", "dmoe.expert_ffn",
         "dmoe.combine", "dmoe.unembed")


def read(obs):
    return program_spans.per_pass("self_ms", SPANS)
