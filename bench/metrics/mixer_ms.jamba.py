"""Host milliseconds a protocol pass of the Jamba cell spends in the
in-situ steps: the total of the program's spans `dmoe.mixer` (each
sublayer's Mamba or attention mixer, with the gate at a MoE sublayer)
and `dmoe.dense_ffn` (the dense SwiGLU of the even sublayers)
(`serving/dmoe_sim.py`), mean per pass of the traced window
(`bench/program_spans.py`)."""

import program_spans


def read(obs):
    return program_spans.per_pass("total_ms", ("dmoe.mixer",
                                               "dmoe.dense_ffn"))
