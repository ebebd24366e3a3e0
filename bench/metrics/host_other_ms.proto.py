"""Host milliseconds of a protocol pass that no device-facing or
scheduler span names: the self time of the program's spans `dmoe.pass`
and `dmoe.round`, plus `dmoe.account` (the round's energy accounting)
(`serving/dmoe_sim.py`), mean per pass of the traced window
(`bench/program_spans.py`)."""

import program_spans


def read(obs):
    return program_spans.per_pass(
        "self_ms", ("dmoe.pass", "dmoe.round", "dmoe.account"))
