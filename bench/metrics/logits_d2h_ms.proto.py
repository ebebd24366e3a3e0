"""Host milliseconds a protocol pass takes to bring its logits to the
host: the program's span `dmoe.logits_d2h` around
`np.asarray(logits)` at the end of `serve()` (`serving/dmoe_sim.py`),
mean per pass of the traced window (`bench/program_spans.py`)."""

import program_spans


def read(obs):
    return program_spans.per_pass("total_ms", ("dmoe.logits_d2h",))
