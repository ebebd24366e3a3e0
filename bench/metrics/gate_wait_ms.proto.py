"""Host milliseconds a protocol pass waits for the gate scores on the
host, summed over its rounds: the program's span `dmoe.gate_d2h` around
`np.asarray(gates_dev)` (`serving/dmoe_sim.py`), mean per pass of the
traced window (`bench/program_spans.py`)."""

import program_spans


def read(obs):
    return program_spans.per_pass("total_ms", ("dmoe.gate_d2h",))
