"""Host milliseconds a protocol pass spends in the exact DES sweeps of
the scheduler's alpha steps: the program's span `dmoe.des` around the
solver call of `schedulers/host.py::_des_sweep`, mean per pass of the
traced window (`bench/program_spans.py`)."""

import program_spans


def read(obs):
    return program_spans.per_pass("total_ms", ("dmoe.des",))
