"""Host milliseconds a protocol pass spends in the scheduler's
subcarrier assignments (beta steps): the program's span `dmoe.assign`
in `schedulers/host.py::_allocate_beta`, mean per pass of the traced
window (`bench/program_spans.py`)."""

import program_spans


def read(obs):
    return program_spans.per_pass("total_ms", ("dmoe.assign",))
