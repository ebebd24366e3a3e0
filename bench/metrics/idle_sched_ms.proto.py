"""Milliseconds a protocol pass leaves the first device idle while the
host is in the scheduler: the device's idle gaps in the traced window,
each piece given to the innermost program span over it, summed over
the pieces inside `dmoe.schedule` and the spans nested in it; mean per
pass (`bench/program_spans.py`)."""

import program_spans


def read(obs):
    s = program_spans.summary()
    return None if s is None else s["idle_sched_ms"]
