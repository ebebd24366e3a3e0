"""Rows a protocol pass's DES sweeps settle by the Remark-2 Top-D
fallback (no D experts meet the QoS), counted once per alpha step: the
metadata `fallback` of the program's span `dmoe.des`
(`schedulers/host.py::_des_sweep`), summed and taken as a mean per pass
of the traced window (`bench/program_spans.py`).  A count."""

import program_spans


def read(obs):
    s = program_spans.summary()
    if s is None:
        return None
    return s["stats"].get("dmoe.des", {}).get("fallback")
