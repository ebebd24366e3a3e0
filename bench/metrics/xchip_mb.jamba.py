"""Megabytes of the protocol's step-4 and step-5 payload a pass of the
Jamba cell sends between chips: the metadata `xchip_bytes` of the
program's `dmoe.pass` span, mean per pass of the traced window.  The
simulator counts it from the shapes and the node mesh (each round's FFN
input to the other chips, each chip's float32 expert sum back), so it
is the protocol's nominal payload, fixed by the configuration, and not
the bytes the compiled collectives move: `xchip_ms.jamba` reads those
from the device trace."""

import program_spans


def read(obs):
    s = program_spans.summary()
    value = None if s is None else s["stats"].get(
        "dmoe.pass", {}).get("xchip_bytes")
    return None if value is None else value / 1e6
