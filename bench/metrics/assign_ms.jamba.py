"""Host milliseconds a protocol pass of the Jamba cell spends in the
scheduler's subcarrier assignments (`dmoe.assign`): `assign_ms.proto`'s
reading, where each Hungarian assignment is over 240 links."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "assign_ms.proto.py").read
