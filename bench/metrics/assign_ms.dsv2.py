"""Host milliseconds a protocol pass of the DeepSeek-V2 cell spends in the
scheduler's subcarrier assignments (`dmoe.assign`): `assign_ms.proto`'s
reading, at K=8 nodes and M=64 subcarriers."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "assign_ms.proto.py").read
