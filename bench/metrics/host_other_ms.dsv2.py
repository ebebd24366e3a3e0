"""Host milliseconds of a protocol pass of the DeepSeek-V2 cell that no
device-facing or scheduler span names (the self time of `dmoe.pass` and
`dmoe.round`, plus `dmoe.account`): `host_other_ms.proto`'s reading."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "host_other_ms.proto.py").read
