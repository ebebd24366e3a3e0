"""Host milliseconds a protocol pass of the Jamba cell spends in the
exact DES sweeps (`dmoe.des`): `des_ms.proto`'s reading, at K=16."""

import common

read = common.load_module(common.BENCH / "metrics" / "des_ms.proto.py").read
