"""Host milliseconds a protocol pass of the DeepSeek-V2 cell waits for
the router's (K, N, 160) float32 scores on the host, summed over its
rounds (`dmoe.gate_d2h`): `gate_wait_ms.proto`'s reading."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "gate_wait_ms.proto.py").read
