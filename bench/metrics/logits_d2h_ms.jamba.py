"""Host milliseconds a protocol pass of the Jamba cell takes to bring its
logits to the host (`dmoe.logits_d2h`): `logits_d2h_ms.proto`'s reading,
where the float32 logits of the K=16 queries come from four chips."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "logits_d2h_ms.proto.py").read
