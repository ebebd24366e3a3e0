"""Host milliseconds a protocol pass of the DeepSeek-V2 cell takes to
bring its logits to the host (`dmoe.logits_d2h`, with the rounds'
chosen experts and counts): `logits_d2h_ms.proto`'s reading, for the
float32 logits of 8 queries of 64 tokens over 102,400 ids."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "logits_d2h_ms.proto.py").read
