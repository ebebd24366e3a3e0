"""Host milliseconds per protocol pass of the DeepSeek-V2 cell inside the
scheduler's `schedule()` calls: `sched_ms.proto`'s reading of the
benchmark's own `bench.schedule` span, over the four MoE rounds of a
pass (K=8 nodes, D=3)."""

import common

read = common.load_module(common.BENCH / "metrics" / "sched_ms.proto.py").read
