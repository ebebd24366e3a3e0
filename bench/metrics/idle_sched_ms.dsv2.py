"""Milliseconds a protocol pass of the DeepSeek-V2 cell leaves the device
idle while the host is in the scheduler: `idle_sched_ms.proto`'s
reading of the device trace against the program's spans."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "idle_sched_ms.proto.py").read
