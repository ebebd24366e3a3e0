"""Milliseconds a protocol pass of the Jamba cell leaves the first device
idle while the host is in the scheduler: `idle_sched_ms.proto`'s
reading of the device trace against the program's spans."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "idle_sched_ms.proto.py").read
