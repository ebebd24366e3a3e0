"""The devices' idle share over the traced window of the Jamba cell:
`idle_share.proto`'s reading of the profiler trace, the busy time
averaged over the four chips, in percent."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "idle_share.proto.py").read
