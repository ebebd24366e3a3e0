"""The device's idle share over the traced window of the DeepSeek-V2
cell: `idle_share.proto`'s reading of the profiler trace, in percent."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "idle_share.proto.py").read
