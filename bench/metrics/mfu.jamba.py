"""The Jamba cell's share of its four chips' bf16 peak: the operations
its passes require (`bench/flops/protocol_mesh.py`), over the window's
seconds times the peak of the chips used, in percent
(`mfu.proto`'s reading)."""

import common

read = common.load_module(common.BENCH / "metrics" / "mfu.proto.py").read
