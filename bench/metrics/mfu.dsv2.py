"""The DeepSeek-V2 cell's share of its chip's bf16 peak: the operations
its passes require (`bench/flops/protocol_group.py`: MLA, the dense and
shared SwiGLUs, the router, the routed pairs the combines weighed and
the unembedding), over the window's seconds times the peak, in percent
(`mfu.proto`'s reading)."""

import common

read = common.load_module(common.BENCH / "metrics" / "mfu.proto.py").read
