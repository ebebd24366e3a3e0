"""Branch-and-bound nodes the exact DES explored per protocol pass of the
DeepSeek-V2 cell (K=8 nodes, D=3): `des_nodes.proto`'s reading of
`RoundSchedule.des_nodes`.  A count."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "des_nodes.proto.py").read
