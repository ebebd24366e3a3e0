"""Branch-and-bound nodes the exact DES explored per protocol pass of the
Jamba cell (K=16): `des_nodes.proto`'s reading of
`RoundSchedule.des_nodes`.  A count."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "des_nodes.proto.py").read
