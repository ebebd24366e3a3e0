"""Rows a protocol pass of the DeepSeek-V2 cell settles by the Remark-2
Top-D fallback, counted once per alpha step (metadata `fallback` of
`dmoe.des`): `fallback_rows.proto`'s reading, at K=8 nodes, D=3.  A
count."""

import common

read = common.load_module(common.BENCH / "metrics"
                          / "fallback_rows.proto.py").read
