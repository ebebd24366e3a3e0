"""The reduction from a profiler trace to busy time, idle share, top
operations and labelled idle gaps, on hand-made planes whose answers are
worked out below."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

import smoke  # noqa: F401  (puts bench/ on the path)
import trace_reduce


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e) for n, e in lines])


def test_union_busy_gaps_and_labels():
    host = plane("/host:CPU", [("python", [
        ev("bench.window", 100, 1100),
        ev("bench.pass", 100, 600),
        ev("bench.schedule", 300, 500),
        ev("bench.pass", 600, 1100),
        ev("unrelated", 0, 2000),
    ])])
    dev = plane("/device:TPU:0", [
        ("XLA Ops", [ev("dot", 50, 200),         # clipped to 100..200
                     ev("fusion", 150, 250),      # overlaps dot
                     ev("dot", 550, 700),
                     ev("copy", 1050, 1300)]),    # clipped to 1050..1100
        ("XLA Modules", [ev("jit_x", 0, 5000)]),  # not an operation line
    ])
    got = trace_reduce.reduce_planes([host, dev], top=10)
    # busy: [100, 250] + [550, 700] + [1050, 1100] = 150 + 150 + 50 ns
    assert got["busy_s"] == pytest.approx(350e-9)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["idle_share"] == pytest.approx(0.65)
    assert got["devices"] == 1
    ops = dict(got["device_ops"])
    assert ops["dot"] == pytest.approx(250e-9)     # 100 + 150 inside
    assert ops["fusion"] == pytest.approx(100e-9)
    assert ops["copy"] == pytest.approx(50e-9)
    assert got["device_ops"][0][0] == "dot"
    # gaps: [250, 550] mid 400 in the schedule span; [700, 1050] mid 875
    # in the second pass only.
    assert got["idle_gaps"] == [["bench.pass", pytest.approx(350e-9)],
                                ["bench.schedule", pytest.approx(300e-9)]]


def test_busy_averages_devices_and_needs_a_window():
    host = plane("/host:CPU", [("t", [ev("bench.window", 0, 100)])])
    d0 = plane("/device:TPU:0", [("XLA Ops", [ev("a", 0, 100)])])
    d1 = plane("/device:TPU:1", [("XLA Ops", [ev("a", 0, 50)])])
    got = trace_reduce.reduce_planes([host, d0, d1])
    assert got["busy_s"] == pytest.approx(75e-9)
    assert got["devices"] == 2
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_planes([d0])
    with pytest.raises(ValueError, match="device plane"):
        trace_reduce.reduce_planes([host])
