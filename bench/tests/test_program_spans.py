"""The reduction from the program's own spans (`dmoe.*`) to host time,
metadata and device idle time per pass, on hand-made planes whose
answers are worked out below, and the readers of the metrics built on
it, which find nothing without a trace or without a `dmoe.pass`."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

import smoke
import common
import program_spans

READERS = ["dispatch_ms.proto", "gate_wait_ms.proto", "logits_d2h_ms.proto",
           "host_other_ms.proto", "des_ms.proto", "assign_ms.proto",
           "fallback_rows.proto", "idle_sched_ms.proto",
           "idle_serve_ms.proto"]


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start),
              duration_ns=float(end - start), stats=list(stats.items()))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e) for n, e in lines])


def ns_ms(ns):
    return pytest.approx(ns * 1e-6)


# The main thread: two passes inside the window (the second ends past
# it and is clipped), one wholly before it (left out).
MAIN = [
    ev("bench.window", 100, 1100),
    ev("dmoe.pass", 0, 90, **{"pass": 0}),
    ev("dmoe.des", 10, 80, nodes=100, fallback=100),
    ev("dmoe.pass", 100, 600, **{"pass": 1}),
    ev("dmoe.round", 120, 580, **{"pass": 1, "layer": 1}),
    ev("dmoe.attn_gate", 130, 200),
    ev("dmoe.gate_d2h", 200, 260),
    ev("dmoe.schedule", 260, 500),
    ev("dmoe.des", 270, 400, nodes=7, fallback=2),
    ev("dmoe.assign", 400, 450),
    ev("dmoe.combine", 500, 560),
    ev("dmoe.pass", 600, 1200, **{"pass": 2}),
    ev("dmoe.round", 610, 1000, **{"pass": 2, "layer": 1}),
    ev("dmoe.schedule", 700, 900),
    ev("dmoe.des", 710, 800, nodes=5, fallback=1),
    ev("dmoe.logits_d2h", 1050, 1150),
    ev("unrelated", 0, 2000),
]
# Another thread: its span lies inside the first pass's schedule in
# time, but is nested in nothing.
WORKER = [ev("dmoe.des", 300, 350, nodes=1, fallback=0)]
# Busy [100, 130], [180, 280], [600, 620], [990, 1100]: idle
# [130, 180], [280, 600], [620, 990].
DEVICE = plane("/device:TPU:0", [("XLA Ops", [
    ev("a", 50, 130), ev("b", 180, 280), ev("c", 600, 620),
    ev("d", 990, 1300)])])


def host(*lines):
    return plane("/host:CPU", [("python", line) for line in lines])


def test_self_time_nesting_clipping_and_per_pass_division():
    got = program_spans.reduce_planes([host(MAIN, WORKER), DEVICE])
    assert got["passes"] == 2
    total, own = got["total_ms"], got["self_ms"]
    # Passes clipped to [100, 600] and [600, 1100]; the one before the
    # window and its span are gone.
    assert total["dmoe.pass"] == ns_ms(500)
    # pass 1: 500 - round 460; pass 2: 500 - round 390 - logits 50.
    assert own["dmoe.pass"] == ns_ms((40 + 60) / 2)
    # round 1: 460 - 70 - 60 - 240 - 60; round 2: 390 - 200.
    assert own["dmoe.round"] == ns_ms((30 + 190) / 2)
    assert own["dmoe.schedule"] == ns_ms((60 + 110) / 2)
    assert total["dmoe.schedule"] == ns_ms((240 + 200) / 2)
    assert total["dmoe.logits_d2h"] == ns_ms(50 / 2)
    # The worker's span counts on its own line, whole: 130 + 90 + 50.
    assert total["dmoe.des"] == ns_ms(270 / 2)
    assert own["dmoe.des"] == total["dmoe.des"]
    assert got["count"]["dmoe.des"] == 1.5
    assert "unrelated" not in total and "bench.window" not in total
    assert got["stats"]["dmoe.des"] == {"nodes": 6.5, "fallback": 1.5}
    # The parts add up to the pass.
    parts = sum(own[n] for n in own if n not in ("dmoe.des", "dmoe.assign",
                                                  "dmoe.schedule"))
    assert parts + total["dmoe.schedule"] == pytest.approx(
        total["dmoe.pass"])


def test_idle_attributed_by_intersection_on_the_pass_line():
    got = program_spans.reduce_planes([host(MAIN, WORKER), DEVICE])
    idle = got["idle_ms"]
    # [130, 180] lies in attn_gate.  [280, 600] straddles des (120),
    # assign (50), the schedule's own time (50), combine (60), round 1's
    # own time (20) and pass 1's (20).  [620, 990]: round 2 (80 + 90),
    # schedule (10 + 100), des (90).  The worker's des takes nothing.
    assert idle["dmoe.attn_gate"] == ns_ms(50 / 2)
    assert idle["dmoe.des"] == ns_ms((120 + 90) / 2)
    assert idle["dmoe.assign"] == ns_ms(50 / 2)
    assert idle["dmoe.schedule"] == ns_ms((50 + 10 + 100) / 2)
    assert idle["dmoe.combine"] == ns_ms(60 / 2)
    assert idle["dmoe.round"] == ns_ms((20 + 80 + 90) / 2)
    assert idle["dmoe.pass"] == ns_ms(20 / 2)
    assert idle["dmoe.gate_d2h"] == 0.0
    assert got["idle_sched_ms"] == ns_ms((120 + 50 + 50 + 10 + 90 + 100) / 2)
    assert got["idle_serve_ms"] == ns_ms((50 + 60 + 20 + 20 + 80 + 90) / 2)
    # Every idle nanosecond of the window lies in some pass here.
    assert got["idle_sched_ms"] + got["idle_serve_ms"] == ns_ms(
        (50 + 320 + 370) / 2)


def test_nothing_to_read():
    assert program_spans.reduce_planes([host(WORKER), DEVICE]) is None
    no_pass = [e for e in MAIN if e.name != "dmoe.pass"]
    assert program_spans.reduce_planes([host(no_pass), DEVICE]) is None
    no_window = [e for e in MAIN if e.name != "bench.window"]
    assert program_spans.reduce_planes([host(no_window), DEVICE]) is None
    # Without a device, the spans are read and the idle time is not.
    got = program_spans.reduce_planes([host(MAIN)])
    assert got["passes"] == 2
    assert got["idle_ms"] is None and got["idle_sched_ms"] is None


def _trace(log_dir, with_pass: bool):
    """A real trace of the host spans a traced run leaves (no device)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            if with_pass:
                with jax.profiler.TraceAnnotation("dmoe.pass", **{"pass": 1}):
                    with jax.profiler.TraceAnnotation("dmoe.des") as span:
                        span.set_metadata(nodes=9, fallback=4)
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("reader", READERS)
def test_readers_find_nothing_without_a_trace_or_a_pass(reader, tmp_path,
                                                         monkeypatch):
    mod = common.load_module(smoke.BENCH / "metrics" / f"{reader}.py")
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path / "none")
    assert mod.read({}) is None
    _trace(tmp_path / "empty", with_pass=False)
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path / "empty")
    assert mod.read({}) is None


def test_a_real_trace_carries_the_metadata(tmp_path, monkeypatch):
    _trace(tmp_path, with_pass=True)
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    got = program_spans.summary()
    assert got["passes"] == 1
    assert got["stats"]["dmoe.des"] == {"nodes": 9, "fallback": 4}
    read = {r: common.load_module(smoke.BENCH / "metrics" / f"{r}.py").read(
        {}) for r in READERS}
    assert read["fallback_rows.proto"] == 4
    assert read["des_ms.proto"] > 0 and read["host_other_ms.proto"] > 0
    # The CPU trace has no TPU device: no idle time, and no dispatch span.
    assert read["idle_sched_ms.proto"] is None
    assert read["dispatch_ms.proto"] is None
