"""CPU tests of the readers of the round engine's stage spans and of the
device's unattributed idle share.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/test_tracing_metrics.py

Each reader gets hand-made obs ``SpanRecord``s and a hand-made
``trace_reduce.Reduced`` through the harness's own ``Run``.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
PKG = ROOT / "chipbench"

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def reader(name: str):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", PKG / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def make_run(spans=(), units=2, trace=None):
    from chipbench.harness import Run

    run = Run(None, None, None, 0, 10.0, None, None)
    run.t0, run.t1 = 100.0, 110.0
    run.units = units
    run.spans = list(spans)
    run.trace = trace
    return run


def rec(name, t, dur, parent=None, depth=0, trace_id=1):
    from repro.obs.tracing import SpanRecord

    return SpanRecord(name=name, t_start=t, dur_s=dur, thread_id=7, parent=parent,
                      depth=depth, trace_id=trace_id)


def resolution(t0: float, trace_id: int) -> list:
    """One resolution's spans, starting at ``t0``: 1 s in ``em.run``."""
    return [
        rec("em.run", t0, 1.0, trace_id=trace_id),
        rec("rounds.stage", t0, 0.1, "em.run", 1, trace_id),
        rec("compile", t0 + 0.01, 0.02, "rounds.stage", 2, trace_id),
        rec("rounds.schedule", t0 + 0.1, 0.05, "em.run", 1, trace_id),
        rec("rounds.full", t0 + 0.2, 0.3, "em.run", 1, trace_id),
        rec("rounds.messages", t0 + 0.3, 0.1, "rounds.full", 2, trace_id),
        rec("rounds.schedule", t0 + 0.5, 0.05, "em.run", 1, trace_id),
        rec("rounds.promote", t0 + 0.6, 0.2, "em.run", 1, trace_id),
    ]


@pytest.mark.parametrize("name,want_ms", [
    ("stage_ms.batch", 80.0),  # 0.1 s less the nested 0.02 s compile
    ("schedule_ms.batch", 100.0),  # two stretches of 0.05 s
    ("messages_ms.batch", 100.0),
    ("full_round_ms.batch", 200.0),  # 0.3 s less the nested messages
])
def test_self_time_per_resolution(name, want_ms):
    run = make_run(resolution(101.0, 1) + resolution(103.0, 2), units=2)
    assert reader(name)(run) == pytest.approx(want_ms)


def test_full_round_and_messages_add_up_to_the_whole_full_round():
    run = make_run(resolution(101.0, 1) + resolution(103.0, 2), units=2)
    whole = 1e3 * run.span_seconds(("rounds.full",), self_time=False) / run.units
    assert reader("full_round_ms.batch")(run) + reader("messages_ms.batch")(run) == \
        pytest.approx(whole)


@pytest.mark.parametrize("name", ["stage_ms.batch", "schedule_ms.batch", "messages_ms.batch"])
def test_absent_span_reads_nothing(name):
    only_full = [rec("em.run", 101.0, 1.0), rec("rounds.full", 101.2, 0.3, "em.run", 1)]
    assert reader(name)(make_run(only_full)) is None
    assert reader(name)(make_run([])) is None
    assert reader(name)(make_run(resolution(101.0, 1), units=0)) is None


def test_spans_outside_the_window_do_not_count():
    run = make_run(resolution(99.0, 0) + resolution(101.0, 1) + resolution(110.5, 2), units=1)
    assert reader("stage_ms.batch")(run) == pytest.approx(80.0)


def reduced(idle_gaps, window_s=10.0, busy_s=7.0):
    from chipbench.trace_reduce import Reduced

    return Reduced(window_s=window_s, busy_s=busy_s, chips=1, op_seconds={}, kernels={},
                   idle_gaps=idle_gaps)


def test_unattributed_idle_counts_em_run_self_and_no_span_open_together():
    read = reader("unattributed_idle.batch")
    gaps = {"no span open": 0.5, "em.run": 0.2, "rounds.full": 2.0, "rounds.messages": 0.3}
    assert read(make_run(trace=reduced(gaps))) == pytest.approx(0.07)
    assert read(make_run(trace=reduced({"no span open": 0.5}))) == pytest.approx(0.05)
    assert read(make_run(trace=reduced({"em.run": 0.2}))) == pytest.approx(0.02)
    assert read(make_run(trace=reduced({"rounds.full": 3.0}))) == 0.0


def test_unattributed_idle_reads_nothing_without_a_device_trace():
    read = reader("unattributed_idle.batch")
    assert read(make_run(trace=None)) is None
    assert read(make_run(trace=reduced({"no span open": 10.0}, busy_s=0.0))) is None


def test_idle_gaps_are_named_by_the_innermost_stage_span():
    """The trace reduction names a gap inside ``em.run`` but outside every
    stage span ``em.run``, and one inside ``rounds.messages`` by it."""
    from chipbench import trace_reduce

    class Ev:
        def __init__(self, name, start_ns, duration_ns):
            self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines=(), stats=()):
            self.name, self.lines, self.stats = name, list(lines), list(stats)

    class Profile:
        pass

    # 1 s window from the epoch's 1000 s; device ops at 0-0.1 s and 0.9-1.0 s
    t0_ns = 1000 * 10**9
    ops = [Ev("%fusion.1 = f32[4] fusion()", 0, 10**8), Ev("%fusion.2 = f32[4] fusion()", 9 * 10**8, 10**8)]
    pd = Profile()
    pd.planes = [
        Plane("Task Environment", stats=[("profile_start_time", t0_ns),
                                         ("profile_stop_time", t0_ns + 10**9)]),
        Plane("/device:TPU:0", lines=[Line("XLA Ops", ops)]),
    ]
    # perf_counter 0 is the epoch's 1000 s: spans on the trace's clock
    anchor = (t0_ns, 0)
    spans = [
        rec("em.run", 0.0, 1.0),
        rec("rounds.full", 0.1, 0.3, "em.run", 1),
        rec("rounds.messages", 0.15, 0.2, "rounds.full", 2),
    ]
    r = trace_reduce.reduce_profile(pd, anchor, spans)
    # the one gap, 0.1 to 0.9 s, has its midpoint at 0.5 s: em.run's self time
    assert r.idle_gaps == pytest.approx({"em.run": 0.8})
    assert reader("unattributed_idle.batch")(make_run(trace=r)) == pytest.approx(0.8)
    spans[1] = rec("rounds.full", 0.1, 0.7, "em.run", 1)
    spans[2] = rec("rounds.messages", 0.2, 0.6, "rounds.full", 2)
    r = trace_reduce.reduce_profile(pd, anchor, spans)
    assert r.idle_gaps == pytest.approx({"rounds.messages": 0.8})
    assert reader("unattributed_idle.batch")(make_run(trace=r)) == 0.0
