"""Device idle time put on the engine's spans (``chipbench/spans.py``), on
synthetic traces."""

from __future__ import annotations

import pytest

from chipbench import run, spans
from chipbench.trace_reduce import WINDOW, Device, Span, Trace


def _trace(host: list[Span], *busy: list[tuple[float, float]], window=(0, 1000)) -> Trace:
    """A trace whose devices are busy over ``busy`` (one list per device)."""
    devices = [
        Device(f"/device:TPU:{i}", [Span("op", a, b) for a, b in ivs], [])
        for i, ivs in enumerate(busy)
    ]
    host = sorted([Span(WINDOW, *window), *host], key=lambda s: s.start_ns)
    return Trace(devices=devices, host=host)


class _W:
    """The fields of run.TracedWindow that the span readers read."""

    def __init__(self, trace: Trace, iterations: int):
        self.trace, self.iterations = trace, iterations
        self.lo, self.hi = trace.window()


def _idle(t: Trace) -> dict:
    return spans.idle_by_span(t, *t.window())


def test_a_gap_goes_to_its_innermost_span_over_deep_nesting():
    # 600 units, each with its operands and a runtime launch span inside:
    # 1,800 events start inside the schedule before its last gap
    units = [Span(spans.UNIT, 100 + 10 * i, 108 + 10 * i) for i in range(600)]
    host = [
        Span("repro.execute", 0, 7000),
        Span(spans.SCHEDULE, 50, 6900),
        *units,
        *(Span(spans.OPERANDS, u.start_ns, u.start_ns + 2) for u in units),
        *(Span("PjitFunction(f)", u.start_ns + 3, u.start_ns + 7) for u in units),
    ]
    # idle over [6500, 6600] (in the schedule, after every unit) and
    # [104, 106] (under unit 0's launch span: the unit's own time)
    t = _trace(host, [(0, 104), (106, 6500), (6600, 7000)], window=(0, 7000))
    assert _idle(t) == {spans.SCHEDULE: 100, spans.UNIT: 2}


def test_a_unit_keeps_its_self_time_apart_from_its_operands():
    host = [Span(spans.UNIT, 100, 200), Span(spans.OPERANDS, 100, 150)]
    # idle over [110, 130] (building operands) and [160, 180] (the launch)
    t = _trace(host, [(0, 110), (130, 160), (180, 1000)])
    assert _idle(t) == {spans.OPERANDS: 20, spans.UNIT: 20}
    w = _W(t, iterations=2)
    assert run.load_reader("stack_idle_ms")(w) == pytest.approx(20e-6 / 2)
    assert run.load_reader("dispatch_idle_ms")(w) == pytest.approx(20e-6 / 2)


def test_idle_is_clipped_to_the_window_and_averaged_over_devices():
    host = [Span(spans.MERGE, 0, 300)]
    # device 0 idles over [10, 120], of which [50, 120] lies in the window;
    # device 1 idles over [200, 220]
    t = _trace(host, [(0, 10), (120, 1000)], [(0, 200), (220, 1000)], window=(50, 1000))
    assert _idle(t) == {spans.MERGE: pytest.approx((70 + 20) / 2)}
    assert run.load_reader("merge_idle_ms")(_W(t, iterations=5)) == pytest.approx(45e-6 / 5)


def test_idle_under_no_engine_span_is_put_on_none():
    host = [
        Span(spans.PREPARE, 100, 200),
        Span(spans.LOWER, 200, 300),
        Span("PjitFunction(next_centers)", 400, 500),
    ]
    # idle over [120, 140] (prepare), [250, 260] (lower) and [440, 460]
    # (the benchmark's own program: no engine span)
    t = _trace(host, [(0, 120), (140, 250), (260, 440), (460, 1000)])
    assert _idle(t) == {spans.PREPARE: 20, spans.LOWER: 10, None: 20}
    w = _W(t, iterations=1)
    assert run.load_reader("plan_idle_ms")(w) == pytest.approx(30e-6)
    for metric in ("stack_idle_ms", "dispatch_idle_ms", "merge_idle_ms"):
        assert run.load_reader(metric)(w) == 0.0


def test_a_program_without_engine_spans_reports_nothing():
    t = _trace([Span("PjitFunction(f)", 0, 500)], [(0, 100), (200, 1000)])
    assert _idle(t) == {}
    for metric in ("plan_idle_ms", "stack_idle_ms", "dispatch_idle_ms", "merge_idle_ms"):
        assert run.load_reader(metric)(_W(t, iterations=3)) is None


def test_innermost_sweeps_ascending_times_over_nested_spans():
    s = [Span("a", 0, 100), Span("b", 10, 50), Span("c", 20, 30), Span("d", 60, 70)]
    assert spans.innermost(s, [5, 10, 25, 30, 40, 55, 65, 100, 101]) == [
        "a", "b", "c", "c", "b", "a", "d", "a", None,
    ]


def test_the_readers_know_the_engines_span_names():
    from repro.api import profile

    assert {spans.PREPARE, spans.LOWER, spans.SCHEDULE, spans.UNIT, spans.OPERANDS,
            spans.MERGE} == {
        profile.SPAN_PREPARE, profile.SPAN_LOWER, profile.SPAN_SCHEDULE,
        profile.SPAN_UNIT, profile.SPAN_OPERANDS, profile.SPAN_MERGE,
    }
    assert profile.SPAN_EXECUTE.startswith(spans.PREFIX)
