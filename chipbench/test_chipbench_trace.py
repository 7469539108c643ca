"""Trace reduction, pass rooflines and the peaks table, on synthetic events."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import peaks, roofline
from chipbench.trace_reduce import (
    ITERATION,
    WINDOW,
    Device,
    Span,
    Trace,
    from_profile,
    gaps,
    largest_array,
    union,
)

ROOT = Path(__file__).resolve().parents[1]


def _trace() -> Trace:
    """Two devices over a 100 ns window [100, 200].

    Device 0: two overlapping ops and one that starts before the window,
    busy over [100, 130] and [150, 170] = 50 ns; the first program reads
    4096 x 20 data, the second gathers partials of 8 x 21.  Device 1: one op
    over the whole window and beyond, busy 100 ns, named without its HLO.
    """
    d0 = Device(
        "/device:TPU:0",
        ops=[
            Span("%fusion.1 = f32[8,21]{1,0} fusion(f32[4096,20]{1,0:T(8,128)} %p0), kind=kLoop",
                 90, 120),
            Span("%copy.2 = f32[20,4096]{1,0} copy(f32[4096,20]{1,0} %p1)", 110, 130),
            Span("%all-gather.3 = f32[2,8,21]{2,1,0} all-gather(f32[1,8,21]{2,1,0} %p2), "
                 "dimensions={0}", 150, 170),
        ],
        modules=[Span("jit_stack(4)", 85, 131), Span("jit_fold(5)", 149, 171)],
    )
    d1 = Device("/device:TPU:1", ops=[Span("fusion.1", 50, 250)], modules=[Span("jit_x", 50, 250)])
    host = sorted([
        Span(WINDOW, 100, 200),
        Span(ITERATION, 100, 140),
        Span(ITERATION, 145, 200),
        Span("PjitFunction(stack)", 128, 139),
        Span("ParseArguments", 131, 133),
    ], key=lambda s: s.start_ns)
    return Trace(devices=[d0, d1], host=host)


def test_union_merges_overlaps_and_clips_to_the_window():
    spans = [Span("a", 0, 10), Span("b", 5, 15), Span("c", 20, 30), Span("d", 28, 29)]
    assert union(spans, 0, 100) == [(0, 15), (20, 30)]
    assert union(spans, 8, 25) == [(8, 15), (20, 25)]
    assert gaps([(0, 15), (20, 30)], 0, 40) == [(15, 20), (30, 40)]
    assert gaps([], 3, 7) == [(3, 7)]


def test_busy_and_idle_share_average_over_devices():
    t = _trace()
    lo, hi = t.window()
    assert (lo, hi) == (100, 200)
    assert t.busy_ns(lo, hi) == pytest.approx((50 + 100) / 2)
    assert t.idle_share(lo, hi) == pytest.approx((0.5 + 0.0) / 2)


PARTIALS = 2 * (8 * 21)   # one 8 x 21 partial for each of two blocks


def test_program_time_leaves_out_programs_that_read_no_data():
    t = _trace()
    # device 0: jit_stack clipped to [100, 131] = 31, jit_fold (partials
    # only) left out; device 1: its program names no array, so it counts
    keep = lambda n: roofline.reads_data(n, PARTIALS)  # noqa: E731
    assert t.module_ns(100, 200, keep) == pytest.approx((31 + 100) / 2)


def test_a_merge_is_told_from_a_pass_by_size_not_by_name():
    stack = "%bid.1 = f32[1,{rows},20]{{2,1,0}} broadcast_in_dim(f32[{rows},20]{{1,0}} %p0)"
    dev = Device(
        "/device:TPU:0",
        ops=[Span(stack.format(rows=4096), 0, 10), Span(stack.format(rows=8), 20, 22)],
        modules=[Span("jit_broadcast_in_dim(7)", 0, 10), Span("jit_broadcast_in_dim(7)", 20, 22)],
    )
    t = Trace(devices=[dev], host=[])
    keep = lambda n: roofline.reads_data(n, PARTIALS)  # noqa: E731
    assert t.module_ns(0, 30, keep) == 10
    # an op that names no array counts, so a pass is never left out for want of a name
    dev.ops[1] = Span("fusion.9", 20, 22)
    assert t.module_ns(0, 30, keep) == 12


@pytest.mark.parametrize("text, largest", [
    ("%f = f32[8,21]{1,0} fusion(f32[4096,20]{1,0:T(8,128)} %p0)", 4096 * 20),
    ("%t = (f32[10,20]{1,0}, s32[10]{0}) custom-call(bf16[16,156250,20] %a, pred[] %b)",
     16 * 156250 * 20),
    ("%c = f8e4m3fn[3,5]{1,0} convert(u8[7] %x)", 15),
    ("%p = pred[] parameter(0)", 1),
    ("fusion.12", None),
])
def test_largest_array_reads_result_and_operand_shapes(text, largest):
    assert largest_array(text) == largest


def test_breakdown_names_ops_by_program_and_gaps_by_host_activity():
    t = _trace()
    ops = dict(map(tuple, t.top_ops(100, 200)))
    assert ops == {
        "jit_x/fusion.1": pytest.approx(100e-9 / 2),
        # clipped to the window
        "jit_stack/fusion.1 fusion f32[8,21]{1,0}": pytest.approx(20e-9 / 2),
        "jit_stack/copy.2 copy f32[20,4096]{1,0}": pytest.approx(20e-9 / 2),
        "jit_fold/all-gather.3 all-gather f32[2,8,21]{2,1,0}": pytest.approx(20e-9 / 2),
    }
    idle = dict(map(tuple, t.idle_by_host(100, 200)))
    # device 0 idles over [130, 150] (midpoint 140: the end of the first
    # iteration, inside no runtime span) and [170, 200] (midpoint 185: in
    # the second iteration); device 1 never idles
    assert idle == {"iteration/-": pytest.approx((20e-9 + 30e-9) / 2)}


@pytest.mark.parametrize("busy, label", [
    # gap [130, 140], midpoint 135: inside PjitFunction(stack), after ParseArguments
    ([(100, 130), (140, 200)], "iteration/PjitFunction(stack)"),
    # gap [131, 133], midpoint 132: inside ParseArguments, the innermost
    ([(100, 131), (133, 200)], "iteration/ParseArguments"),
    # gap [140, 146], midpoint 143: between the two iterations
    ([(100, 140), (146, 200)], "between/-"),
])
def test_idle_gap_is_named_by_the_innermost_host_event(busy, label):
    t = _trace()
    t.devices = [Device("/device:TPU:0", [Span("op", a, b) for a, b in busy], [])]
    ((name, seconds),) = t.idle_by_host(100, 200)
    gap = 100 - sum(b - a for a, b in busy)
    assert name == label and seconds == pytest.approx(gap * 1e-9)


_XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 110000 duration_ps: 30000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 40000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(1)" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0 } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "other thread" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 500000 } }
  lines { id: 2 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "noise" } } }
"""


def test_from_profile_reads_device_and_host_lines():
    import jax

    t = from_profile(jax.profiler.ProfileData.from_text_proto(_XSPACE), devices=1)
    assert len(t.devices) == 1
    assert [s.name for s in t.devices[0].ops] == ["fusion.1", "all-reduce.2"]
    assert t.window() == (100, 200)
    assert t.busy_ns(100, 200) == pytest.approx(40)
    assert [s.name for s in t.host] == ["chipbench.window"]
    with pytest.raises(ValueError, match="TPU planes"):
        from_profile(jax.profiler.ProfileData.from_text_proto(_XSPACE), devices=4)


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.peak_for("TPU v5 lite")
    assert (v5e.flops_per_s, v5e.bytes_per_s) == (197e12, 819e9)
    assert "Google Cloud" in v5e.source
    with pytest.raises(peaks.UnknownDevice, match="TPU v9"):
        peaks.peak_for("TPU v9")


def _cfg(name: str) -> dict:
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())


def test_pass_work_from_the_shapes():
    from chipbench.apps import kmeans

    km = _cfg("kmeans-hibench-large")
    nbytes, flops = kmeans.pass_work(km)
    n = 20_000_000
    assert n == km["locations"] * km["blocks_per_location"] * km["rows_per_block"] == km["rows"]
    assert nbytes == 4 * (n * 20 + 10 * 20 + 10)
    assert flops == 2 * n * 10 * 20 + n * 20
    assert kmeans.partial_elements(km) == 10 * 20 + 10
    least, bound = roofline.least_pass_s(nbytes, flops, 1, peaks.peak_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(1.9536e-3, rel=1e-3)
    # four chips read a quarter each
    least4, _ = roofline.least_pass_s(nbytes, flops, 4, peaks.peak_for("TPU v5 lite"))
    assert least4 == pytest.approx(least / 4)
    # a compute-bound pass is bounded by the operations
    assert roofline.least_pass_s(1.0, 197e12, 1, peaks.peak_for("TPU v5 lite")) == (1.0, "compute")


class _W:
    """The fields of run.TracedWindow that a pass roofline reads."""

    def __init__(self, trace, app="kmeans", iterations=2, chips=2):
        self.trace, self.app, self.iterations, self.chips = trace, app, iterations, chips
        self.lo, self.hi = trace.window()
        self.peak = peaks.peak_for("TPU v5 lite")
        self.pass_bytes, self.pass_flops = 819e9 * 2 * 40e-9, 0.0   # 40 ns on 2 chips
        self.partials, self.block_elements = PARTIALS, 4096 * 20


def test_pass_share_divides_the_least_time_by_data_program_time():
    t = _trace()
    w = _W(t)
    # least 40 ns; data programs (31 + 100) / 2 ns per device over 2 iterations
    assert roofline.pass_share(w, "kmeans") == pytest.approx(100 * 40 / (131 / 2 / 2))
    assert roofline.pass_share(w, "knn") is None
    t.devices[0].modules = [m for m in t.devices[0].modules if "fold" in m.name]
    t.devices[1].modules = []
    assert roofline.pass_share(w, "kmeans") is None   # nothing to read, never 0
    # a partial per block as large as a block: size cannot tell merges apart
    w.partials = w.block_elements
    with pytest.raises(ValueError, match="cannot tell"):
        roofline.pass_share(w, "kmeans")
