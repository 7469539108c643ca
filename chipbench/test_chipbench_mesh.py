"""The four-chip k-means cell: its data maker, reference and comparison on
four forced CPU devices, and its trace readers on synthetic events.

The device checks run in one child process (``chipbench/_mesh_child.py``),
so this process keeps seeing one device; each check is a test case here.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import peaks, roofline, run
from chipbench.trace_reduce import WINDOW, Device, Span, Trace

ROOT = Path(__file__).resolve().parents[1]
CHILD_CHECKS = [
    "make_data_places_and_matches_one_device",
    "reference_passes_the_engine_and_refuses_the_control",
    "fault_none",
    "fault_stale",
    "fault_half",
    "fault_altered",
    "fault_one_rank",
]


@pytest.fixture(scope="module")
def child(tmp_path_factory) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("cache")))
    out = subprocess.run([sys.executable, "chipbench/_mesh_child.py"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("check", CHILD_CHECKS)
def test_kmeans_mesh_on_four_devices(child, check):
    assert child.get(check) == "ok", child.get(check)


class _W:
    """The fields of run.TracedWindow that the device-trace readers read."""

    def __init__(self, trace: Trace, iterations: int = 2, app: str = "kmeans_mesh"):
        self.trace, self.iterations, self.app = trace, iterations, app
        self.lo, self.hi = trace.window()


def _trace(*ops_per_device: list[Span]) -> Trace:
    devices = [Device(f"/device:TPU:{i}", list(ops), []) for i, ops in enumerate(ops_per_device)]
    return Trace(devices=devices, host=[Span(WINDOW, 100, 200)])


def test_collective_ms_is_the_mean_over_devices_per_iteration():
    gather = "%all-gather.2 = f32[4,10,20]{2,1,0} all-gather(f32[1,10,20]{2,1,0} %p), dimensions={0}"
    reduce_ = "%all-reduce = f32[40]{0} all-reduce(f32[40]{0} %dus), to_apply=%add"
    kernel = "%custom-call.3 = (f32[10,20]{1,0}) custom-call(f32[20,1562500]{1,0} %b)"
    t = _trace(
        # device 0: a gather of 10 ns, a reduce clipped to the window (150 of
        # it before), and a kernel that does not count
        [Span(gather, 120, 130), Span(reduce_, 50, 105), Span(kernel, 130, 190)],
        # device 1: async halves named by their instructions only, 4 + 6 ns
        [Span("all-gather-start.1", 140, 144), Span("all-gather-done.1", 150, 156)],
        [], [],
    )
    read = run.load_reader("collective_ms")
    # (10 + 5 + 10 + 0 + 0) ns over 4 devices, over 2 iterations, in ms
    assert read(_W(t)) == pytest.approx(25 / 4 / 2 * 1e-6)


def test_collective_ms_reads_nothing_where_no_collective_ran():
    t = _trace([Span("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 120, 130)],
               [Span("all-gather-start.1", 10, 20)])   # before the window
    read = run.load_reader("collective_ms")
    assert read(_W(t)) is None
    assert read(_W(_trace([], []))) is None


def test_the_mesh_pass_roofline_divides_the_pass_over_four_chips():
    from chipbench.apps import kmeans_mesh

    cfg = json.loads((ROOT / "chipbench" / "configs" / "kmeans-hibench-gigantic.json").read_text())
    n = cfg["locations"] * cfg["blocks_per_location"] * cfg["rows_per_block"]
    assert n == cfg["rows"] == cfg["source_settings"]["num_of_samples"] == 200_000_000
    assert cfg["chips"] == cfg["locations"] == 4
    nbytes, flops = kmeans_mesh.pass_work(cfg)
    least, bound = roofline.least_pass_s(nbytes, flops, 4, peaks.peak_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(16.0e9 / (4 * 819e9), rel=1e-3)
    # the single-chip reader stays silent on this cell, and this one on the others
    t = _trace([Span("fusion.1", 120, 130)])
    assert run.load_reader("kmeans_pass_roofline")(_W(t)) is None
    assert run.load_reader("kmeans_mesh_pass_roofline")(_W(t, app="kmeans")) is None
