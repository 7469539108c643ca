"""The plain references and the comparison accept the engine's answers and
refuse the same answers computed one precision lower.

The limits are the configurations' own; only the row counts are cut, to
what a test run holds.  k-means needs about a million rows for the
three-pass control to move rows across a center's boundary.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SEED = 2**31 + 11


def _cfg(name: str, **sizes) -> dict:
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())
    cfg.update(sizes)
    return cfg


def _engine_answers(app, cfg, policy, n, data=None):
    from repro.api import engine

    data = app.make_data(cfg, SEED) if data is None else data
    answers = []
    with engine("local") as ex:
        loop = app.Loop(cfg, data, policy, ex, SEED)
        for _ in range(n):
            answer, _ = loop.call()
            answers.append(answer)
            loop.carry(answer)
    return data, answers


@pytest.fixture(scope="module")
def kmeans_case():
    from chipbench.apps import kmeans
    from repro.api import SplIter

    cfg = _cfg("kmeans-hibench-large", locations=4, blocks_per_location=2, rows_per_block=262_144)
    data, answers = _engine_answers(kmeans, cfg, SplIter(), 3)
    return kmeans, cfg, data, answers


def test_kmeans_engine_answers_pass(kmeans_case):
    kmeans, cfg, data, answers = kmeans_case
    worst, failed = kmeans.check(cfg, data, answers)
    assert failed == 0, worst


@pytest.mark.parametrize("passes", [3, 1], ids=["control-3-passes", "default-1-pass"])
def test_kmeans_lower_precision_fails(kmeans_case, passes):
    kmeans, cfg, data, _ = kmeans_case
    control = kmeans.control_answers(cfg, data, SEED, 3, passes=passes)
    worst, failed = kmeans.check(cfg, data, control)
    assert failed > 0, worst
    assert worst["moved_ppm"] > cfg["limits"]["moved_ppm"]


def test_kmeans_baseline_answers_pass(kmeans_case):
    from repro.api import Baseline

    kmeans, cfg, data, _ = kmeans_case
    _, answers = _engine_answers(kmeans, cfg, Baseline(), 2, data)
    worst, failed = kmeans.check(cfg, data, answers)
    assert failed == 0, worst


def test_kmeans_comparison_counts_moved_rows_and_center_gaps():
    import numpy as np

    from chipbench.apps import kmeans

    ref_sums = np.array([[2.0, 2.0], [9.0, 9.0]])
    ref_counts = np.array([2.0, 3.0])
    # one row of value 1 counted under the second center instead of the first
    got = kmeans.compare(ref_sums - [[1, 1], [-1, -1]], ref_counts - [1, -1],
                         ref_sums, ref_counts, rows=5)
    assert got["moved_ppm"] == pytest.approx(1 / 5 * 1e6)
    assert got["center_err"] == pytest.approx(abs(10 / 4 - 3.0))
    nan = kmeans.compare(ref_sums * np.nan, ref_counts, ref_sums, ref_counts, rows=5)
    assert nan["center_err"] == float("inf")
