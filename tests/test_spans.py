"""The engine's host spans and program names, read back from a CPU profile.

Each execute writes ``repro.execute`` ⊃ {``repro.prepare``, ``repro.lower``,
``repro.schedule`` ⊃ {``repro.unit`` ⊃ ``repro.operands``, ``repro.merge``}}
into the profiler's trace (DESIGN.md §9.1), ``repro.operands`` counts the
blocks it copies, and every task program runs under a
``repro_<kind>_<name>`` name.
"""

from __future__ import annotations

import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Baseline, Collection, SplIter, engine
from repro.api.executors import _merge_partials
from repro.api.lowering import MergeSpec, fold_plan, planned_fold, stacked_fold
from repro.api.profile import (
    SPAN_EXECUTE,
    SPAN_LOWER,
    SPAN_MERGE,
    SPAN_OPERANDS,
    SPAN_PREPARE,
    SPAN_SCHEDULE,
    SPAN_UNIT,
)
from repro.core.apps.kmeans import _combine, partial_sum_block
from repro.core.blocked import BlockedArray, round_robin_placement
from repro.core.engine import TaskEngine

WINDOW = "test.window"
LOCATIONS, BLOCKS, ROWS, D, K = 4, 8, 64, 3, 2

#: the span each span nests in
PARENT = {
    SPAN_PREPARE: SPAN_EXECUTE,
    SPAN_LOWER: SPAN_EXECUTE,
    SPAN_SCHEDULE: SPAN_EXECUTE,
    SPAN_UNIT: SPAN_SCHEDULE,
    SPAN_MERGE: SPAN_SCHEDULE,
    SPAN_OPERANDS: SPAN_UNIT,
}


def _data() -> BlockedArray:
    x = jax.random.uniform(jax.random.key(0), (BLOCKS * ROWS, D), jnp.float32)
    return BlockedArray.from_blocks(
        jnp.split(x, BLOCKS), round_robin_placement(BLOCKS, LOCATIONS), LOCATIONS
    )


def _collection(policy, data=None):
    centers = jnp.linspace(0.0, 1.0, K * D, dtype=jnp.float32).reshape(K, D)
    return (
        Collection.from_blocked(_data() if data is None else data)
        .split(policy)
        .map_blocks(partial_sum_block, extra_args=(centers,))
        .reduce(_combine)
    )


def _engine_spans(log_dir: str) -> list[tuple[str, float, float, dict]]:
    """(name, start, end, stats) of the engine's spans on the window's line."""
    (path,) = pathlib.Path(log_dir).rglob("*.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(str(path))
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(e.name == WINDOW for e in events):
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    return [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                        for e in events
                        if e.name.startswith("repro.")
                    ]
    raise AssertionError(f"no host line holds {WINDOW!r}")


def _parent(span, spans):
    """The innermost other span that encloses ``span``, or None."""
    name, a, b, _ = span
    outer = [s for s in spans if s is not span and s[1] <= a and b <= s[2]]
    return max(outer, key=lambda s: s[1], default=None)


@pytest.mark.parametrize("policy, kind", [(Baseline(), "block"),
                                          (SplIter(fusion="scan"), "partition_scan")])
def test_an_execute_writes_its_spans_nested_with_one_execute_id(tmp_path, policy, kind):
    with engine("local") as ex:
        plans = [_collection(policy), _collection(policy)]
        ex.execute(plans[0].plan())  # compile outside the trace
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation(WINDOW):
                for p in plans:
                    jax.block_until_ready(p.compute(executor=ex).value)
        n_tasks = len(ex.lower(plans[0].plan()).tasks)

    spans = _engine_spans(str(tmp_path))
    assert {s[0] for s in spans} == {SPAN_EXECUTE, *PARENT}
    executes = [s for s in spans if s[0] == SPAN_EXECUTE]
    assert len(executes) == 2
    ids = [s[3]["execute"] for s in executes]
    assert len(set(ids)) == 2 and all(s[3]["mode"] == policy.mode_name for s in executes)
    for span in spans:
        parent = _parent(span, spans)
        if span[0] == SPAN_EXECUTE:
            assert parent is None
            continue
        assert parent is not None and parent[0] == PARENT[span[0]], span
        # every span of an execute sits inside it and carries its id
        (top,) = [e for e in executes if e[1] <= span[1] and span[2] <= e[2]]
        assert span[3].get("execute", top[3]["execute"]) == top[3]["execute"]
    for top in executes:
        inside = [s for s in spans if top[1] <= s[1] and s[2] <= top[2]]
        units = [s for s in inside if s[0] == SPAN_UNIT]
        (merge,) = [s for s in inside if s[0] == SPAN_MERGE]
        (lower,) = [s for s in inside if s[0] == SPAN_LOWER]
        assert len(units) == n_tasks == lower[3]["tasks"] == merge[3]["partials"]
        assert {u[3]["kind"] for u in units} == {kind}
        assert {u[3]["location"] for u in units} == set(range(LOCATIONS))
        assert sum(s[0] == SPAN_OPERANDS for s in inside) == n_tasks


@pytest.mark.parametrize("policy, copied", [
    (Baseline(), 0),
    (SplIter(fusion="scan"), BLOCKS // LOCATIONS),   # the run's blocks, stacked
    (SplIter(fusion="pallas"), 0),                   # the blocks go in place
])
def test_the_operands_span_counts_the_blocks_it_copies(tmp_path, policy, copied):
    with engine("local") as ex:
        plan = _collection(policy)
        ex.execute(plan.plan())  # compile outside the trace
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation(WINDOW):
                jax.block_until_ready(plan.compute(executor=ex).value)
        n_tasks = len(ex.lower(plan.plan()).tasks)

    spans = _engine_spans(str(tmp_path))
    assert [s[3]["copied"] for s in spans if s[0] == SPAN_OPERANDS] == [copied] * n_tasks


def test_a_pallas_task_gets_its_blocks_in_place():
    data = _data()
    with engine("local") as ex:
        graph = ex.lower(_collection(SplIter(fusion="pallas"), data).plan())
    for t in graph.tasks:
        run, _centers = t.operands()
        assert all(x is data.blocks[b] for x, b in zip(run, t.block_ids, strict=True))


@pytest.mark.parametrize("policy, program", [
    (Baseline(), "jit_repro_block_partial_sum_block"),
    (SplIter(fusion="scan"), "jit_repro_partition_scan_partial_sum_block"),
    (SplIter(fusion="pallas"), "jit_repro_partition_pallas_partition_kmeans"),
    (SplIter(materialize=True), "jit_repro_partition_materialized_partial_sum_block"),
])
def test_task_programs_lower_under_their_repro_names(policy, program):
    with engine("local") as ex:
        graph = ex.lower(_collection(policy).plan())
        t = graph.tasks[0]
        text = ex.engine.task(t.fn, key=t.key, name=t.name).lower(*t.operands()).as_text()
    assert f"module @{program} " in text


def test_the_merge_program_lowers_as_repro_merge_fold():
    eng = TaskEngine()
    partials = [(jnp.ones((K, D)), jnp.ones((K,))) for _ in range(3)]
    _merge_partials(eng, MergeSpec(_combine, key=("merge", "test")), partials)
    (dispatch,) = eng._cache.values()
    assert "module @jit_repro_merge_fold " in dispatch.lower(*partials).as_text()


def _partials(n: int) -> list[tuple[jax.Array, jax.Array]]:
    """``n`` k-means partials ``(sums (10, 24), counts (10,))``, all distinct."""
    keys = jax.random.split(jax.random.key(1), n)
    return [
        (jax.random.normal(k, (10, 24), jnp.float32),
         jnp.round(jax.random.uniform(k, (10,), jnp.float32) * 1e5))
        for k in keys
    ]


def _round_robin_plan(n: int, locations: int = 8) -> tuple:
    """The fold plan of ``n`` per-block units over round-robin locations."""
    return fold_plan((i, i % locations) for i in range(n))


MERGE_CASES = [
    pytest.param(2, None, id="flat-2"),
    pytest.param(8, None, id="flat-8"),
    pytest.param(128, None, id="flat-128"),
    pytest.param(128, _round_robin_plan(128), id="planned-8x16"),
]


@pytest.mark.parametrize("n, plan", MERGE_CASES)
def test_the_merge_is_bit_identical_to_an_eager_stack_then_the_fold(n, plan):
    partials = _partials(n)
    out = _merge_partials(
        TaskEngine(), MergeSpec(_combine, key=("merge", "test")), partials, plan=plan
    )
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *partials)
    if plan is None:
        fold = stacked_fold(_combine)
    else:
        groups = tuple(members for _, members in plan)
        assert len(groups) == 8 and {len(m) for m in groups} == {16}
        fold = planned_fold(_combine, groups)
    want = jax.jit(fold)(stacked)
    for got, ref in zip(out, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("n, plan", MERGE_CASES)
def test_a_merge_is_one_launch_that_stacks_under_its_trace(monkeypatch, n, plan):
    stack, calls = jnp.stack, []

    def recording_stack(arrays, *args, **kw):
        calls.append(tuple(arrays))
        return stack(arrays, *args, **kw)

    eng = TaskEngine()
    eng.new_report("test")
    partials = _partials(n)
    before = eng.current_report.dispatches
    monkeypatch.setattr(jnp, "stack", recording_stack)
    _merge_partials(eng, MergeSpec(_combine, key=("merge", "test")), partials, plan=plan)
    monkeypatch.undo()
    assert eng.current_report.dispatches == before + 1
    assert eng.current_report.merges == 1
    assert calls and all(
        isinstance(x, jax.core.Tracer) for arrays in calls for x in arrays
    )


def test_a_program_name_leaves_the_cache_and_the_counts_as_they_were():
    def run(name):
        eng = TaskEngine()
        eng.new_report("test")
        f = lambda x: x * 2  # noqa: E731
        first = eng.task(f, key="k", name=name)
        again = eng.task(f, key="k", name=name)
        out = [np.asarray(first(jnp.arange(3.0))), np.asarray(again(jnp.arange(3.0)))]
        return first is again, len(eng._cache), eng.report.as_row(), out

    named, plain = run("repro_block_double"), run(None)
    assert named[:3] == plain[:3] == (True, 1, plain[2])
    assert plain[2]["dispatches"] == 2 and plain[2]["traces"] == 1
    np.testing.assert_array_equal(named[3], plain[3])
