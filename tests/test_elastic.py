"""Elastic ClusterExecutor — work stealing, autoscaling, chaos harness.

The acceptance contract of DESIGN.md §15:

* under a seeded :class:`ChaosSchedule` (kills + stragglers + grow/shrink
  between rounds) every run stays bit-identical to LocalExecutor, and the
  ``steals`` / ``retries`` / ``scale_events`` report counters reconcile
  EXACTLY against the executor's event logs — one log entry per billed
  event, no slop;
* a straggler (one worker slowed via the fault hook) triggers work
  stealing (``steals > 0``) with zero retries: a steal is a scheduling
  decision, not a failure;
* planned scale-down drains through the same requeue/replay path as a
  kill — bit-identical results, ``retries == 0`` (attempts refunded),
  ``scale_events`` billed;
* the heartbeat debouncer counts only *observed* silence, so a stalled
  driver (GC pause, laptop sleep) can no longer bury idle workers;
* ``_SchedulerState`` ownership invariants hold under arbitrary
  assign/steal/kill/preempt/complete interleavings: every unit completes
  exactly once, a live claim can never be doubled, attempts never go
  negative;
* no ``/dev/shm`` segment outlives any executor, and every dispatch pin
  is released exactly once (``ShmStore.pinned_segments()`` is empty once
  a run settles).

The CI ``elastic-chaos-lane`` job runs exactly this module with
``REPRO_CLUSTER_LOG_DIR`` set, uploading per-worker logs and junit on
failure and asserting ``/dev/shm`` is clean afterwards.

All block functions are module-level: ClusterExecutor workers are spawned
processes and must re-import them by qualified name.
"""

from __future__ import annotations

import os
import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    ChaosSchedule,
    ClusterExecutor,
    Collection,
    FaultPlan,
    LocalExecutor,
    SplIter,
    shm_available,
)
from repro.api.autotune import CostModel, should_steal, steal_cost_estimate
from repro.api.executors import _SchedulerState, _Unit
from repro.api.shm import leaked_segments
from repro.core.apps.histogram import histogram
from repro.core.apps.kmeans import kmeans
from repro.core.blocked import BlockedArray, round_robin_placement

try:  # optional in the execution environment; CI installs it
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised where hypothesis is absent
    HAVE_HYPOTHESIS = False

LOG_DIR = os.environ.get("REPRO_CLUSTER_LOG_DIR")  # CI chaos lane artifacts
POL = SplIter(partitions_per_location=4)
CHAOS_SEEDS = (11, 23, 47)  # the CI lane's fixed, replayable seeds
needs_shm = pytest.mark.skipif(
    not shm_available(), reason="host has no POSIX shared memory"
)


def _cluster(**kw) -> ClusterExecutor:
    kw.setdefault("log_dir", LOG_DIR)
    return ClusterExecutor(**kw)


def _segment_prefix(ex: ClusterExecutor) -> str | None:
    """The executor's own shm segment prefix, or None without a data plane.

    Leak checks are scoped to it: pools of other test files running at the
    same time keep their segments live under the global prefix.
    """
    return ex._shm.prefix if ex._shm is not None else None


def _blocked(a, block_rows=256, locs=2) -> BlockedArray:
    return BlockedArray.from_array(
        jnp.asarray(a), block_rows, num_locations=locs, policy=round_robin_placement
    )


@pytest.fixture(scope="module")
def points() -> BlockedArray:
    rng = np.random.default_rng(0)
    return _blocked(rng.random((2048, 4)).astype(np.float32))


def identical(a, b) -> bool:
    return bool(jnp.all(jnp.equal(a, b)))


# -- module-level block fns for the mid-run preemption plan ------------------


def _partial(b, c):
    return (b * c).sum(axis=0)


def _combine(a, b):
    return a + b


# ---------------------------------------------------------------------------
# ChaosSchedule: seeded, replayable fault + elasticity schedules
# ---------------------------------------------------------------------------


class TestChaosSchedule:
    def test_deterministic(self):
        for seed in CHAOS_SEEDS:
            a, b = ChaosSchedule(seed=seed), ChaosSchedule(seed=seed)
            assert a.fault_plan() == b.fault_plan()
            assert a.actions() == b.actions()

    def test_seeds_differ(self):
        plans = {ChaosSchedule(seed=s).fault_plan() for s in range(16)}
        assert len(plans) > 1  # the seed actually steers the schedule

    def test_first_round_unscaled_and_shrink_never_outruns_growth(self):
        for seed in range(32):
            acts = ChaosSchedule(seed=seed, rounds=6).actions()
            assert acts[0] == "none"
            grown = 0
            for a in acts:
                grown += {"grow": 1, "shrink": -1}.get(a, 0)
                assert grown >= 0

    def test_kill_and_slow_target_different_workers(self):
        for seed in range(32):
            plan = ChaosSchedule(seed=seed).fault_plan()
            killed = {w for w, _ in plan.kill_after}
            slowed = {w for w, _ in plan.slow}
            assert not (killed & slowed)


# ---------------------------------------------------------------------------
# the chaos matrix: kills + stragglers + grow/shrink, bit-identical + exact
# accounting, zero leaked segments  (CI: elastic-chaos-lane)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_chaos_rounds_bit_identical_with_exact_accounting(points, seed):
    cs = ChaosSchedule(seed=seed, rounds=3)
    ref, _ = histogram(points, bins=8, policy=POL)
    ex = _cluster(fault_plan=cs.fault_plan(), steal=True, max_workers=8)
    prefix = _segment_prefix(ex)
    applied = 0
    reports = []
    try:
        for action in cs.actions():
            if action == "grow":
                applied += ex.grow() is not None
            elif action == "shrink":
                applied += ex.shrink() is not None
            h, rep = histogram(points, bins=8, policy=POL, executor=ex)
            assert identical(h, ref)
            reports.append(rep)
        # the accounting contract: counters reconcile exactly vs the logs
        assert sum(r.steals for r in reports) == len(ex.steal_log)
        assert sum(r.retries for r in reports) == len(ex.retry_log)
        assert len(ex.scale_log) == applied
        if cs.fault_plan().kill_after:
            assert len(ex.retry_log) >= 1  # the kill really fired
    finally:
        ex.close()
    assert prefix is None or leaked_segments(prefix) == []


# ---------------------------------------------------------------------------
# the straggler: one slowed worker -> steals > 0, zero retries, identical
# ---------------------------------------------------------------------------


def test_straggler_triggers_steals_bit_identical(points):
    ref = kmeans(points, k=4, iters=3, policy=POL)
    ex = _cluster(fault_plan=FaultPlan(slow=((0, 0.05),)), steal=True)
    prefix = _segment_prefix(ex)
    try:
        res = kmeans(points, k=4, iters=3, policy=POL, executor=ex)
        steals = sum(r.steals for r in res.reports)
        assert steals > 0  # the straggler's queue really was raided
        assert steals == len(ex.steal_log)  # exact, not approximate
        assert identical(res.centers, ref.centers)
        # a steal is a scheduling decision, not a failure
        assert sum(r.retries for r in res.reports) == 0
        assert ex.retry_log == []
        if ex._shm is not None:
            # every dispatch pin (including the voided victim dispatches)
            # was released exactly once: nothing stays pinned at rest
            assert ex._shm.pinned_segments() == {}
    finally:
        ex.close()
    assert prefix is None or leaked_segments(prefix) == []


def test_steal_disabled_by_default(points):
    ex = _cluster(fault_plan=FaultPlan(slow=((0, 0.02),)))
    try:
        res = kmeans(points, k=4, iters=2, policy=POL, executor=ex)
        assert sum(r.steals for r in res.reports) == 0
        assert ex.steal_log == []
    finally:
        ex.close()


# ---------------------------------------------------------------------------
# planned scale-down == deliberate preemption through the replay path
# ---------------------------------------------------------------------------


def test_midrun_preemption_is_bit_identical_and_free_of_retries():
    """Shrink a worker with units in flight: the drain is the kill path,
    but attempts are refunded and nothing bills retries."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.random((512, 8), np.float32))
    c = jnp.ones((8,))

    def plan():
        return (
            Collection.from_array(x, block_rows=64, num_locations=2)
            .split(SplIter(partitions_per_location=2))
            .map_blocks(_partial, extra_args=(c,))
            .reduce(_combine)
        )

    ref = plan().compute(executor=LocalExecutor())
    # worker 0 is slowed so its queue is provably non-empty at shrink time
    ex = _cluster(fault_plan=FaultPlan(slow=((0, 0.1),)))
    prefix = _segment_prefix(ex)
    try:
        fut = plan().compute_async(executor=ex)
        assert ex.shrink(0) == 0  # preempt the busy owner mid-run
        res = fut.result()
        assert identical(res.value, ref.value)
        assert res.report.retries == 0 and ex.retry_log == []
        assert res.report.scale_events == 1
        assert ex.scale_log == [{"event": "shrink", "worker": 0}]
    finally:
        ex.close()
    assert prefix is None or leaked_segments(prefix) == []


def test_grow_shrink_between_runs(points):
    ref, _ = histogram(points, bins=8, policy=POL)
    ex = _cluster(steal=True, max_workers=8)
    prefix = _segment_prefix(ex)
    try:
        h0, _ = histogram(points, bins=8, policy=POL, executor=ex)
        wid = ex.grow()
        assert wid is not None and wid in ex.workers_alive()
        h1, rep1 = histogram(points, bins=8, policy=POL, executor=ex)
        assert ex.shrink() == wid  # the idle roamer retires first
        assert wid not in ex.workers_alive()
        h2, rep2 = histogram(points, bins=8, policy=POL, executor=ex)
        assert identical(h0, ref) and identical(h1, ref) and identical(h2, ref)
        assert rep1.retries == 0 and rep2.retries == 0
        assert [e["event"] for e in ex.scale_log] == ["grow", "shrink"]
    finally:
        ex.close()
    assert prefix is None or leaked_segments(prefix) == []


def test_grow_respects_max_workers():
    ex = _cluster(max_workers=1)
    try:
        assert ex.grow() is not None  # pool empty: first roamer fits
        assert ex.grow() is None  # at the ceiling
        assert len(ex.workers_alive()) == 1
    finally:
        ex.close()


def test_autoscaler_grows_under_backlog(points):
    ref, _ = histogram(points, bins=8, policy=POL)
    ex = _cluster(autoscale=True, scale_up_backlog=1, max_workers=6)
    prefix = _segment_prefix(ex)
    try:
        reports = []
        for _ in range(2):
            h, rep = histogram(points, bins=8, policy=POL, executor=ex)
            assert identical(h, ref)
            reports.append(rep)
        assert any(e["event"] == "grow" for e in ex.scale_log)
        # autoscaler events happen inside runs, so report sums reconcile
        assert sum(r.scale_events for r in reports) == len(ex.scale_log)
        assert sum(r.retries for r in reports) == 0
    finally:
        ex.close()
    assert prefix is None or leaked_segments(prefix) == []


# ---------------------------------------------------------------------------
# heartbeat debounce: a stalled driver must not bury idle workers
# ---------------------------------------------------------------------------


def test_stalled_driver_does_not_bury_idle_workers(points):
    """Regression: staleness used to be wall-clock since the last
    heartbeat, so a driver that did not pump for heartbeat_timeout_s
    (GC pause, laptop sleep, a long in-process merge) declared every
    idle worker hung and respawned the pool.  The debouncer counts only
    *observed* silence — time the driver actually spent pumping — capped
    per check, so a stall of any length adds at most one capped tick."""
    ref, _ = histogram(points, bins=8, policy=POL)
    ex = _cluster()
    try:
        histogram(points, bins=8, policy=POL, executor=ex)
        alive = ex.workers_alive()
        assert alive
        # simulate a 500s driver stall: both clocks say "ancient"
        before = dict(ex._silence)
        ex._last_pump -= 500.0
        for wid in list(ex._last_hb):
            ex._last_hb[wid] -= 500.0
        ex._check_workers()
        assert ex.workers_alive() == alive  # nobody buried
        # the stall contributed at most one capped tick of silence
        cap = max(ex.poll_s, ex.heartbeat_s) * 4
        assert all(
            s - before.get(wid, 0.0) <= cap + 1e-6
            for wid, s in ex._silence.items()
        )
        h, rep = histogram(points, bins=8, policy=POL, executor=ex)
        assert identical(h, ref) and rep.retries == 0
    finally:
        ex.close()


def test_truly_silent_worker_is_still_buried(points):
    """The debouncer must not break real hang detection: a muted worker
    (replies suppressed by the fault hook) accumulates observed silence
    across pumps and exceeds the timeout."""
    ref, _ = histogram(points, bins=8, policy=POL)
    ex = _cluster(
        fault_plan=FaultPlan(mute_after=((0, 2),)), heartbeat_timeout_s=2.0
    )
    try:
        h, rep = histogram(points, bins=8, policy=POL, executor=ex)
        assert identical(h, ref)
        assert rep.retries >= 1  # the mute was detected and replayed
        assert len(ex.retry_log) == rep.retries
    finally:
        ex.close()


# ---------------------------------------------------------------------------
# the steal cost gate (autotune)
# ---------------------------------------------------------------------------


class TestStealCostGate:
    def test_fitted_model_waits_vs_fetch(self):
        model = CostModel(c0=0.0, c1=0.01, c2=0.0)
        wait, fetch = steal_cost_estimate(model, queued_tasks=4, span=1)
        assert wait == pytest.approx(0.04)
        assert fetch >= 0.01  # one extra dispatch to the thief
        assert should_steal(model, queued_tasks=4)
        assert not should_steal(model, queued_tasks=0)

    def test_bytes_bite_only_off_the_shm_plane(self):
        model = CostModel(c0=0.0, c1=0.001, c2=0.0)
        # descriptors (shm on): cheap fetch, steal approved
        assert should_steal(model, queued_tasks=8, operand_bytes=0)
        # raw operands over a pipe: fetch dwarfs the wait, steal rejected
        assert not should_steal(
            model, queued_tasks=8, operand_bytes=1 << 30
        )

    def test_fallback_profile_estimate(self):
        wait, fetch = steal_cost_estimate(
            None, queued_tasks=3, fallback_task_s=0.2
        )
        assert wait == pytest.approx(0.6)
        assert should_steal(None, queued_tasks=3, fallback_task_s=0.2)


# ---------------------------------------------------------------------------
# _SchedulerState ownership invariants (the property suite)
# ---------------------------------------------------------------------------


def _make_state(n=6):
    units = [
        _Unit(index=i, location=i % 2, tasks=(), run=None) for i in range(n)
    ]
    units.append(
        _Unit(
            index=n, location=-1, tasks=(), run=None,
            deps=tuple(range(n)), kind="merge",
        )
    )
    return _SchedulerState(units), units


class TestOwnershipInvariants:
    def test_live_double_claim_raises(self):
        state, units = _make_state()
        state.assign(units[0], "w1")
        with pytest.raises(RuntimeError, match="double-claimed"):
            state.assign(units[0], "w2")
        state.assign(units[0], "w1")  # same owner re-assign is idempotent

    def test_assign_after_completion_raises(self):
        state, units = _make_state()
        state.assign(units[0], "w1")
        state.complete(units[0], 0)
        with pytest.raises(RuntimeError, match="after completion"):
            state.assign(units[0], "w2")

    def test_release_moves_ownership_and_refunds_the_attempt(self):
        state, units = _make_state()
        state.assign(units[0], "w1")
        assert state.release(units[0])  # the steal grant
        assert units[0].index not in state.owner
        assert state.attempts[units[0].index] == 0  # refunded
        state.assign(units[0], "w2")  # the thief's claim is legal
        assert state.attempts[units[0].index] == 1  # net zero for the steal

    def test_release_is_stale_safe(self):
        state, units = _make_state()
        assert not state.release(units[0])  # never owned
        state.assign(units[0], "w1")
        state.complete(units[0], 0)
        assert not state.release(units[0])  # completed: grant is stale

    def test_requeue_then_reassign(self):
        state, units = _make_state()
        state.assign(units[0], "w1")
        state.assign(units[1], "w1")
        state.complete(units[1], 1)
        lost = state.requeue("w1")
        assert [u.index for u in lost] == [0]  # completed unit not replayed
        state.assign(units[0], "w2")  # post-death claim is legal

    def test_refund_never_goes_negative(self):
        state, units = _make_state()
        state.refund_attempt(0)
        assert state.attempts[0] == 0
        state.assign(units[0], "w1")
        state.refund_attempt(0)
        state.refund_attempt(0)
        assert state.attempts[0] == 0

    def _chaos_run(self, rng: random.Random, n=6, steps=200):
        """Drive one seeded interleaving of assign / steal / kill /
        preempt / complete; return completion counts per unit."""
        state, units = _make_state(n)
        owners = ["w0", "w1", "w2"]
        completed = [0] * len(units)
        for _ in range(steps):
            op = rng.choice(("assign", "steal", "kill", "preempt", "complete"))
            u = units[rng.randrange(len(units))]
            if op == "assign":
                prev = state.owner.get(u.index)
                owner = rng.choice(owners)
                if state.is_done(u.index) or (prev is not None and prev != owner):
                    with pytest.raises(RuntimeError):
                        state.assign(u, owner)
                else:
                    state.assign(u, owner)
            elif op == "steal":
                before = state.attempts[u.index]
                if state.release(u):
                    assert state.attempts[u.index] == max(0, before - 1)
                    state.assign(u, rng.choice(owners))  # thief re-claims
            elif op == "kill":
                owner = rng.choice(owners)
                for lost in state.requeue(owner):
                    assert not state.is_done(lost.index)
                    state.assign(lost, rng.choice(owners))  # survivor replay
            elif op == "preempt":
                if state.release(u):
                    state.assign(u, rng.choice(owners))
            elif op == "complete" and u.index in state.owner:
                if not state.is_done(u.index):
                    state.complete(u, u.index)
                    completed[u.index] += 1
            assert all(v >= 0 for v in state.attempts.values())
        # drain: everything completes exactly once, whatever happened above
        for u in units:
            if not state.is_done(u.index):
                if u.index not in state.owner:
                    state.assign(u, "w0")
                state.complete(u, u.index)
                completed[u.index] += 1
            assert state.complete(u, -1) == []  # duplicates are dropped
        assert completed == [1] * len(units)
        assert state.done.is_set()

    def test_seeded_interleavings(self):
        """Deterministic fallback for environments without hypothesis —
        the same invariants over a fixed fan of seeds."""
        for seed in range(25):
            self._chaos_run(random.Random(seed))

    if HAVE_HYPOTHESIS:

        @settings(max_examples=200, deadline=None)
        @given(st.integers(min_value=0, max_value=2**31 - 1))
        def test_property_interleavings(self, seed):
            self._chaos_run(random.Random(seed))

    else:  # pragma: no cover - the gated twin of the property test

        @pytest.mark.skip(reason="hypothesis not installed")
        def test_property_interleavings(self):
            pass
