"""The plan-based execution layer: policy equivalence, executors, the shim.

Covers DESIGN.md §7.4/§6: every policy agrees on associative reductions up
to fp reassociation — including ragged tails and partitions_per_location>1
— and ThreadedExecutor is bit-identical to LocalExecutor; plus the
deprecated run_map_reduce shim (warns, matches the new API), the lowering
pass (TaskGraph kinds per fusion knob, Pallas fallback rules), the
MeshExecutor backend, the LRU-bounded prepare cache, stable task keys, and
the persistent threaded worker pool.
"""

import gc
import weakref
from functools import partial

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    Baseline,
    Collection,
    LocalExecutor,
    MeshExecutor,
    PlanError,
    Rechunk,
    SplIter,
    ThreadedExecutor,
    as_policy,
    stable_task_key,
)
from repro.core.blocked import BlockedArray, contiguous_placement, round_robin_placement
from repro.core.engine import run_map_reduce

POLICIES = [
    Baseline(),
    SplIter(),
    SplIter(materialize=True),
    SplIter(partitions_per_location=3),
    SplIter(partitions_per_location=3, materialize=True),
    Rechunk(),
    Rechunk(target_rows=17),
]

# (rows, block_rows, locations, placement) — uniform, ragged tail, ragged with
# many locations, single location, more locations than blocks.
DATASETS = [
    (96, 8, 4, round_robin_placement),
    (97, 12, 3, round_robin_placement),      # ragged tail
    (341, 100, 5, contiguous_placement),     # ragged, uneven fill
    (40, 7, 1, contiguous_placement),        # single location, ragged
    (5, 2, 8, round_robin_placement),        # locations > blocks
]


def _blocked(rows, block_rows, locs, placement, d=3, seed=0):
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(rows, d)).astype(np.float32)
    return pts, BlockedArray.from_array(
        jnp.asarray(pts), block_rows, num_locations=locs, policy=placement
    )


def _moments_fn(b):
    return jnp.sum(b, 0), jnp.sum(b * b, 0), jnp.asarray(b.shape[0], jnp.float32)


def _moments_combine(a, b):
    return a[0] + b[0], a[1] + b[1], a[2] + b[2]


class TestModeEquivalence:
    @pytest.mark.parametrize("ds", DATASETS, ids=lambda d: f"n{d[0]}b{d[1]}l{d[2]}")
    def test_all_policies_agree(self, ds):
        """C4: any policy grouping agrees up to float reassociation."""
        pts, ba = _blocked(*ds)
        ref = (pts.sum(0), (pts * pts).sum(0), np.float32(len(pts)))
        for pol in POLICIES:
            res = (
                Collection.from_blocked(ba)
                .split(pol)
                .map_blocks(_moments_fn)
                .reduce(_moments_combine)
                .compute()
            )
            for got, want in zip(res.value, ref):
                np.testing.assert_allclose(
                    np.asarray(got), want, rtol=2e-4, atol=2e-4, err_msg=repr(pol)
                )
            assert res.report.bytes_moved == 0 or isinstance(pol, Rechunk)

    @pytest.mark.parametrize("ds", DATASETS, ids=lambda d: f"n{d[0]}b{d[1]}l{d[2]}")
    @pytest.mark.parametrize("pol", POLICIES, ids=lambda p: repr(p))
    def test_threaded_identical_to_local(self, ds, pol):
        """Local vs Threaded on the SAME policy must be bit-identical."""
        _, ba = _blocked(*ds)
        plan = (
            Collection.from_blocked(ba)
            .split(pol)
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
        )
        seq = plan.compute(executor=LocalExecutor())
        thr = plan.compute(executor=ThreadedExecutor())
        for a, b in zip(seq.value, thr.value):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert thr.report.dispatches == seq.report.dispatches
        assert thr.report.bytes_moved == seq.report.bytes_moved

    def test_spliter_dispatch_bound(self):
        """C1: spliter dispatches ≤ partitions + ragged-tail extras + merge."""
        _, ba = _blocked(97, 12, 3, round_robin_placement)
        for ppl in (1, 2, 4):
            res = (
                Collection.from_blocked(ba)
                .split(SplIter(partitions_per_location=ppl))
                .map_blocks(_moments_fn)
                .reduce(_moments_combine)
                .compute()
            )
            # ≤ 2 shape-runs per partition (body + tail) + 1 merge.
            assert res.report.dispatches <= 2 * 3 * ppl + 1


class TestExecutorStatefulness:
    def test_rechunk_paid_once_with_persistent_executor(self):
        """C3: the prepare cache bills rechunk traffic exactly once."""
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = LocalExecutor()
        data = Collection.from_blocked(ba).split(Rechunk())
        plan = data.map_blocks(_moments_fn).reduce(_moments_combine)
        first = plan.compute(executor=ex)
        second = plan.compute(executor=ex)
        assert first.report.bytes_moved > 0
        assert second.report.bytes_moved == 0
        assert second.report.dispatches == first.report.dispatches

    def test_traces_attributed_to_paying_report(self):
        """Per-report traces are the delta over the report's window."""
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = LocalExecutor()
        plan = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
        )
        r1 = plan.compute(executor=ex).report
        r2 = plan.compute(executor=ex).report
        assert r1.traces == 2          # partition task + merge task
        assert r2.traces == 0          # cache hits only
        assert ex.engine.traces_total == 2

    def test_scope_accumulates_custom_dispatches(self):
        _, ba = _blocked(40, 7, 1, contiguous_placement)
        ex = LocalExecutor()
        with ex.scope("spliter") as report:
            res = (
                Collection.from_blocked(ba)
                .split(SplIter())
                .map_blocks(_moments_fn)
                .reduce(_moments_combine)
                .compute(executor=ex)
            )
            assert res.report is report
            t = ex.task(lambda v: v * 2, key="double")
            t(jnp.ones(3))
        assert report.dispatches >= 2
        assert report.wall_s > 0


class TestMapPartitions:
    @pytest.mark.parametrize("pol", [Baseline(), SplIter(), SplIter(2), Rechunk()],
                             ids=lambda p: p.mode_name + str(getattr(p, "partitions_per_location", "")))
    def test_views_cover_all_rows_once(self, pol):
        pts, ba = _blocked(97, 12, 3, round_robin_placement)
        views = (
            Collection.from_blocked(ba)
            .split(pol)
            .map_partitions(lambda v: (v.location, v.item_indexes))
            .compute()
            .value
        )
        allidx = np.concatenate([idx for _, idx in views])
        assert sorted(allidx.tolist()) == list(range(97))

    def test_zip_materialized_stays_aligned(self):
        rng = np.random.default_rng(5)
        pts = rng.normal(size=(60, 2)).astype(np.float32)
        lab = np.arange(60, dtype=np.float32)
        xb = BlockedArray.from_array(jnp.asarray(pts), 7, num_locations=3,
                                     policy=round_robin_placement)
        yb = BlockedArray.from_array(jnp.asarray(lab), 7, num_locations=3,
                                     policy=round_robin_placement)
        groups = (
            Collection.zip(Collection.from_blocked(xb), Collection.from_blocked(yb))
            .split(SplIter())
            .map_partitions(lambda v: (v.materialized, v.item_indexes))
            .compute()
            .value
        )
        for (bx, by), idx in groups:
            np.testing.assert_array_equal(np.asarray(by), lab[idx])
            np.testing.assert_array_equal(np.asarray(bx), pts[idx])


class TestPlanValidation:
    def test_reduce_without_map_fails(self):
        _, ba = _blocked(40, 7, 1, contiguous_placement)
        with pytest.raises(PlanError):
            Collection.from_blocked(ba).reduce(lambda a, b: a + b).plan()

    def test_misaligned_zip_fails(self):
        _, a = _blocked(40, 7, 2, contiguous_placement)
        _, b = _blocked(40, 5, 2, contiguous_placement)
        with pytest.raises(PlanError):
            (Collection.zip(Collection.from_blocked(a), Collection.from_blocked(b))
             .map_blocks(_moments_fn).plan())

    def test_describe_names_every_stage(self):
        _, ba = _blocked(40, 7, 2, contiguous_placement)
        text = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
            .plan()
            .describe()
        )
        for token in ("Source", "Split", "MapBlocks", "Reduce", "SplIter"):
            assert token in text

    def test_as_policy_coercion(self):
        assert as_policy("baseline") == Baseline()
        assert as_policy("spliter_mat", partitions_per_location=2) == SplIter(2, True)
        assert as_policy(Rechunk()) == Rechunk()
        with pytest.raises(ValueError):
            as_policy("warp-drive")


class TestDeprecatedShim:
    def test_warns_and_matches_new_api(self):
        pts, ba = _blocked(97, 12, 3, round_robin_placement)
        with pytest.warns(DeprecationWarning, match="run_map_reduce"):
            old_val, old_rep = run_map_reduce(
                [ba], _moments_fn, _moments_combine, mode="spliter"
            )
        new = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
            .compute()
        )
        for a, b in zip(old_val, new.value):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert old_rep.dispatches == new.report.dispatches
        assert old_rep.mode == "spliter"

    @pytest.mark.parametrize("mode", ["baseline", "spliter", "spliter_mat", "rechunk"])
    def test_all_legacy_modes_still_run(self, mode):
        pts, ba = _blocked(96, 8, 4, round_robin_placement)
        with pytest.warns(DeprecationWarning):
            val, rep = run_map_reduce([ba], _moments_fn, _moments_combine, mode=mode)
        np.testing.assert_allclose(
            np.asarray(val[0]), pts.sum(0), rtol=2e-4, atol=2e-4
        )
        assert rep.mode == mode


# ---------------------------------------------------------------------------
# lowering pass: TaskGraph kinds, the fusion knob, Pallas fallback rules
# ---------------------------------------------------------------------------


def _hist_plan(ba, pol, bins=4):
    from repro.core.apps.histogram import histogramdd_block

    fn = partial(histogramdd_block, bins=bins, lo=0.0, hi=1.0)
    return (
        Collection.from_blocked(ba)
        .split(pol)
        .map_blocks(fn)
        .reduce(lambda a, b: a + b)
    )


class TestLoweringFusion:
    def _kinds(self, ex, plan):
        return {t.kind for t in ex.lower(plan.plan()).tasks}

    def test_taskgraph_kinds_follow_fusion_knob(self):
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = LocalExecutor()
        assert self._kinds(ex, _hist_plan(ba, SplIter(fusion="scan"))) == {
            "partition_scan"
        }
        assert self._kinds(ex, _hist_plan(ba, SplIter(fusion="pallas"))) == {
            "partition_pallas"
        }
        # "auto" on a non-TPU backend keeps the compiled scan
        assert self._kinds(ex, _hist_plan(ba, SplIter())) == {"partition_scan"}

    def test_pallas_falls_back_without_kernel(self):
        """fusion="pallas" on an unregistered fn lowers to the scan."""
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = LocalExecutor()
        plan = (
            Collection.from_blocked(ba)
            .split(SplIter(fusion="pallas"))
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
        )
        assert {t.kind for t in ex.lower(plan.plan()).tasks} == {"partition_scan"}
        res = plan.compute(executor=ex)
        ref = plan.compute(executor=LocalExecutor())
        for a, b in zip(res.value, ref.value):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_pallas_falls_back_when_kernel_rejects_shapes(self):
        """The kernel's supports() guard (bins**d too large) → scan."""
        _, ba = _blocked(96, 8, 4, round_robin_placement)  # d=3
        ex = LocalExecutor()
        graph = ex.lower(_hist_plan(ba, SplIter(fusion="pallas"), bins=128).plan())
        assert {t.kind for t in graph.tasks} == {"partition_scan"}

    def test_pallas_histogram_exact_incl_ragged(self):
        """End-to-end C4 under fusion="pallas": exact int counts, ragged
        tails lower per same-shape run (at most one extra task per tail)."""
        _, ba = _blocked(97, 12, 3, round_robin_placement)
        base = _hist_plan(ba, Baseline()).compute()
        for ex in (LocalExecutor(), ThreadedExecutor(), MeshExecutor()):
            res = _hist_plan(ba, SplIter(fusion="pallas")).compute(executor=ex)
            np.testing.assert_array_equal(
                np.asarray(res.value), np.asarray(base.value), err_msg=repr(ex)
            )
            # C1 bound: <= 2 shape runs per partition + 1 merge
            assert res.report.dispatches <= 2 * 3 + 1

    def test_pallas_tasks_get_their_blocks_in_place(self):
        """A partition_pallas task's data operand is its run's block buffers
        themselves, with no stacking copy, while data_shapes still reads the
        run as (nblocks, rows, d); the partition holding the ragged tail
        lowers to two same-shape runs."""
        _, ba = _blocked(97, 12, 3, round_robin_placement)
        graph = LocalExecutor().lower(_hist_plan(ba, SplIter(fusion="pallas")).plan())
        assert {t.kind for t in graph.tasks} == {"partition_pallas"}
        assert len(graph.tasks) == ba.num_locations + 1
        for t in graph.tasks:
            (run,) = t.operands()
            assert all(x is ba.blocks[b] for x, b in zip(run, t.block_ids, strict=True))
            assert t.data_shapes == (((len(run), *run[0].shape), "float32"),)
            assert t.copied == 0

    def test_pallas_dispatch_counts_match_scan(self):
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = LocalExecutor()
        r_scan = _hist_plan(ba, SplIter(fusion="scan")).compute(executor=ex).report
        r_pal = _hist_plan(ba, SplIter(fusion="pallas")).compute(executor=ex).report
        assert r_pal.dispatches == r_scan.dispatches == ba.num_locations + 1

    def test_describe_golden_per_policy(self):
        """Golden strings for TaskGraph.describe(): a lowering regression
        (placement, grouping, fusion kind, merge identity) must show up as
        a readable string diff, not a silent behaviour change."""
        _, ba = _blocked(40, 8, 2, round_robin_placement)

        def moments(b):
            return jnp.sum(b, 0)

        def combine(a, b):
            return a + b

        def describe(pol):
            plan = (
                Collection.from_blocked(ba)
                .split(pol)
                .map_blocks(moments)
                .reduce(combine)
                .plan()
            )
            return LocalExecutor().lower(plan).describe()

        assert describe(Baseline()) == "\n".join([
            "[0] loc=0 block blocks=(0,)",
            "[1] loc=1 block blocks=(1,)",
            "[2] loc=0 block blocks=(2,)",
            "[3] loc=1 block blocks=(3,)",
            "[4] loc=0 block blocks=(4,)",
            "[merge] combine=combine",
        ])
        assert describe(SplIter()) == "\n".join([
            "[0] loc=0 partition_scan blocks=(0, 2, 4)",
            "[1] loc=1 partition_scan blocks=(1, 3)",
            "[merge] combine=combine",
        ])
        assert describe(SplIter(partitions_per_location=2)) == "\n".join([
            "[0] loc=0 partition_scan blocks=(0, 4)",
            "[1] loc=0 partition_scan blocks=(2,)",
            "[2] loc=1 partition_scan blocks=(1,)",
            "[3] loc=1 partition_scan blocks=(3,)",
            "[merge] combine=combine",
        ])
        assert describe(Rechunk()) == "\n".join([
            "[0] loc=0 block blocks=(0,)",
            "[1] loc=1 block blocks=(1,)",
            "[merge] combine=combine",
        ])

    def test_taskgraph_is_placed_and_described(self):
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        graph = LocalExecutor().lower(_hist_plan(ba, SplIter(fusion="pallas")).plan())
        assert graph.locations == (0, 1, 2, 3)
        assert all(t.kernel_name == "partition_histogramdd" for t in graph.tasks)
        text = graph.describe()
        assert "partition_pallas" in text and "merge" in text
        # every block appears exactly once across the graph
        covered = sorted(b for t in graph.tasks for b in t.block_ids)
        assert covered == list(range(ba.num_blocks))


# ---------------------------------------------------------------------------
# MeshExecutor: sharded scheduling agrees with per-task backends
# ---------------------------------------------------------------------------


class TestMeshExecutor:
    @pytest.mark.parametrize("ds", DATASETS, ids=lambda d: f"n{d[0]}b{d[1]}l{d[2]}")
    def test_matches_local_all_policies(self, ds):
        _, ba = _blocked(*ds)
        for pol in POLICIES:
            plan = (
                Collection.from_blocked(ba)
                .split(pol)
                .map_blocks(_moments_fn)
                .reduce(_moments_combine)
            )
            loc = plan.compute(executor=LocalExecutor())
            mesh = plan.compute(executor=MeshExecutor())
            for a, b in zip(mesh.value, loc.value):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                    err_msg=repr(pol),
                )
            # sharded calls never exceed the per-task dispatch count
            assert mesh.report.dispatches <= loc.report.dispatches

    def test_uniform_spliter_is_one_sharded_dispatch(self):
        _, ba = _blocked(96, 8, 4, round_robin_placement)  # 12 uniform blocks
        res = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
            .compute(executor=MeshExecutor())
        )
        assert res.report.dispatches == 1  # all 4 partitions, one sharded call

    def test_map_partitions_fallback_covers_all_rows(self):
        _, ba = _blocked(97, 12, 3, round_robin_placement)
        views = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_partitions(lambda v: (v.location, v.item_indexes))
            .compute(executor=MeshExecutor())
            .value
        )
        allidx = np.concatenate([idx for _, idx in views])
        assert sorted(allidx.tolist()) == list(range(97))

    def test_unreduced_map_falls_back_to_block_order(self):
        pts, ba = _blocked(96, 8, 4, round_robin_placement)
        partials = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_blocks(lambda b: jnp.sum(b, 0))
            .compute(executor=MeshExecutor())
            .value
        )
        assert len(partials) == ba.num_blocks
        np.testing.assert_allclose(
            np.asarray(partials[0]), pts[:8].sum(0), rtol=2e-4, atol=2e-4
        )

    def test_iterative_reuses_compiled_sharded_call(self):
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = MeshExecutor()
        plan = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
        )
        r1 = plan.compute(executor=ex).report
        r2 = plan.compute(executor=ex).report
        assert r1.traces >= 1 and r2.traces == 0
        assert r2.dispatches == r1.dispatches == 1


# ---------------------------------------------------------------------------
# prepare-cache LRU bound (no unbounded dataset pinning)
# ---------------------------------------------------------------------------


class TestPrepareCacheLRU:
    def test_cache_bounded_and_releases_evicted_inputs(self):
        ex = LocalExecutor()
        cap = ex.prepare_cache_size
        refs = []
        for i in range(cap + 4):
            _, ba = _blocked(40, 7, 2, contiguous_placement, seed=i)
            refs.append(weakref.ref(ba))
            (
                Collection.from_blocked(ba)
                .split(SplIter())
                .map_blocks(_moments_fn)
                .reduce(_moments_combine)
                .compute(executor=ex)
            )
            del ba
        assert len(ex._prepare_cache) == cap
        gc.collect()
        # evicted entries no longer pin their datasets; recent ones still do
        assert refs[0]() is None
        assert refs[-1]() is not None

    def test_recently_used_entry_survives_eviction(self):
        ex = LocalExecutor()
        cap = ex.prepare_cache_size
        _, hot = _blocked(40, 7, 2, contiguous_placement, seed=100)
        hot_plan = (
            Collection.from_blocked(hot)
            .split(Rechunk())
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
        )
        first = hot_plan.compute(executor=ex)
        assert first.report.bytes_moved >= 0
        for i in range(cap - 1):  # fill the rest of the cache, touching hot
            _, ba = _blocked(40, 7, 2, contiguous_placement, seed=i)
            (
                Collection.from_blocked(ba)
                .split(SplIter())
                .map_blocks(_moments_fn)
                .reduce(_moments_combine)
                .compute(executor=ex)
            )
            hot_plan.compute(executor=ex)  # LRU touch
        again = hot_plan.compute(executor=ex)
        assert again.report.bytes_moved == 0  # still cached: rechunk not re-billed


# ---------------------------------------------------------------------------
# stable task keys: fresh lambdas / partials must hit the jit cache
# ---------------------------------------------------------------------------


class TestStableTaskKeys:
    def test_fresh_lambdas_hit_jit_cache(self):
        """The historical ("merge", combine) bug: app-level lambdas recreated
        per call must not defeat the jit cache / inflate trace counts."""
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = LocalExecutor()

        def once():
            return (
                Collection.from_blocked(ba)
                .split(SplIter())
                .map_blocks(lambda b: (jnp.sum(b, 0),))
                .reduce(lambda a, b: (a[0] + b[0],))
                .compute(executor=ex)
            )

        r1 = once().report
        r2 = once().report
        assert r1.traces == 2            # partition task + merge, traced once
        assert r2.traces == 0            # fresh lambdas, same stable keys
        assert ex.engine.traces_total == 2

    def test_histogram_app_traces_once_across_calls(self):
        from repro.core.apps.histogram import histogram

        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = LocalExecutor()
        _, r1 = histogram(ba, bins=4, policy=SplIter(), executor=ex)
        _, r2 = histogram(ba, bins=4, policy=SplIter(), executor=ex)
        assert r1.traces == 2 and r2.traces == 0

    def test_partial_statics_distinguish_keys(self):
        from repro.core.apps.histogram import histogramdd_block

        mk = lambda bins: partial(histogramdd_block, bins=bins, lo=0.0, hi=1.0)
        assert stable_task_key(mk(4)) == stable_task_key(mk(4))
        assert stable_task_key(mk(4)) != stable_task_key(mk(8))

    def test_closure_values_distinguish_keys(self):
        def mk(c):
            return lambda a, b: a + b * c

        assert stable_task_key(mk(2.0)) == stable_task_key(mk(2.0))
        assert stable_task_key(mk(2.0)) != stable_task_key(mk(3.0))

    def test_unhashable_closure_falls_back_to_identity(self):
        big = jnp.ones((4,))

        def mk():
            return lambda a: a + big  # closes over an array (unhashable)

        f = mk()
        assert stable_task_key(f) is f


# ---------------------------------------------------------------------------
# threaded executor: persistent per-location worker pool
# ---------------------------------------------------------------------------


class TestThreadedWorkerPool:
    def test_workers_persist_across_runs_and_close(self):
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = ThreadedExecutor()
        plan = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
        )
        plan.compute(executor=ex)
        first = dict(ex._workers)
        assert len(first) == 4           # one worker per location
        plan.compute(executor=ex)
        assert dict(ex._workers) == first  # reused, not respawned
        ex.close()
        assert not ex._workers
        res = plan.compute(executor=ex)    # pool respawns transparently
        ref = plan.compute(executor=LocalExecutor())
        for a, b in zip(res.value, ref.value):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ex.close()

    def test_single_location_runs_inline(self):
        _, ba = _blocked(40, 7, 1, contiguous_placement)
        ex = ThreadedExecutor()
        (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
            .compute(executor=ex)
        )
        assert not ex._workers           # no threads for 1 location

    def test_worker_error_propagates(self):
        _, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = ThreadedExecutor()

        def boom(v):
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            (
                Collection.from_blocked(ba)
                .split(SplIter())
                .map_partitions(boom)
                .compute(executor=ex)
            )
        ex.close()


class TestReviewRegressions:
    def test_mesh_cache_keyed_on_combine_identity(self):
        """Same map fn reduced by DIFFERENT combines on one MeshExecutor must
        not share a compiled sharded fold (regression: wrong values)."""
        pts, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = MeshExecutor()
        base = Collection.from_blocked(ba).split(Baseline()).map_blocks(
            lambda b: jnp.sum(b, 0)
        )
        s = base.reduce(lambda a, b: a + b).compute(executor=ex).value
        m = base.reduce(jnp.maximum).compute(executor=ex).value
        np.testing.assert_allclose(
            np.asarray(s), pts.sum(0), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(m),
            np.max(pts.reshape(12, 8, 3).sum(1), axis=0),
            rtol=2e-4, atol=2e-4,
        )

    def test_threaded_nested_compute_does_not_deadlock(self):
        """A map_partitions callback computing on the SAME ThreadedExecutor
        runs inline instead of deadlocking its own location worker."""
        pts, ba = _blocked(96, 8, 4, round_robin_placement)
        ex = ThreadedExecutor()
        inner_plan = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_blocks(_moments_fn)
            .reduce(_moments_combine)
        )

        def view_fn(view):
            inner = inner_plan.compute(executor=ex)  # nested, same executor
            return view.location, np.asarray(inner.value[0])

        res = (
            Collection.from_blocked(ba)
            .split(SplIter())
            .map_partitions(view_fn)
            .compute(executor=ex)
        )
        for _, total in res.value:
            np.testing.assert_allclose(total, pts.sum(0), rtol=2e-4, atol=2e-4)
        ex.close()

    def test_stable_key_distinguishes_globals(self):
        """Identical bytecode resolving different module globals must not
        share a key (two modules defining the same-looking fn)."""
        ns1 = {"SCALE": 2.0}
        ns2 = {"SCALE": 3.0}
        code = "def f(b):\n    return SCALE * b\n"
        exec(code, ns1)
        exec(code, ns2)
        assert stable_task_key(ns1["f"]) != stable_task_key(ns2["f"])
        # re-creating the fn in the SAME namespace keeps the key stable
        f_old = ns1["f"]
        exec(code, ns1)
        assert ns1["f"] is not f_old
        assert stable_task_key(ns1["f"]) == stable_task_key(f_old)
