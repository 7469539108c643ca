"""The paper's four applications: mode equivalence + dispatch accounting."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Baseline, LocalExecutor, MeshExecutor, Rechunk, SplIter, ThreadedExecutor
from repro.core import BlockedArray, round_robin_placement
from repro.core.apps import cascade_svm, histogram, kmeans, knn

POLICIES = (Baseline(), SplIter(), SplIter(materialize=True), Rechunk())


@pytest.fixture(scope="module")
def points():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.uniform(0, 1, (512, 3)).astype(np.float32))
    return x, BlockedArray.from_array(
        x, 32, num_locations=4, policy=round_robin_placement
    )


class TestHistogram:
    def test_all_modes_exact_match(self, points):
        x, ba = points
        ref = None
        for pol in POLICIES:
            h, rep = histogram(ba, bins=4, policy=pol)
            assert int(h.sum()) == 512
            if ref is None:
                ref = np.asarray(h)
            np.testing.assert_array_equal(np.asarray(h), ref)

    def test_matches_numpy_histogramdd(self, points):
        x, ba = points
        h, _ = histogram(ba, bins=4, lo=0.0, hi=1.0, policy=SplIter())
        expected, _ = np.histogramdd(
            np.asarray(x), bins=4, range=[(0, 1)] * 3
        )
        np.testing.assert_array_equal(np.asarray(h), expected.astype(np.int32))

    def test_dispatch_counts(self, points):
        _, ba = points
        _, rb = histogram(ba, policy=Baseline())
        _, rs = histogram(ba, policy=SplIter())
        assert rb.dispatches == ba.num_blocks + 1       # per block + merge
        assert rs.dispatches == ba.num_locations + 1    # per partition + merge
        assert rs.bytes_moved == 0

    def test_rechunk_moves_bytes_under_round_robin(self, points):
        _, ba = points
        _, rr = histogram(ba, policy=Rechunk())
        assert rr.bytes_moved > 0


class TestKMeans:
    def test_modes_converge_identically(self, points):
        _, ba = points
        res = {p: kmeans(ba, k=4, iters=5, policy=p) for p in POLICIES}
        base = np.asarray(res[Baseline()].centers)
        for p in POLICIES:
            np.testing.assert_allclose(
                np.asarray(res[p].centers), base, rtol=2e-4, atol=2e-5
            )
        # ThreadedExecutor is bit-identical to LocalExecutor on the same policy
        thr = kmeans(ba, k=4, iters=5, policy=SplIter(), executor=ThreadedExecutor())
        np.testing.assert_array_equal(
            np.asarray(thr.centers), np.asarray(res[SplIter()].centers)
        )

    def test_iterative_dispatch_amortization(self, points):
        """Task definitions are traced once; dispatches scale with iterations
        for the baseline but stay at #partitions for SplIter."""
        _, ba = points
        rb = kmeans(ba, k=4, iters=5, policy=Baseline())
        rs = kmeans(ba, k=4, iters=5, policy=SplIter())
        assert rb.total_dispatches == 5 * (ba.num_blocks + 1)
        assert rs.total_dispatches == 5 * (ba.num_locations + 1)
        # one trace of the block task + one of the merge across ALL iters
        assert sum(r.traces for r in rs.reports) <= 2

    def test_centers_reduce_inertia(self, points):
        x, ba = points
        r = kmeans(ba, k=8, iters=10, policy=SplIter())
        xs = np.asarray(x)
        d2 = ((xs[:, None, :] - np.asarray(r.centers)[None]) ** 2).sum(-1)
        inertia = d2.min(1).mean()
        rng = np.random.default_rng(0)
        rand = xs[rng.choice(len(xs), 8, replace=False)]
        d2r = ((xs[:, None, :] - rand[None]) ** 2).sum(-1)
        assert inertia < d2r.min(1).mean()


class TestCascadeSVM:
    @pytest.fixture(scope="class")
    def labeled(self):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(256, 4)).astype(np.float32)
        w = rng.normal(size=(4,)).astype(np.float32)
        y = np.sign(x @ w + 0.1).astype(np.float32)
        xb = BlockedArray.from_array(
            jnp.asarray(x), 32, num_locations=4, policy=round_robin_placement
        )
        yb = BlockedArray.from_array(
            jnp.asarray(y), 32, num_locations=4, policy=round_robin_placement
        )
        return x, y, xb, yb

    def test_classifies_train_data(self, labeled):
        x, y, xb, yb = labeled
        r = cascade_svm(
            xb, yb, num_sv=128, steps=300, iterations=2, policy=SplIter(), c=10.0
        )
        pred = np.sign(np.asarray(r.decision(jnp.asarray(x))))
        acc = (pred == y).mean()
        assert acc > 0.85, acc

    def test_label_alignment_via_get_indexes(self, labeled):
        """Shuffled-placement labels stay aligned with their points."""
        x, y, xb, yb = labeled
        for pol in (Baseline(), SplIter(), Rechunk()):
            r = cascade_svm(xb, yb, num_sv=16, steps=100, iterations=1, policy=pol)
            # every reported SV must be an actual (x, y) pair from the data
            svx, svy = np.asarray(r.sv_x), np.asarray(r.sv_y)
            for i in range(len(svx)):
                row = np.nonzero((x == svx[i]).all(1))[0]
                assert len(row) >= 1
                assert y[row[0]] == svy[i]

    def test_spliter_fewer_dispatches(self, labeled):
        _, _, xb, yb = labeled
        rb = cascade_svm(xb, yb, num_sv=16, steps=50, iterations=1, policy=Baseline())
        rs = cascade_svm(xb, yb, num_sv=16, steps=50, iterations=1, policy=SplIter())
        assert rs.report.dispatches < rb.report.dispatches


class TestKNN:
    @pytest.fixture(scope="class")
    def data(self):
        rng = np.random.default_rng(11)
        fit = rng.normal(size=(300, 3)).astype(np.float32)
        q = rng.normal(size=(64, 3)).astype(np.float32)
        fb = BlockedArray.from_array(
            jnp.asarray(fit), 25, num_locations=4, policy=round_robin_placement
        )
        qb = BlockedArray.from_array(jnp.asarray(q), 16, num_locations=4)
        return fit, q, fb, qb

    def test_matches_bruteforce_numpy(self, data):
        fit, q, fb, qb = data
        r = knn(fb, qb, k=5, policy=SplIter())
        d2 = ((q[:, None, :] - fit[None]) ** 2).sum(-1)
        expected = np.argsort(d2, axis=1)[:, :5]
        got = np.asarray(r.indices)
        # compare as sets per row (ties may reorder)
        for i in range(len(q)):
            assert set(got[i]) == set(expected[i]), i
        np.testing.assert_allclose(
            np.asarray(r.distances), np.sort(d2, 1)[:, :5], rtol=1e-4, atol=1e-4
        )

    def test_global_item_indexes(self, data):
        """Returned ids are GLOBAL fit rows — the get_item_indexes contract."""
        fit, q, fb, qb = data
        for pol in POLICIES:
            r = knn(fb, qb, k=3, policy=pol)
            ids = np.asarray(r.indices)
            assert ids.min() >= 0 and ids.max() < len(fit)
            d = np.asarray(r.distances)
            # distance of the reported id must equal the reported distance
            for qi in range(0, len(q), 16):
                for j in range(3):
                    true = ((q[qi] - fit[ids[qi, j]]) ** 2).sum()
                    np.testing.assert_allclose(d[qi, j], true, rtol=1e-4, atol=1e-4)

    def test_consolidation_shrinks_tasks_and_merges(self, data):
        _, _, fb, qb = data
        rb = knn(fb, qb, k=5, policy=Baseline()).report
        rs = knn(fb, qb, k=5, policy=SplIter()).report
        # paper Table 1 / Fig 21: tasks = #structures x #query blocks
        assert rs.dispatches < rb.dispatches
        assert rs.merges < rb.merges


class TestPallasFusionApps:
    """Acceptance: histogram and k-means end-to-end through
    SplIter(fusion="pallas") on LocalExecutor AND MeshExecutor, equal to
    Baseline within float32 reassociation, dispatches within the C1 bound."""

    def test_histogram_pallas_local_and_mesh(self, points):
        _, ba = points
        ref, _ = histogram(ba, bins=4, policy=Baseline())
        for ex in (LocalExecutor(), ThreadedExecutor(), MeshExecutor()):
            h, rep = histogram(
                ba, bins=4, policy=SplIter(fusion="pallas"), executor=ex
            )
            np.testing.assert_array_equal(
                np.asarray(h), np.asarray(ref), err_msg=type(ex).__name__
            )
            assert rep.dispatches <= ba.num_locations + 1  # C1
            assert rep.bytes_moved == 0                    # 1 host device

    def test_kmeans_pallas_local_and_mesh(self, points):
        _, ba = points
        base = kmeans(ba, k=4, iters=5, policy=Baseline())
        for ex in (LocalExecutor(), MeshExecutor()):
            r = kmeans(
                ba, k=4, iters=5, policy=SplIter(fusion="pallas"), executor=ex
            )
            np.testing.assert_allclose(
                np.asarray(r.centers), np.asarray(base.centers),
                rtol=2e-4, atol=2e-4, err_msg=type(ex).__name__,
            )
            assert r.total_dispatches <= 5 * (ba.num_locations + 1)  # C1

    def test_kmeans_pallas_answer_is_the_stacked_kernels_fold(self, points):
        """Each backend's fused answer equals, bit for bit, folding the
        kernel's block entry over each task's stacked run in task order:
        handing the kernel its blocks in place changes no bit."""
        from repro.api import Collection
        from repro.core.apps.kmeans import _combine, partial_sum_block
        from repro.kernels.partition_reduce import partition_kmeans_blocks

        _, ba = points
        centers = jnp.linspace(0.0, 1.0, 4 * 3, dtype=jnp.float32).reshape(4, 3)
        plan = (
            Collection.from_blocked(ba)
            .split(SplIter(fusion="pallas"))
            .map_blocks(partial_sum_block, extra_args=(centers,))
            .reduce(_combine)
        )
        want = None
        for t in LocalExecutor().lower(plan.plan()).tasks:
            run = jnp.stack([ba.blocks[b] for b in t.block_ids])
            p = partition_kmeans_blocks(tuple(run), centers)
            want = p if want is None else _combine(want, p)
        for ex in (LocalExecutor(), ThreadedExecutor(), MeshExecutor()):
            got = plan.compute(executor=ex).value
            for g, w in zip(got, want, strict=True):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w), err_msg=type(ex).__name__
                )

    def test_knn_and_svm_run_on_mesh_executor(self):
        """Apps built on scope()/task()/map_partitions use the fallback
        scheduling path — every plan the other backends accept runs here."""
        rng = np.random.default_rng(2)
        fit = rng.normal(size=(120, 3)).astype(np.float32)
        q = rng.normal(size=(32, 3)).astype(np.float32)
        fb = BlockedArray.from_array(
            jnp.asarray(fit), 16, num_locations=4, policy=round_robin_placement
        )
        qb = BlockedArray.from_array(jnp.asarray(q), 16, num_locations=4)
        r_mesh = knn(fb, qb, k=3, policy=SplIter(), executor=MeshExecutor())
        r_loc = knn(fb, qb, k=3, policy=SplIter(), executor=LocalExecutor())
        np.testing.assert_array_equal(
            np.asarray(r_mesh.indices), np.asarray(r_loc.indices)
        )
