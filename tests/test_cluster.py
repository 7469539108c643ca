"""ClusterExecutor — multi-process scheduling, locality, fault tolerance.

The acceptance contract of DESIGN.md §11:

* bit-identical results to LocalExecutor on all four apps (histogram,
  kmeans, knn, svm) — including with injected worker kills mid-run
  (``EngineReport.retries >= 1``);
* chunk-backed plans resolve blocks worker-side from the handed-off
  DiskStore (bytes never transit the control channel), and a kill releases
  the dead dispatch's pins on requeue;
* two sequential kills of the same unit exhaust ``max_retries`` and raise
  a typed :class:`ClusterFailedError` naming the poisoned task key;
* every executor's ``close()`` is idempotent (the shared base-class sweep).

The CI ``cluster-fault-lane`` job runs exactly this module with
``REPRO_CLUSTER_LOG_DIR`` set, uploading per-worker logs as artifacts on
failure.
"""

from __future__ import annotations

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    Baseline,
    ClusterExecutor,
    ClusterFailedError,
    Collection,
    DiskStore,
    Executor,
    FaultPlan,
    LocalExecutor,
    MeshExecutor,
    SplIter,
    StreamExecutor,
    ThreadedExecutor,
    decode_fn,
    encode_fn,
)
from repro.api import shm_available
from repro.api.executors import _SchedulerState, _Unit
from repro.api.shm import leaked_segments
from repro.core.apps.cascade_svm import cascade_svm
from repro.core.apps.histogram import histogram
from repro.core.apps.kmeans import kmeans
from repro.core.apps.knn import knn
from repro.core.blocked import BlockedArray, round_robin_placement

LOG_DIR = os.environ.get("REPRO_CLUSTER_LOG_DIR")  # CI fault lane artifacts
POL = SplIter(partitions_per_location=2)
needs_shm = pytest.mark.skipif(
    not shm_available(), reason="host has no POSIX shared memory"
)


def _cluster(**kw) -> ClusterExecutor:
    kw.setdefault("log_dir", LOG_DIR)
    return ClusterExecutor(**kw)


def _blocked(a, block_rows=256, locs=2) -> BlockedArray:
    return BlockedArray.from_array(
        jnp.asarray(a), block_rows, num_locations=locs, policy=round_robin_placement
    )


@pytest.fixture(scope="module")
def points() -> BlockedArray:
    rng = np.random.default_rng(0)
    return _blocked(rng.random((2048, 4)).astype(np.float32))


@pytest.fixture(scope="module")
def cluster():
    """One shared pool for the fault-free tests (spawn paid once)."""
    ex = _cluster()
    yield ex
    ex.close()


def identical(a, b) -> bool:
    return bool(jnp.all(jnp.equal(a, b)))


# ---------------------------------------------------------------------------
# bit-identity vs LocalExecutor — all four apps
# ---------------------------------------------------------------------------


class TestBitIdentity:
    def test_histogram(self, points, cluster):
        ref, ref_rep = histogram(points, bins=8, policy=POL)
        h, rep = histogram(points, bins=8, policy=POL, executor=cluster)
        assert identical(h, ref)
        assert rep.dispatches == ref_rep.dispatches  # C1 parity over IPC
        assert rep.remote_dispatches == ref_rep.dispatches - ref_rep.merges
        assert rep.ipc_bytes > 0 and rep.retries == 0

    def test_histogram_pallas_fusion(self, points, cluster):
        pol = SplIter(partitions_per_location=2, fusion="pallas")
        ref, _ = histogram(points, bins=8, policy=pol)
        h, rep = histogram(points, bins=8, policy=pol, executor=cluster)
        assert identical(h, ref)
        assert rep.remote_dispatches >= 1  # kernel rehydrated by name remotely

    def test_kmeans(self, points, cluster):
        ref = kmeans(points, k=4, iters=3, policy=POL)
        res = kmeans(points, k=4, iters=3, policy=POL, executor=cluster)
        assert identical(res.centers, ref.centers)
        assert sum(r.remote_dispatches for r in res.reports) >= 3 * 4

    def test_knn(self, points, cluster):
        rng = np.random.default_rng(1)
        qry = _blocked(rng.random((256, 4)).astype(np.float32), 128)
        ref = knn(points, qry, k=4, policy=POL)
        res = knn(points, qry, k=4, policy=POL, executor=cluster)
        assert identical(res.indices, ref.indices)
        assert identical(res.distances, ref.distances)
        # fit builds + lookup/merge loops are driver RPCs on the cluster
        assert res.report.remote_dispatches >= 1

    def test_svm(self, points, cluster):
        rng = np.random.default_rng(2)
        y = _blocked(np.where(rng.random(2048) > 0.5, 1.0, -1.0).astype(np.float32))
        ref = cascade_svm(points, y, num_sv=16, steps=30, iterations=1, policy=POL)
        res = cascade_svm(
            points, y, num_sv=16, steps=30, iterations=1, policy=POL, executor=cluster
        )
        assert identical(res.sv_x, ref.sv_x)
        assert identical(res.sv_y, ref.sv_y)
        assert res.report.remote_dispatches >= 1

    def test_unreduced_map_partials_order(self, points, cluster):
        plan = Collection.from_blocked(points).split(Baseline()).map_blocks(
            lambda b: jnp.sum(b, axis=0)
        )
        ref = plan.compute(executor=LocalExecutor())
        got = plan.compute(executor=cluster)
        assert len(got.value) == len(ref.value) == points.num_blocks
        for g, r in zip(got.value, ref.value):
            assert identical(g, r)


def test_large_payloads_do_not_deadlock_pipes():
    """Regression: both control-channel directions are blocking writes over
    ~64KB OS pipes.  In-memory plans ship operands inline, so a single
    command or result above the buffer used to let the parent block in
    ``send_bytes`` while the worker blocked writing its reply — a mutual
    hang.  The one-un-replied-command-per-worker window must keep every
    send aimed at a worker that is parked in ``recv``."""
    rng = np.random.default_rng(3)
    big = _blocked(rng.random((2048, 128)).astype(np.float32), 256)  # 128KB/block
    plan = Collection.from_blocked(big).split(Baseline()).map_blocks(
        lambda b: b * 2.0
    )
    ref = plan.compute(executor=LocalExecutor())
    ex = _cluster(shm=False)  # force inline payloads: this test IS the pipe path
    box: dict = {}

    def run():
        box["got"] = plan.compute(executor=ex)

    t = threading.Thread(target=run, daemon=True)  # watchdog: hang -> fail, not CI stall
    t.start()
    t.join(timeout=180)
    try:
        if t.is_alive():
            pytest.fail("cluster run deadlocked on >64KB pipe payloads")
    finally:
        if not t.is_alive():
            ex.close()
    got = box["got"]
    assert got.report.remote_dispatches >= 1
    # operands AND results crossed the wire: ipc dwarfs the dataset
    assert got.report.ipc_bytes > 1.9 * big.nbytes
    for g, r in zip(got.value, ref.value):
        assert identical(g, r)


# ---------------------------------------------------------------------------
# chunk-backed plans: bytes stay off the control channel
# ---------------------------------------------------------------------------


def test_chunk_handles_keep_bytes_off_the_wire(points):
    """shm=False — the PR 5 spill-file path, unchanged by the data plane."""
    ref, _ = histogram(points, bins=8, policy=POL)
    ex_mem = _cluster(shm=False)
    _, rep_mem = histogram(points, bins=8, policy=POL, executor=ex_mem)
    ex_mem.close()

    store = DiskStore(residency_bytes=1 << 20)
    chunked = points.to_store(store)
    ex = _cluster(shm=False)
    h, rep = histogram(chunked, bins=8, policy=POL, executor=ex)
    ex.close()
    assert identical(h, ref)
    # operands travel as ChunkHandles resolved worker-side from the
    # manifested spill files: vs the in-memory run, (at least) the whole
    # dataset's bytes disappear from the control channel and reappear as
    # worker-side spill reads (bytes_loaded).
    assert rep_mem.ipc_bytes - rep.ipc_bytes > 0.9 * points.nbytes
    assert rep.bytes_loaded >= points.nbytes
    assert all(not store.is_pinned(r) for r in chunked.blocks)
    store.close()


@needs_shm
def test_chunk_manifest_hands_off_via_shm_without_spilling(points):
    """shm on — resident chunks manifest as segments: no spill, no loads."""
    ref, _ = histogram(points, bins=8, policy=POL)
    store = DiskStore(residency_bytes=64 << 20)  # everything stays resident
    chunked = points.to_store(store)
    ex = _cluster(shm=True)
    h, rep = histogram(chunked, bins=8, policy=POL, executor=ex)
    assert identical(h, ref)
    # The old handoff force-spilled EVERY chunk; shm-first writes nothing
    # to disk and workers read segments, not files.  (Asserted before
    # close(): the close-time trim legitimately spills the residency
    # cache, which is release bookkeeping, not handoff traffic.)
    assert store.stats.spills == 0 and store.stats.bytes_spilled == 0
    ex.close()
    assert rep.bytes_spilled == 0 and rep.bytes_loaded == 0
    assert rep.shm_bytes >= points.nbytes  # each chunk copied exactly once
    assert rep.ipc_bytes < points.nbytes  # descriptors, not block bytes
    assert all(not store.is_pinned(r) for r in chunked.blocks)
    store.close()


# ---------------------------------------------------------------------------
# the shared-memory data plane — the PR 7 acceptance numbers
# ---------------------------------------------------------------------------


@needs_shm
class TestShmDataPlane:
    """Block payloads move through /dev/shm; the pipes carry descriptors.

    The acceptance bar: ≥10× less control-channel traffic on the two
    payload-heavy apps (knn ships fit structures into every lookup RPC,
    cascade_svm ships group matrices into every cascade level), with
    results bit-identical to both LocalExecutor and the shm-off cluster.
    """

    def _run_both(self, app):
        out = {}
        for shm in (False, True):
            ex = _cluster(shm=shm)
            try:
                for _ in range(2):  # 2nd call: steady-state, export cache warm
                    res = app(ex)
            finally:
                ex.close()
            out[shm] = res
        return out[False], out[True]

    def test_knn_ipc_bytes_drop_10x(self):
        rng = np.random.default_rng(0)
        fit = _blocked(rng.random((2048, 3)).astype(np.float32))
        qry = _blocked(rng.random((512, 3)).astype(np.float32), 256)
        ref = knn(fit, qry, k=4, policy=POL)
        off, on = self._run_both(lambda ex: knn(fit, qry, k=4, policy=POL, executor=ex))
        for res in (off, on):
            assert identical(res.indices, ref.indices)
            assert identical(res.distances, ref.distances)
        assert off.report.ipc_bytes >= 10 * on.report.ipc_bytes
        assert on.report.shm_bytes > 0 and off.report.shm_bytes == 0

    def test_svm_ipc_bytes_drop_10x(self):
        rng = np.random.default_rng(0)
        pts = rng.standard_normal((4096, 16)).astype(np.float32)
        w = rng.standard_normal(16).astype(np.float32)
        labels = np.sign(pts @ w + 0.05 * rng.standard_normal(4096)).astype(np.float32)
        x, y = _blocked(pts, 512), _blocked(labels, 512)

        def app(ex):
            return cascade_svm(
                x, y, num_sv=32, steps=30, iterations=1, policy=POL, executor=ex
            )

        ref = cascade_svm(x, y, num_sv=32, steps=30, iterations=1, policy=POL)
        off, on = self._run_both(app)
        for res in (off, on):
            assert identical(res.sv_x, ref.sv_x)
            assert identical(res.sv_y, ref.sv_y)
        assert off.report.ipc_bytes >= 10 * on.report.ipc_bytes
        assert on.report.shm_bytes > 0

    def test_grown_store_reattaches_as_a_delta(self, points):
        # A second dataset lands in an ALREADY handed-off store: workers
        # hold an attach from run 1, so run 2 must ship only the new
        # chunks' descriptors (manifest delta, merged in place) — not
        # re-manifest, re-spill, or re-send the world.
        store = DiskStore(residency_bytes=64 << 20)
        chunked = points.to_store(store)
        ref, _ = histogram(points, bins=8, policy=POL)
        ex = _cluster(shm=True)
        h1, _ = histogram(chunked, bins=8, policy=POL, executor=ex)
        assert identical(h1, ref)
        rng = np.random.default_rng(7)
        pts2 = _blocked(rng.random((1024, 4)).astype(np.float32))
        ref2, _ = histogram(pts2, bins=8, policy=POL)
        chunked2 = pts2.to_store(store)  # the SAME store, grown mid-session
        h2, rep2 = histogram(chunked2, bins=8, policy=POL, executor=ex)
        assert identical(h2, ref2)
        assert store.stats.spills == 0  # delta handed off via shm too
        assert rep2.ipc_bytes < pts2.nbytes  # descriptors, not block bytes
        ex.close()
        store.close()


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------


class TestFaultTolerance:
    def test_kill_midrun_replays_bit_identical(self, points):
        ref, _ = histogram(points, bins=8, policy=SplIter(partitions_per_location=4))
        ex = _cluster(fault_plan=FaultPlan(kill_after=((0, 2),)))
        h, rep = histogram(
            points, bins=8, policy=SplIter(partitions_per_location=4), executor=ex
        )
        assert identical(h, ref)
        assert rep.retries >= 1
        # the pool healed onto survivors: a follow-up run still works
        h2, rep2 = histogram(
            points, bins=8, policy=SplIter(partitions_per_location=4), executor=ex
        )
        assert identical(h2, ref) and rep2.retries == 0
        ex.close()

    def test_kill_during_merge_dependency_wait(self, points):
        # Worker 0 dies on its LAST queued unit: by then every task unit
        # is dispatched and the parent is parked waiting for the merge
        # unit's dependencies — the requeue must un-stick that wait.
        pol = SplIter(partitions_per_location=4)
        ref, ref_rep = histogram(points, bins=8, policy=pol)
        ex = _cluster(fault_plan=FaultPlan(kill_after=((0, 4),)))
        h, rep = histogram(points, bins=8, policy=pol, executor=ex)
        ex.close()
        assert identical(h, ref)
        assert rep.retries >= 1
        assert rep.merges == ref_rep.merges  # the merge still ran, once

    def test_kill_worker_owning_pinned_chunk_releases_pins(self, points):
        store = DiskStore(residency_bytes=1 << 20)
        chunked = points.to_store(store)
        pol = SplIter(partitions_per_location=4)
        ref, _ = histogram(points, bins=8, policy=pol)
        ex = _cluster(fault_plan=FaultPlan(kill_after=((1, 1),)))
        h, rep = histogram(chunked, bins=8, policy=pol, executor=ex)
        ex.close()
        assert identical(h, ref)
        assert rep.retries >= 1
        # release-on-requeue: no pin outlives the dead dispatch
        assert all(not store.is_pinned(r) for r in chunked.blocks)
        store.close()

    def test_two_kills_exhaust_max_retries(self, points):
        # worker 0 dies on first receipt; the replay lands on surviving
        # worker 1, which dies on any retried unit → attempts exceed
        # max_retries=1 → typed failure naming the poisoned task.
        ex = _cluster(
            max_retries=1,
            fault_plan=FaultPlan(kill_after=((0, 1),), kill_on_retry=(1,)),
        )
        with pytest.raises(ClusterFailedError, match="poisoned") as ei:
            histogram(points, bins=8, policy=POL, executor=ex)
        assert ei.value.task_key is not None
        assert "histogramdd_block" in ei.value.task_key
        # the error carries the full attempt history: both deaths, with
        # worker ids and a per-attempt cause summary
        assert len(ei.value.attempts) >= 2
        assert len({a["worker"] for a in ei.value.attempts}) >= 2
        assert all(a["error"] for a in ei.value.attempts)
        assert "attempt history" in str(ei.value)
        if LOG_DIR:
            # with worker logging on, the error points at the log files
            assert ei.value.log_paths
            assert all(p.startswith(LOG_DIR) for p in ei.value.log_paths)
        # the executor survives the failure: fresh workers, clean run
        ref, _ = histogram(points, bins=8, policy=POL)
        h, rep = histogram(points, bins=8, policy=POL, executor=ex)
        assert identical(h, ref)
        ex.close()

    def test_send_boundary_death_requeues_unit(self, points):
        # A worker that passes the liveness check but whose command pipe
        # is already torn raises OSError inside the send itself.  The unit
        # is assigned before the transport is touched, so the death
        # sweep's requeue must replay it — not silently lose it.
        ref, _ = histogram(points, bins=8, policy=POL)
        ex = _cluster()
        h0, _ = histogram(points, bins=8, policy=POL, executor=ex)  # warm pool
        assert identical(h0, ref)
        ex._workers[0]._conn.close()  # torn transport, process still alive
        h, rep = histogram(points, bins=8, policy=POL, executor=ex)
        ex.close()
        assert identical(h, ref)
        assert rep.retries >= 1

    def test_driver_rpc_retries_on_worker_death(self, points):
        rng = np.random.default_rng(1)
        qry = _blocked(rng.random((256, 4)).astype(np.float32), 128)
        ref = knn(points, qry, k=4, policy=POL)
        ex = _cluster(fault_plan=FaultPlan(kill_after=((0, 3),)))
        res = knn(points, qry, k=4, policy=POL, executor=ex)
        ex.close()
        assert identical(res.indices, ref.indices)
        assert res.report.retries >= 1

    @needs_shm
    def test_kill_midrun_leaks_no_shm_segments(self, points):
        # The dead worker's in-flight reply segment (and every operand
        # segment pinned for its units) must be swept: close() leaves
        # /dev/shm with zero entries under this executor's prefix.
        pol = SplIter(partitions_per_location=4)
        ref, _ = histogram(points, bins=8, policy=pol)
        ex = _cluster(fault_plan=FaultPlan(kill_after=((0, 2),)))
        prefix = ex._shm.prefix
        h, rep = histogram(points, bins=8, policy=pol, executor=ex)
        assert identical(h, ref)
        assert rep.retries >= 1
        ex.close()
        assert leaked_segments(prefix) == []

    @needs_shm
    def test_poisoned_run_leaks_no_shm_segments(self, points):
        # Even the failure path — two kills, typed ClusterFailedError,
        # partial results discarded — must unwind every segment.
        ex = _cluster(
            max_retries=1,
            fault_plan=FaultPlan(kill_after=((0, 1),), kill_on_retry=(1,)),
        )
        prefix = ex._shm.prefix
        with pytest.raises(ClusterFailedError):
            histogram(points, bins=8, policy=POL, executor=ex)
        ex.close()
        assert leaked_segments(prefix) == []

    def test_hung_worker_detected_by_heartbeat_timeout(self, points):
        # mute: the worker process stays alive but stops heartbeating and
        # never replies — only the staleness detector can reclaim it.
        ex = _cluster(
            fault_plan=FaultPlan(mute_after=((0, 2),)),
            heartbeat_s=0.1,
            heartbeat_timeout_s=1.5,
        )
        ref, _ = histogram(points, bins=8, policy=POL)
        h, rep = histogram(points, bins=8, policy=POL, executor=ex)
        ex.close()
        assert identical(h, ref)
        assert rep.retries >= 1


# ---------------------------------------------------------------------------
# scheduler-state ownership hooks (the requeue substrate)
# ---------------------------------------------------------------------------


def test_scheduler_state_requeue_hooks():
    units = [
        _Unit(index=i, location=0, tasks=(), run=lambda: i, kind="task")
        for i in range(3)
    ]
    state = _SchedulerState(units)
    state.assign(units[0], "w0")
    state.assign(units[1], "w0")
    state.assign(units[2], "w1")
    state.complete(units[1], "done-1")
    lost = state.requeue("w0")
    assert [u.index for u in lost] == [0]  # completed unit 1 is not lost
    assert state.attempts[0] == 1
    state.assign(units[0], "w1")
    assert state.attempts[0] == 2
    # duplicate completion (late reply from a presumed-dead worker) is a no-op
    assert state.complete(units[1], "dup") == []
    assert state.results[1] == "done-1"
    assert state.is_done(1) and not state.is_done(0)


def test_fnref_roundtrip():
    import functools

    from repro.core.apps.kmeans import _combine, partial_sum_block

    # importable module-level fn
    ref = encode_fn(_combine)
    assert ref[0] == "import"
    assert decode_fn(ref) is _combine
    # partial with picklable statics
    p = functools.partial(partial_sum_block)
    assert decode_fn(encode_fn(p)).func is partial_sum_block
    # closure lambda → code ref that computes the same thing
    k = 3
    f = lambda x: x * k  # noqa: E731 — the shape under test
    g = decode_fn(encode_fn(f))
    assert g(7) == 21
    # unpicklable closure cell → not remotable
    lock = threading.Lock()
    assert encode_fn(lambda x: (lock, x)) is None


def test_cluster_executor_satisfies_protocol():
    ex = ClusterExecutor()
    assert isinstance(ex, Executor)
    ex.close()


def _cpu_marker(x):
    """1 + x when the jit that traces this runs on the CPU backend."""
    return x + (1.0 if jax.default_backend() == "cpu" else 0.0)


def test_worker_jax_is_pinned_to_cpu(monkeypatch):
    """A worker started with a platform it must not open still runs on the
    CPU: the pin comes before the worker's first JAX call."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # inherited by spawned workers
    ex = _cluster()
    try:
        out = ex.task(_cpu_marker, key="cpu-probe")(np.zeros((), np.float32))
        assert ex.report.remote_dispatches == 1  # it ran on a worker
    finally:
        ex.close()
    assert float(out) == 1.0


def test_prefer_pallas_follows_workers_not_driver(monkeypatch, points):
    """On a TPU driver, fusion="auto" keeps the scan for CPU workers, while
    an explicit fusion="pallas" still ships the kernel (interpreted there)."""
    from functools import partial

    from repro.core.apps.histogram import histogramdd_block

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ex = _cluster()
    try:
        assert LocalExecutor().capabilities.prefer_pallas  # the driver's view
        assert not ex.capabilities.prefer_pallas

        def kinds(fusion):
            plan = (
                Collection.from_blocked(points)
                .split(SplIter(partitions_per_location=2, fusion=fusion))
                .map_blocks(partial(histogramdd_block, bins=8, lo=0.0, hi=1.0))
                .reduce(lambda a, b: a + b)
            )
            return {t.kind for t in ex.lower(plan.plan()).tasks}

        assert kinds("auto") == {"partition_scan"}
        assert kinds("pallas") == {"partition_pallas"}
    finally:
        ex.close()


# ---------------------------------------------------------------------------
# close() idempotence — the shared base-class sweep (all five backends)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "make",
    [LocalExecutor, ThreadedExecutor, MeshExecutor, StreamExecutor, ClusterExecutor],
    ids=lambda c: c.__name__,
)
def test_close_is_idempotent(make, points):
    ex = make()
    _, _ = histogram(points, bins=8, policy=POL, executor=ex)
    ex.close()
    ex.close()  # second close must be a clean no-op
    # close → reuse → close: pools/workers respawn transparently
    h, _ = histogram(points, bins=8, policy=POL, executor=ex)
    ref, _ = histogram(points, bins=8, policy=POL)
    assert identical(h, ref)
    ex.close()
    ex.close()


def test_stream_close_twice_with_disk_store(points):
    """The close-idempotence satellite's regression: double close must not
    re-enter the (already closed) store's teardown."""
    store = DiskStore(residency_bytes=1 << 14)
    chunked = points.to_store(store)
    ex = StreamExecutor()
    _, _ = histogram(chunked, bins=8, policy=POL, executor=ex)
    ex.close()
    assert store.closed
    ex.close()  # second close: store already gone, must not raise
    assert store.closed
