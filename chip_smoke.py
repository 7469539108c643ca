#!/usr/bin/env python3
"""Run the engine's main path once on a TPU and check every result.

Usage::

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --chips 4     # four chips: kmeans through the mesh only
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # rehearsal sizes, any backend

Phases on one chip, each through the user's entry points (``engine(...)``,
``Collection``, the paper apps), with data made on the device from
``--seed``:

* ``device``: JAX version, platform, device kind and count.  A platform
  other than ``tpu`` fails the run (``--tiny`` alone accepts one).
* ``kmeans``: 8 locations x 16 blocks x 204,800 rows x 20 f32 (2.1 GB on
  the chip), 3 Lloyd iterations with k=8 under ``Baseline()``,
  ``SplIter(fusion="scan")`` and ``SplIter(fusion="pallas")`` on
  ``engine("local")``, and under ``SplIter(fusion="pallas")`` on
  ``engine("threaded")`` and ``engine("mesh")``.  Every run is checked
  against a jitted Lloyd step over the unblocked array.
* ``histogram``: 4,194,304 rows x 5 dimensions, 8 bins each (32,768
  cells), in 8 x 16 blocks; counts must equal a scatter-add reference.
* ``knn_svm``: kNN over 32,768 fit rows against a brute-force top-k, and
  cascade SVM at 4,096 rows per location and 300 steps, whose two policies
  must give the same model.
* ``cluster``: kmeans through ``engine("cluster")`` on 2 worker processes
  that run on the CPU while this process holds the chip, against
  ``engine("local")`` on the CPU.

The ``fusion="pallas"`` runs must lower to ``partition_pallas`` tasks
only, so a quiet fall-back to the scan fails the run.  ``--chips 4`` runs
``device`` and the kmeans data through ``engine("mesh")`` against
``engine("local")``, and checks that the dispatch spanned four devices.

The last line of standard output is the verdict: ``{"ok": true,
"device": {...}}``.  Any failed phase raises, so the exit code is not 0
and no verdict is printed.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke: no repro package under {ROOT / 'src'}; run it from a checkout")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import Baseline, Collection, SplIter, engine  # noqa: E402
from repro.core.apps.cascade_svm import cascade_svm  # noqa: E402
from repro.core.apps.histogram import histogram, histogramdd_block  # noqa: E402
from repro.core.apps.kmeans import kmeans, partial_sum_block  # noqa: E402
from repro.core.apps.knn import knn  # noqa: E402
from repro.core.blocked import BlockedArray, round_robin_placement  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST

# kmeans centers are compared with this absolute tolerance (they lie in
# [0, 1)).  Two causes of difference are legitimate: float32 sums of ~3.3M
# rows per center added in another order (relative error ~1e-6), and the
# few boundary points (tens of 26M) whose argmin flips when a distance is
# rounded differently, each moving a center by ~1/count ~ 3e-7.  Both stay
# near 1e-5 after 3 iterations; a wrong kernel (a lost or doubled tile, a
# bad assignment) moves centers by 1e-3 or more.
KMEANS_ATOL = 1e-4
# kNN distances are float32 sums of 3 squares in [0, 3]: a rounding of the
# cross term differs by ~1e-6.  Indices must match wherever the distances
# leave no tie within this tolerance.
KNN_ATOL = 1e-5


@dataclasses.dataclass(frozen=True)
class Sizes:
    locs: int = 8
    km_blocks: int = 16        # blocks per location
    km_rows: int = 204_800     # rows per block
    km_d: int = 20
    km_k: int = 8
    km_iters: int = 3
    hist_blocks: int = 16
    hist_rows: int = 32_768
    hist_d: int = 5
    bins: int = 8
    knn_blocks: int = 8
    knn_rows: int = 512
    knn_queries: int = 1_024
    knn_query_rows: int = 256
    knn_d: int = 3
    knn_k: int = 8
    svm_rows: int = 4_096      # one block per location
    svm_d: int = 8
    svm_steps: int = 300
    svm_num_sv: int = 32
    cl_locs: int = 2
    cl_blocks: int = 16
    cl_rows: int = 512


FULL = Sizes()
TINY = Sizes(
    km_blocks=4, km_rows=300, hist_blocks=4, hist_rows=512, knn_blocks=2,
    knn_rows=128, knn_queries=256, knn_query_rows=64, svm_rows=128, svm_steps=100,
    cl_blocks=4, cl_rows=64,
)


def _backend_probe(x):
    """``x + 1`` where the process that traces this runs JAX on the CPU."""
    return x + (1.0 if jax.default_backend() == "cpu" else 0.0)


def _sum_pair(a, b):
    return a[0] + b[0], a[1] + b[1]


def _add(a, b):
    return a + b


# ---------------------------------------------------------------------------
# data, made on the default device from the seed
# ---------------------------------------------------------------------------


def _blocked(x: jax.Array, n_blocks: int, locs: int) -> BlockedArray:
    """``x`` split into ``n_blocks`` equal blocks, dealt round-robin."""
    return BlockedArray.from_blocks(
        jnp.split(x, n_blocks), round_robin_placement(n_blocks, locs), locs
    )


def _data_key(seed: int, phase: str):
    """One key per phase, none equal to the apps' own ``key(seed)``."""
    return jax.random.fold_in(jax.random.key(seed), sum(map(ord, phase)))


@functools.partial(jax.jit, static_argnames=("shape",))
def _uniform(key, shape):
    return jax.random.uniform(key, shape, jnp.float32)


def _assert_lowered_to(ex, collection: Collection, kind: str, label: str) -> None:
    kinds = {t.kind for t in ex.lower(collection.plan()).tasks}
    if kinds != {kind}:
        raise AssertionError(f"{label}: lowered to {sorted(kinds)}, expected only {kind}")


def _twice(fn):
    """``fn()`` run twice, the second time from the jit cache: its result."""
    jax.block_until_ready(fn())
    return jax.block_until_ready(fn())


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(chips: int, tiny: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    print(f"device: jax={jax.__version__} platform={dev.platform} "
          f"kind={dev.device_kind} count={len(devs)}", flush=True)
    if dev.platform != "tpu" and not tiny:
        sys.exit(f"chip_smoke: JAX's first device is on platform {dev.platform!r}, "
                 "not 'tpu'; this smoke runs only on a TPU (no CPU fall-back)")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, JAX sees {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}


def _lloyd_step(x, centers, chunk):
    """One plain Lloyd step over the unblocked rows (full f32 matmuls).

    The per-center sums add up ``chunk``-row partial sums in a scan.  On a
    TPU v5e one float32 matmul that contracts all 26M rows at once lands
    1.6e-3 (relative) away from a float64 sum, while the engine's matmuls
    over 204,800-row blocks stay within 5e-6; the scan keeps XLA from
    fusing the chunks back into one contraction.
    """
    k, d = centers.shape
    c2 = jnp.sum(centers * centers, axis=1)[None, :]

    def add_chunk(acc, xc):
        d2 = c2 - 2.0 * jnp.matmul(xc, centers.T, precision=HIGHEST)
        onehot = jax.nn.one_hot(jnp.argmin(d2, axis=1), k, dtype=xc.dtype)
        sums = acc[0] + jnp.matmul(onehot.T, xc, precision=HIGHEST)
        return (sums, acc[1] + onehot.sum(axis=0)), None

    zero = (jnp.zeros((k, d), x.dtype), jnp.zeros((k,), x.dtype))
    (sums, counts), _ = jax.lax.scan(add_chunk, zero, x.reshape(-1, chunk, d))
    return sums / jnp.maximum(counts, 1.0)[:, None]


def _kmeans_reference(x, s: Sizes, seed: int) -> np.ndarray:
    # kmeans() draws its initial centers from the seed this way
    centers = jax.random.uniform(jax.random.key(seed), (s.km_k, s.km_d), x.dtype)
    step = jax.jit(functools.partial(_lloyd_step, chunk=math.gcd(x.shape[0], 65_536)))
    for _ in range(s.km_iters):
        centers = step(x, centers)
    return np.asarray(centers)


def phase_kmeans(s: Sizes, seed: int, chips: int) -> None:
    n_blocks = s.locs * s.km_blocks
    x = _uniform(_data_key(seed, "kmeans"), (n_blocks * s.km_rows, s.km_d))
    want = _kmeans_reference(x, s, seed)
    ba = _blocked(x, n_blocks, s.locs)
    del x  # the blocks hold their own copy

    pallas = SplIter(partitions_per_location=1, fusion="pallas")
    if chips == 1:
        runs = [
            ("local", Baseline()),
            ("local", SplIter(partitions_per_location=1, fusion="scan")),
            ("local", pallas),
            ("threaded", pallas),
            ("mesh", pallas),
        ]
    else:  # the across-chip path and what it is compared with
        runs = [("local", pallas), ("mesh", pallas)]

    got = {}
    for backend, pol in runs:
        label = f"{backend}/{pol.mode_name}/{pol.fusion if isinstance(pol, SplIter) else '-'}"
        with engine(backend) as ex:
            if getattr(pol, "fusion", None) == "pallas":
                c0 = jnp.zeros((s.km_k, s.km_d), jnp.float32)
                plan = (Collection.from_blocked(ba).split(pol)
                        .map_blocks(partial_sum_block, extra_args=(c0,)).reduce(_sum_pair))
                _assert_lowered_to(ex, plan, "partition_pallas", f"kmeans {label}")
            res = kmeans(ba, k=s.km_k, iters=s.km_iters, seed=seed, policy=pol, executor=ex)
            centers = jax.block_until_ready(res.centers)
        err = float(np.abs(np.asarray(centers) - want).max())
        print(f"  kmeans {label}: max |centers - reference| = {err:.3g}", flush=True)
        np.testing.assert_allclose(np.asarray(centers), want, rtol=0, atol=KMEANS_ATOL,
                                   err_msg=f"kmeans {label} vs plain Lloyd reference")
        got[backend] = (centers, res)

    if chips > 1:
        centers, res = got["mesh"]
        # the dispatch spanned the mesh: its merged value is replicated on
        # every device, and the all-gather merge billed (m - 1) partials
        partial_bytes = 4 * (s.km_k * s.km_d + s.km_k)
        spanned = len(centers.sharding.device_set)
        moved = {r.bytes_moved for r in res.reports}
        if spanned != chips or moved != {(chips - 1) * partial_bytes}:
            raise AssertionError(
                f"mesh dispatch spanned {spanned} devices and moved {moved} bytes per "
                f"iteration; expected {chips} and {(chips - 1) * partial_bytes}")
        np.testing.assert_allclose(np.asarray(centers), np.asarray(got["local"][0]),
                                   rtol=0, atol=KMEANS_ATOL, err_msg="mesh vs local")
        print(f"  kmeans mesh: {spanned} devices, {moved.pop()} bytes merged per iteration",
              flush=True)


@functools.partial(jax.jit, static_argnames=("bins",))
def _histogram_reference(x, bins):
    """Counts of the (bins,)*d grid over [0, 1)^d by a flat scatter-add."""
    idx = jnp.clip(jnp.floor(x * bins).astype(jnp.int32), 0, bins - 1)
    flat = jnp.zeros(x.shape[:1], jnp.int32)
    for j in range(x.shape[1]):
        flat = flat * bins + idx[:, j]
    return jnp.zeros((bins ** x.shape[1],), jnp.int32).at[flat].add(1)


def phase_histogram(s: Sizes, seed: int) -> None:
    n_blocks = s.locs * s.hist_blocks
    x = _uniform(_data_key(seed, "histogram"), (n_blocks * s.hist_rows, s.hist_d))
    want = np.asarray(_histogram_reference(x, s.bins))
    ba = _blocked(x, n_blocks, s.locs)
    del x
    block_fn = functools.partial(histogramdd_block, bins=s.bins, lo=0.0, hi=1.0)
    for pol in (Baseline(),
                SplIter(partitions_per_location=1, fusion="scan"),
                SplIter(partitions_per_location=1, fusion="pallas")):
        label = f"local/{pol.mode_name}/{getattr(pol, 'fusion', '-')}"
        with engine("local") as ex:
            if getattr(pol, "fusion", None) == "pallas":
                plan = Collection.from_blocked(ba).split(pol).map_blocks(block_fn).reduce(_add)
                _assert_lowered_to(ex, plan, "partition_pallas", f"histogram {label}")
            h, _ = _twice(
                lambda: histogram(ba, bins=s.bins, lo=0.0, hi=1.0, policy=pol, executor=ex)
            )
        np.testing.assert_array_equal(np.asarray(h).reshape(-1), want,
                                      err_msg=f"histogram {label} vs scatter-add reference")


@functools.partial(jax.jit, static_argnames=("k",))
def _knn_reference(fit, q, k):
    d2 = (
        jnp.sum(q * q, 1)[:, None]
        - 2.0 * jnp.matmul(q, fit.T, precision=HIGHEST)
        + jnp.sum(fit * fit, 1)[None, :]
    )
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx


def _check_knn(res, fit: np.ndarray, q: np.ndarray, want_d, want_i, label: str) -> None:
    got_d, got_i = np.asarray(res.distances), np.asarray(res.indices)
    np.testing.assert_allclose(got_d, want_d, rtol=0, atol=KNN_ATOL, err_msg=label)
    # an index may differ only inside a tie: its exact distance still
    # equals the reference's at that rank
    diff = got_i != want_i
    exact = np.sum((q[:, None, :].astype(np.float64) - fit[got_i]) ** 2, axis=-1)
    np.testing.assert_allclose(exact[diff], want_d[diff], rtol=0, atol=KNN_ATOL,
                               err_msg=f"{label}: indices outside ties")
    print(f"  knn {label}: {int(diff.sum())} of {diff.size} neighbours differ inside ties",
          flush=True)


def phase_knn_svm(s: Sizes, seed: int) -> None:
    kf, kq, kx, kw, kn = jax.random.split(_data_key(seed, "knn_svm"), 5)

    n_fit = s.locs * s.knn_blocks
    fit = _uniform(kf, (n_fit * s.knn_rows, s.knn_d))
    q = _uniform(kq, (s.knn_queries, s.knn_d))
    want_d, want_i = (np.asarray(a) for a in _knn_reference(fit, q, s.knn_k))
    fit_b = _blocked(fit, n_fit, s.locs)
    q_b = _blocked(q, s.knn_queries // s.knn_query_rows, s.locs)
    fit_np, q_np = np.asarray(fit, np.float64), np.asarray(q)
    for pol in (Baseline(), SplIter()):
        with engine("local") as ex:
            res = _twice(lambda: knn(fit_b, q_b, k=s.knn_k, policy=pol, executor=ex))
        _check_knn(res, fit_np, q_np, want_d, want_i, f"local/{pol.mode_name}")

    # one 4,096-row block per location (the bench's balanced layout): both
    # policies train the same groups in the same order, one task per block
    # or one per partition, so their models must be identical
    x = jax.random.normal(kx, (s.locs * s.svm_rows, s.svm_d), jnp.float32)
    w = jax.random.normal(kw, (s.svm_d,), jnp.float32)
    noise = 0.05 * jax.random.normal(kn, (x.shape[0],), jnp.float32)
    y = jnp.where(jnp.matmul(x, w, precision=HIGHEST) + noise >= 0, 1.0, -1.0)
    xb, yb = _blocked(x, s.locs, s.locs), _blocked(y, s.locs, s.locs)
    rows = {tuple(r) + (float(v),) for r, v in zip(np.asarray(x), np.asarray(y))}
    models = {}
    for pol in (Baseline(), SplIter()):
        with engine("local") as ex:
            res = _twice(lambda: cascade_svm(
                xb, yb, num_sv=s.svm_num_sv, steps=s.svm_steps, iterations=1,
                policy=pol, executor=ex))
        svs = zip(np.asarray(res.sv_x), np.asarray(res.sv_y))
        if not all(tuple(r) + (float(v),) in rows for r, v in svs):
            raise AssertionError(f"svm {pol.mode_name}: a support vector is not a labelled row")
        models[pol.mode_name] = [np.asarray(a) for a in (res.sv_x, res.sv_y, res.sv_alpha)]
    (name_a, model_a), (name_b, model_b) = models.items()
    for part, a, b in zip(("sv_x", "sv_y", "sv_alpha"), model_a, model_b):
        np.testing.assert_array_equal(a, b, err_msg=f"svm {part}: {name_a} vs {name_b}")


def phase_cluster(s: Sizes, seed: int) -> None:
    cpu = jax.devices("cpu")[0]
    n_blocks = s.cl_locs * s.cl_blocks
    rng = np.random.default_rng(seed)
    pts = rng.random((n_blocks * s.cl_rows, s.km_d), dtype=np.float32)
    with jax.default_device(cpu):
        ba = _blocked(jnp.asarray(pts), n_blocks, s.cl_locs)
        with engine("local") as ex:
            want = kmeans(ba, k=s.km_k, iters=s.km_iters, seed=seed,
                          policy=SplIter(fusion="scan"), executor=ex).centers
        with engine("cluster") as ex:
            # the workers run JAX on the CPU, whatever the driver holds ...
            probe = ex.task(_backend_probe, key="backend-probe")(np.zeros((), np.float32))
            if float(probe) != 1.0 or ex.report.remote_dispatches != 1:
                raise AssertionError("a cluster worker's JAX is not on the CPU")
            # ... so fusion="auto" keeps the scan there, never interpreted Pallas
            c0 = jnp.zeros((s.km_k, s.km_d), jnp.float32)
            plan = (Collection.from_blocked(ba).split(SplIter())
                    .map_blocks(partial_sum_block, extra_args=(c0,)).reduce(_sum_pair))
            _assert_lowered_to(ex, plan, "partition_scan", "cluster/auto")
            res = kmeans(ba, k=s.km_k, iters=s.km_iters, seed=seed, policy=SplIter(),
                         executor=ex)
            if sum(r.remote_dispatches for r in res.reports) == 0:
                raise AssertionError("cluster kmeans dispatched nothing to its workers")
    np.testing.assert_array_equal(np.asarray(res.centers), np.asarray(want),
                                  err_msg="cluster kmeans vs local kmeans on the CPU")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of every dataset")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the kmeans mesh path across four chips")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes; also accepts a non-TPU platform")
    args = ap.parse_args()

    # the cluster phase compares against the CPU backend: keep it reachable
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    enable_compile_cache()
    sizes = TINY if args.tiny else FULL

    device = phase_device(args.chips, args.tiny)
    phase_kmeans(sizes, args.seed, args.chips)
    if args.chips == 1:
        phase_histogram(sizes, args.seed)
        phase_knn_svm(sizes, args.seed)
        phase_cluster(sizes, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
