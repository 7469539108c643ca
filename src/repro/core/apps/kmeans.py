"""k-means / Lloyd's algorithm (paper §5.2) — iterative, memory-bound.

Per block: pairwise distances → per-centroid partial sums and counts
(``_partial_sum`` in dislib).  Merge: elementwise sum, then mean
(``_recompute_centers``).

The iterative outer loop re-uses one persistent executor: task definitions
are traced once, and the executor's prepare cache applies the split (or the
rechunk, with its traffic bill) exactly once — paper §6.3.1 "this cost is
only payed once, not for every iteration" — with no app-level special
casing.  Centroids travel as ``extra_args`` so every iteration re-dispatches
the same compiled task.

``policy=SplIter(partitions_per_location="auto")`` turns the loop into the
autotuner's natural host: early iterations probe the granularity ladder,
the cost model picks a granularity, and every retune is a logical regroup
of the already-split blocks (zero movement, zero re-splits).
:class:`KMeansResult` surfaces the per-iteration granularity trajectory and
the total retune count.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.api import Collection, Executor, ExecutionPolicy, SplIter, as_policy
from repro.api.executors import _default_local
from repro.api.kernels import PartitionKernel, pallas_interpret, register_partition_kernel
from repro.core.blocked import BlockedArray
from repro.core.engine import EngineReport
from repro.kernels.partition_reduce import kmeans_vmem, partition_kmeans, row_tile

__all__ = ["kmeans", "partial_sum_block", "KMeansResult"]

_F32 = jax.lax.Precision.HIGHEST


def partial_sum_block(block: jax.Array, centers: jax.Array):
    """One Lloyd E+partial-M step on a ``(rows, d)`` block.

    Returns ``(sums (k,d), counts (k,))`` — the associative partial state.
    Matmuls run at full float32 precision (a TPU's default rounds their
    inputs to bfloat16), as the fused kernel does.
    """
    d2 = (
        jnp.sum(block * block, axis=1)[:, None]
        - 2.0 * jnp.matmul(block, centers.T, precision=_F32)
        + jnp.sum(centers * centers, axis=1)[None, :]
    )                                                        # (rows, k)
    assign = jnp.argmin(d2, axis=1)                          # (rows,)
    k = centers.shape[0]
    one_hot = jax.nn.one_hot(assign, k, dtype=block.dtype)   # (rows, k)
    sums = jnp.matmul(one_hot.T, block, precision=_F32)      # (k, d)
    counts = jnp.sum(one_hot, axis=0)                        # (k,)
    return sums, counts


def _combine(a, b):
    return a[0] + b[0], a[1] + b[1]


def _centers_of(partials):
    """Recompute centers from merged ``(sums, counts)`` partials."""
    sums, counts = partials
    return sums / jnp.maximum(counts, 1.0)[:, None]


def _kmeans_kernel_factory(args: tuple, kwargs: dict) -> PartitionKernel | None:
    """Fused-kernel factory: bare ``partial_sum_block`` (centers via extra_args)."""
    if args or kwargs:
        return None
    return PartitionKernel(
        name="partition_kmeans",
        key=("kmeans_partial",),
        fn=lambda stacked, centers: partition_kmeans(
            stacked, centers, interpret=pallas_interpret()
        ),
        supports=_kmeans_supports,
    )


def _kmeans_supports(stacked_shape: tuple, extra_args: tuple) -> bool:
    """One centers operand, and a row tile that fits the kernel's VMEM."""
    if len(extra_args) != 1:
        return False
    _, rows, d = stacked_shape
    # a pipelined loop's centers are a Deferred whose k is not known yet
    k = getattr(extra_args[0], "shape", (1,))[0]
    return row_tile(rows, kmeans_vmem(d, k)) is not None


register_partition_kernel(partial_sum_block, _kmeans_kernel_factory)


@dataclasses.dataclass
class KMeansResult:
    centers: jax.Array
    iterations: int
    reports: list[EngineReport]

    @property
    def total_dispatches(self) -> int:
        return sum(r.dispatches for r in self.reports)

    @property
    def total_wall_s(self) -> float:
        return sum(r.wall_s for r in self.reports)

    @property
    def total_bytes_moved(self) -> int:
        return sum(r.bytes_moved for r in self.reports)

    @property
    def total_retunes(self) -> int:
        return sum(r.retunes for r in self.reports)

    @property
    def granularity_trajectory(self) -> list[int]:
        """partitions_per_location per iteration (0 for non-SplIter runs)."""
        return [r.granularity for r in self.reports]


def kmeans(
    x: BlockedArray,
    *,
    k: int = 8,
    iters: int = 10,
    seed: int = 0,
    policy: ExecutionPolicy | str = SplIter(),
    executor: Executor | None = None,
    pipeline: bool = False,
) -> KMeansResult:
    d = x.row_shape[0]
    centers = jax.random.uniform(jax.random.key(seed), (k, d), x.dtype)
    pol = as_policy(policy)
    ex = executor if executor is not None else _default_local()
    data = Collection.from_blocked(x).split(pol)

    reports: list[EngineReport] = []

    if pipeline:
        # Pipelined loop (DESIGN.md §14): submit iteration k+1 while k is
        # in flight; the loop-carried centers travel as a lazy Deferred
        # (``fut.map(_centers_of)``), resolved by the scheduler only when
        # a unit that needs them dispatches.  Bit-identical to the
        # barriered loop — same per-block math, same merge order.
        centers_op = centers
        futs = []
        for _ in range(iters):
            fut = (
                data.map_blocks(partial_sum_block, extra_args=(centers_op,))
                .reduce(_combine)
                .compute_async(executor=ex)
            )
            futs.append(fut)
            centers_op = fut.map(_centers_of)
        centers = centers_op.resolve() if futs else centers
        reports = [f.result().report for f in futs]
        return KMeansResult(centers=centers, iterations=iters, reports=reports)

    for _ in range(iters):
        res = (
            data.map_blocks(partial_sum_block, extra_args=(centers,))
            .reduce(_combine)
            .compute(executor=ex)
        )
        sums, counts = res.value
        centers = _centers_of((sums, counts))
        reports.append(res.report)

    return KMeansResult(centers=centers, iterations=iters, reports=reports)
