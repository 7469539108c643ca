"""n-dimensional Histogram (paper §5.1) — embarrassingly parallel, memory-bound.

Per block: ``histogramdd``; merge: summation.  The SplIter version performs
the first summation inside the fused per-partition task (locality
guaranteed), the final merge is a single reduction task — exactly paper
Listings 4/5, expressed as one plan on the :mod:`repro.api` layer.

A fused Pallas partition kernel
(:func:`repro.kernels.partition_reduce.partition_histogramdd_blocks`) is
registered for :func:`histogramdd_block`, so ``SplIter(fusion="pallas")``
lowers each partition to ONE program that walks the partition's blocks
where they lie, in row tiles, into the flat-grid accumulator.

``policy=SplIter(partitions_per_location="auto")`` works here too, but the
autotuner lives on the *executor*: pass a persistent executor across
repeated ``histogram`` calls (e.g. re-binning the same dataset) so the
probe → model → retune schedule can advance; the returned report's
``granularity`` / ``retunes`` fields expose what it chose.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.api import Collection, Executor, ExecutionPolicy, SplIter, as_policy
from repro.api.kernels import PartitionKernel, pallas_interpret, register_partition_kernel
from repro.core.blocked import BlockedArray
from repro.core.engine import EngineReport
from repro.kernels.partition_reduce import (
    EXACT_COUNT_ROWS,
    histdd_vmem,
    partition_histogramdd_blocks,
    row_tile,
)

__all__ = ["histogram", "histogramdd_block"]


def histogramdd_block(block: jax.Array, *, bins: int, lo: float, hi: float) -> jax.Array:
    """d-dimensional histogram of one ``(rows, d)`` block → ``(bins,)*d`` counts.

    jnp analogue of ``np.histogramdd`` with shared uniform bin edges: each
    row is digitized per-dimension and scattered into the flat grid.
    """
    rows, d = block.shape
    scaled = (block - lo) / (hi - lo) * bins
    idx = jnp.clip(scaled.astype(jnp.int32), 0, bins - 1)            # (rows, d)
    flat = jnp.zeros((), jnp.int32)
    for k in range(d):
        flat = flat * bins + idx[:, k]
    counts = jnp.zeros((bins**d,), jnp.int32).at[flat].add(1)
    return counts.reshape((bins,) * d)


def _histogram_kernel_factory(args: tuple, kwargs: dict) -> PartitionKernel | None:
    """Fused-kernel factory: partial(histogramdd_block, bins=, lo=, hi=)."""
    if args or set(kwargs) != {"bins", "lo", "hi"}:
        return None
    bins, lo, hi = kwargs["bins"], kwargs["lo"], kwargs["hi"]

    def supports(stacked_shape: tuple, extra_args: tuple) -> bool:
        # admit what compiles: the (bins**d)-cell accumulator plus a row tile
        # must fit the kernel's VMEM budget, and f32 counts must stay exact
        nb, rows, d = stacked_shape
        return (
            not extra_args
            and nb * rows < EXACT_COUNT_ROWS
            and row_tile(rows, *histdd_vmem(bins, d)) is not None
        )

    return PartitionKernel(
        name="partition_histogramdd",
        key=("hist_dd", bins, lo, hi),
        fn=lambda blocks: partition_histogramdd_blocks(
            blocks, bins=bins, lo=lo, hi=hi, interpret=pallas_interpret()
        ),
        supports=supports,
    )


register_partition_kernel(histogramdd_block, _histogram_kernel_factory)


def histogram(
    x: BlockedArray,
    *,
    bins: int = 8,
    lo: float = 0.0,
    hi: float = 1.0,
    policy: ExecutionPolicy | str = SplIter(),
    executor: Executor | None = None,
) -> tuple[jax.Array, EngineReport]:
    block_fn = partial(histogramdd_block, bins=bins, lo=lo, hi=hi)
    res = (
        Collection.from_blocked(x)
        .split(as_policy(policy))
        .map_blocks(block_fn)
        .reduce(lambda a, b: a + b)
        .compute(executor=executor)
    )
    return res.value, res.report
