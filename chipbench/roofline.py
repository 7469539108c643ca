"""Least time of one pass over a cell's data, and a pass's share of it.

The work counted is what the app needs, computed from the shapes: each
value of the collection read once plus the result written, and the app's
own arithmetic.  How a program implements the pass (stacking copies,
transposes, one-hot matmuls) is not work, so the share reads the same
whatever implements it, and can only fall when a copy is added.

The programs that read the data are told from the others by size, not by
name: a merge stacks and folds partials, and the benchmark's carry works on
the next centers, so every array they touch holds at most one partial per
block.  A program that names a larger array, or names none, is counted.
"""

from __future__ import annotations

from chipbench.peaks import Peak


def least_pass_s(nbytes: float, flops: float, chips: int, peak: Peak) -> tuple[float, str]:
    """(seconds, bound): the larger of the memory and the compute term."""
    mem = nbytes / (chips * peak.bytes_per_s)
    comp = flops / (chips * peak.flops_per_s)
    return (mem, "memory") if mem >= comp else (comp, "compute")


def reads_data(largest: int | None, partials: int) -> bool:
    """Whether a program whose largest array holds ``largest`` elements (None:
    it names no array) reads the data, where ``partials`` is the elements of
    one partial per block."""
    return largest is None or largest > partials


def pass_share(w, app: str) -> float | None:
    """% of the least pass time that the data-reading programs achieve.

    ``w`` is a :class:`chipbench.run.TracedWindow`.  The denominator is the
    device time per iteration of every program that reads the data, as a
    mean over the cell's devices (the least time divides the bytes over all
    of them).  None where the cell runs another app or the trace holds no
    such program.
    """
    if w.app != app:
        return None
    if w.partials >= w.block_elements:
        raise ValueError(
            f"one partial per block ({w.partials} elements) is not smaller than a "
            f"block ({w.block_elements}): size cannot tell a merge from a pass"
        )
    device_ns = w.trace.module_ns(w.lo, w.hi, lambda n: reads_data(n, w.partials))
    if device_ns <= 0 or w.iterations <= 0:
        return None
    least_s, _ = least_pass_s(w.pass_bytes, w.pass_flops, w.chips, w.peak)
    return 100.0 * least_s / (device_ns * 1e-9 / w.iterations)
