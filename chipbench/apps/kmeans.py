"""k-means cells: Lloyd iterations through the engine, and their plain reference.

The timed entry is the user's: one ``compute()`` of
``Collection.from_blocked(x).split(policy).map_blocks(partial_sum_block,
extra_args=(centers,)).reduce(combine)`` per iteration, with the app's own
block function and combine.  Its answer is the merged ``(sums, counts)`` for
the centers it was given; the next centers are ``sums / counts``.

The reference is independent of the engine: a Lloyd partial step over each
block in chunks of at most 8,192 rows, float32 matmuls at full precision,
the blocks' partials added in float64 on the host.  (One float32
contraction over 26M rows lands 1.6e-3 away from a float64 sum on a v5e, so
the reference never contracts more than a chunk at once.)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.datagen import seed_key, uniform_blocked
from repro.api import Collection
from repro.core.apps.kmeans import _combine, partial_sum_block

HIGHEST = jax.lax.Precision.HIGHEST
DATA_TAG, CENTERS_TAG = 1, 2


def make_data(cfg: dict, seed: int):
    return uniform_blocked(seed, DATA_TAG, cfg)


def pass_work(cfg: dict) -> tuple[float, float]:
    """(bytes, operations) one Lloyd step needs: each value read once and the
    (sums, counts) written; a multiply-add per value and center for the
    distances, and an add per value for the sums."""
    n = cfg["locations"] * cfg["blocks_per_location"] * cfg["rows_per_block"]
    d, k = cfg["d"], cfg["k"]
    return 4.0 * (n * d + k * d + k), 2.0 * n * k * d + n * d


def partial_elements(cfg: dict) -> int:
    """Elements of one block's answer: ``(sums, counts)``."""
    return cfg["k"] * cfg["d"] + cfg["k"]


@jax.jit
def next_centers(sums, counts):
    return sums / jnp.maximum(counts, 1.0)[:, None]


class Loop:
    """One user's Lloyd loop: each ``call()`` is one timed ``compute()``."""

    def __init__(self, cfg: dict, data, policy, executor, seed: int):
        self.collection = Collection.from_blocked(data).split(policy)
        self.executor = executor
        self.centers = jax.random.uniform(
            seed_key(seed, CENTERS_TAG), (cfg["k"], cfg["d"]), jnp.float32
        )

    def call(self):
        res = (
            self.collection.map_blocks(partial_sum_block, extra_args=(self.centers,))
            .reduce(_combine)
            .compute(executor=self.executor)
        )
        sums, counts = jax.block_until_ready(res.value)
        return (self.centers, sums, counts), res.report

    def carry(self, answer) -> None:
        _, sums, counts = answer
        self.centers = next_centers(sums, counts)


# ---------------------------------------------------------------------------
# plain reference, its control, and the comparison
# ---------------------------------------------------------------------------


def _dot(a, b, passes: int):
    """A float32 matmul in 6 (``Precision.HIGHEST``), 3 or 1 bf16 passes.

    Three passes are what ``Precision.HIGH`` runs on a TPU: hi*hi + hi*lo +
    lo*hi of each operand's bf16 split, every product exact, sums in
    float32.  One pass is the TPU's default: the operands rounded to bf16.
    Written out, so that the lower passes mean the same on every backend.
    """
    if passes == 6:
        return jnp.matmul(a, b, precision=HIGHEST)

    def split(v):
        hi = v.astype(jnp.bfloat16)
        return hi, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def mm(p, q):
        return jnp.matmul(p, q, preferred_element_type=jnp.float32)

    if passes == 3:
        return mm(ah, bh) + mm(ah, bl) + mm(al, bh)
    if passes == 1:
        return mm(ah, bh)
    raise ValueError(f"passes must be 6, 3 or 1, got {passes}")


@functools.partial(jax.jit, static_argnames=("chunk", "passes"))
def _block_partial(x, centers, *, chunk: int, passes: int):
    dot = functools.partial(_dot, passes=passes)
    k, d = centers.shape
    c2 = jnp.sum(centers * centers, axis=1)[None, :]

    def add_chunk(acc, xc):
        d2 = c2 - 2.0 * dot(xc, centers.T)
        onehot = jax.nn.one_hot(jnp.argmin(d2, axis=1), k, dtype=xc.dtype)
        return (acc[0] + dot(onehot.T, xc), acc[1] + onehot.sum(axis=0)), None

    zero = (jnp.zeros((k, d), x.dtype), jnp.zeros((k,), x.dtype))
    (sums, counts), _ = jax.lax.scan(add_chunk, zero, x.reshape(-1, chunk, d))
    return sums, counts


def _chunk(rows: int, most: int = 8192) -> int:
    """The largest divisor of ``rows`` that is at most ``most``."""
    return max(c for c in range(1, min(rows, most) + 1) if rows % c == 0)


def reference_step(data, centers, *, passes: int = 6):
    """(sums, counts) of one Lloyd step from ``centers``, float64 on the host.

    The configuration states full float32 (6 passes); the control computes
    the same in the precision just below it (3 passes).
    """
    sums = counts = 0.0
    for block in data.blocks:
        chunk = _chunk(block.shape[0])
        s, c = _block_partial(block, centers, chunk=chunk, passes=passes)
        sums = sums + np.asarray(s, np.float64)
        counts = counts + np.asarray(c, np.float64)
    return sums, counts


def compare(sums, counts, ref_sums, ref_counts, rows: int) -> dict[str, float]:
    """The numbers compared with the reference, for one answer.

    ``center_err``: largest gap between a center the answer gives and the
    reference's.  ``moved_ppm``: rows the answer counts under another
    center than the reference does (half the summed count gaps, a lower
    bound), per million rows.
    """
    sums, counts = np.asarray(sums, np.float64), np.asarray(counts, np.float64)
    centers = sums / np.maximum(counts, 1.0)[:, None]
    ref_centers = ref_sums / np.maximum(ref_counts, 1.0)[:, None]
    got = {
        "center_err": np.abs(centers - ref_centers).max(),
        "moved_ppm": np.abs(counts - ref_counts).sum() / 2 / rows * 1e6,
    }
    # a NaN compares false with every limit, so it must not hide as a small number
    return {k: float(v) if np.isfinite(v) else math.inf for k, v in got.items()}


def check(cfg: dict, data, answers) -> tuple[dict[str, float], int]:
    """Worst of each compared number over ``answers``, and how many failed."""
    rows = sum(b.shape[0] for b in data.blocks)
    limits = cfg["limits"]
    worst = dict.fromkeys(limits, 0.0)
    failed = 0
    for centers, sums, counts in answers:
        got = compare(sums, counts, *reference_step(data, centers), rows)
        failed += any(not got[k] <= limits[k] for k in limits)
        worst = {k: max(worst[k], got[k]) for k in limits}
    return worst, failed


def control_answers(cfg: dict, data, seed: int, n: int, passes: int = 3):
    """``n`` answers of the reference computed one precision lower, put in
    the engine's place in the same closed loop from the same start."""
    centers = jax.random.uniform(seed_key(seed, CENTERS_TAG), (cfg["k"], cfg["d"]), jnp.float32)
    out = []
    for _ in range(n):
        sums, counts = reference_step(data, centers, passes=passes)
        out.append((centers, sums, counts))
        centers = jnp.asarray(sums / np.maximum(counts, 1.0)[:, None], jnp.float32)
    return out
