"""Data of a cell, made on the device from ``--seed``.

Each block is drawn on its own from a key folded from the seed and the
block's index, by one compiled program reused for every block.  So the
blocks are made one by one and set-up never holds more than the blocked
collection itself (no unblocked copy to split), and the same seed gives the
same data on any device count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.blocked import BlockedArray, round_robin_placement


def seed_key(seed: int, tag: int) -> jax.Array:
    """A key from a seed of any size (``jax.random.key`` keeps only 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, tag)


@functools.partial(jax.jit, static_argnames=("shape",))
def _uniform(key, index, shape):
    return jax.random.uniform(jax.random.fold_in(key, index), shape, jnp.float32)


def uniform_blocked(seed: int, tag: int, cfg: dict) -> BlockedArray:
    """``locations x blocks_per_location`` blocks of ``rows_per_block x d``
    float32 values uniform in [0, 1), dealt round-robin over the locations."""
    locs, per, rows, d = (cfg[k] for k in ("locations", "blocks_per_location",
                                           "rows_per_block", "d"))
    key = seed_key(seed, tag)
    n = locs * per
    blocks = [_uniform(key, b, (rows, d)) for b in range(n)]
    return BlockedArray.from_blocks(blocks, round_robin_placement(n, locs), locs)
