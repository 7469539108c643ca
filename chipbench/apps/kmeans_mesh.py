"""k-means across chips: each location's blocks made, held and checked on
the chip its location names.

The timed entry, the pass's work and the comparison are
``chipbench/apps/kmeans.py``'s: one ``compute()`` of
``Collection.from_blocked(x).split(policy).map_blocks(partial_sum_block,
extra_args=(centers,)).reduce(combine)`` per iteration, here through
``engine("mesh")`` over the configuration's ``chips``.  What differs is
where the data lives.  Block ``b`` is drawn with the key
``chipbench.datagen.uniform_blocked`` gives it, directly on the chip of its
location (``repro.core.blocked.location_device``), so the data is the
same, bit for bit, as one device's draw of the same seed, and no chip ever
holds more than its share and one block.  The reference computes each
block's Lloyd partial on the chip that holds it, with the centers put
there, dispatches every block before it pulls a partial to the host, and
adds the partials in float64 in block order.
"""

from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.apps.kmeans import (
    CENTERS_TAG,
    DATA_TAG,
    Loop,
    _block_partial,
    _chunk,
    compare,
    pass_work,
    partial_elements,
)
from chipbench.datagen import _uniform, seed_key
from repro.core.blocked import BlockedArray, location_device, round_robin_placement

# the app's surface for run.py and control.py; the loop, the pass's work and
# the comparison are the one-chip app's
__all__ = ["Loop", "check", "control_answers", "make_data", "partial_elements", "pass_work"]


def make_data(cfg: dict, seed: int) -> BlockedArray:
    """``uniform_blocked``'s blocks, each drawn on the chip of its location."""
    locs, per, rows, d = (cfg[k] for k in ("locations", "blocks_per_location",
                                           "rows_per_block", "d"))
    # a run before this one in the same process (control.py's program
    # readings) leaves its data in reference cycles; at a chip's share of
    # 4.47 GiB a third copy would not fit, so collect them first
    gc.collect()
    n = locs * per
    placements = round_robin_placement(n, locs)
    chips = jax.devices()[: cfg["chips"]]
    key = seed_key(seed, DATA_TAG)
    keys = {dev: jax.device_put(key, dev) for dev in chips}
    blocks = [
        _uniform(keys[location_device(int(placements[b]), locs, chips)], b, (rows, d))
        for b in range(n)
    ]
    return BlockedArray.from_blocks(blocks, placements, locs)


def reference_step(data: BlockedArray, centers, *, passes: int = 6):
    """(sums, counts) of one Lloyd step from ``centers``, float64 on the host.

    Every block's partial runs on the chip that holds the block, all of
    them dispatched before the first is pulled, so the chips work at once.
    """
    on: dict = {}
    partials = []
    for block in data.blocks:
        (dev,) = block.devices()
        if dev not in on:
            on[dev] = jax.device_put(centers, dev)
        partials.append(_block_partial(block, on[dev], chunk=_chunk(block.shape[0]),
                                       passes=passes))
    sums = counts = 0.0
    for s, c in partials:
        sums = sums + np.asarray(s, np.float64)
        counts = counts + np.asarray(c, np.float64)
    return sums, counts


def check(cfg: dict, data: BlockedArray, answers) -> tuple[dict[str, float], int]:
    """Worst of each compared number over ``answers``, and how many failed;
    prints the check's own seconds on standard error."""
    t0 = time.perf_counter()
    rows = data.num_rows
    limits = cfg["limits"]
    worst = dict.fromkeys(limits, 0.0)
    failed = 0
    for centers, sums, counts in answers:
        got = compare(sums, counts, *reference_step(data, centers), rows)
        failed += any(not got[k] <= limits[k] for k in limits)
        worst = {k: max(worst[k], got[k]) for k in limits}
    print(f"check: {len(answers)} answers in {time.perf_counter() - t0:.3f} s",
          file=sys.stderr, flush=True)
    return worst, failed


def control_answers(cfg: dict, data: BlockedArray, seed: int, n: int, passes: int = 3):
    """``n`` answers of the reference computed one precision lower, put in
    the engine's place in the same closed loop from the same start."""
    centers = jax.random.uniform(seed_key(seed, CENTERS_TAG), (cfg["k"], cfg["d"]), jnp.float32)
    out = []
    for _ in range(n):
        sums, counts = reference_step(data, centers, passes=passes)
        out.append((centers, sums, counts))
        centers = jnp.asarray(sums / np.maximum(counts, 1.0)[:, None], jnp.float32)
    return out
