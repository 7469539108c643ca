"""k-Nearest Neighbors (paper §5.4) — two-stage, order-sensitive, consolidation.

*fit*: build one lookup structure per fit-block (Baseline) or one per
partition (SplIter — the paper's key insight: consolidation decouples the
number of intermediate structures from the blocking and makes each lookup
structure more efficient, Figs 7/8).  Both cases are ONE
``map_partitions`` plan: under Baseline every block is its own
single-block partition, so the policy object carries the entire mode
difference.

*kneighbors*: every query block is looked up against every structure and the
per-structure top-k results are merged — #tasks = #structures × #query
blocks, so consolidation shrinks both the task count and the merge fan-in
(Table 1 / Fig 21).

TPU adaptation (DESIGN.md §2): sklearn KD-trees → the MXU-native structure
is the consolidated candidate *matrix*; lookup = one distance matmul + one
``top_k``.  The complexity argument transfers: merge cost scales with the
number of structures, per-structure lookup is sub-linear in its size
(top-k over one big matrix beats K-way merge of many small top-ks).

Order sensitivity: returned neighbor ids must be **global** row ids of the
fit dataset — exactly what ``PartitionView.item_indexes`` provides (§4.1).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.api import Collection, Executor, ExecutionPolicy, SplIter, as_policy
from repro.api.executors import _default_local
from repro.core.blocked import BlockedArray
from repro.core.engine import EngineReport

__all__ = ["knn", "KNNResult"]


@dataclasses.dataclass
class KNNResult:
    distances: jax.Array  # (n_queries, k) squared distances, ascending
    indices: jax.Array    # (n_queries, k) GLOBAL fit-row ids
    report: EngineReport


def _lookup(fit_x: jax.Array, fit_ids: jax.Array, q: jax.Array, k: int):
    """Distances of ``q`` against one structure → per-query top-k (d², id).

    The cross term runs at full float32 precision: at a TPU's default
    (bfloat16 inputs) its error would swamp near-neighbor distances.
    """
    d2 = (
        jnp.sum(q * q, 1)[:, None]
        - 2.0 * jnp.matmul(q, fit_x.T, precision=jax.lax.Precision.HIGHEST)
        + jnp.sum(fit_x * fit_x, 1)[None, :]
    )
    neg, pos = jax.lax.top_k(-d2, k)          # smallest distances
    return -neg, fit_ids[pos]


def _merge(d1, i1, d2, i2, k: int):
    """Merge two top-k candidate sets (the paper's _merge_kqueries)."""
    d = jnp.concatenate([d1, d2], axis=1)
    i = jnp.concatenate([i1, i2], axis=1)
    neg, pos = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(i, pos, axis=1)


def knn(
    fit: BlockedArray,
    queries: BlockedArray,
    *,
    k: int = 8,
    policy: ExecutionPolicy | str = SplIter(),
    executor: Executor | None = None,
) -> KNNResult:
    pol = as_policy(policy)
    ex = executor if executor is not None else _default_local()

    with ex.scope(pol.mode_name) as report:
        build_task = ex.task(lambda *bs: jnp.concatenate(bs, 0), key=("knn_fit",))

        def build_structure(view):
            # ONE consolidated structure per partition (paper Fig. 8); a
            # single-block "partition" under Baseline.  Global row ids come
            # from the view's item_indexes (paper §4.1).
            pts = build_task(*view.blocks)
            ids = jnp.asarray(view.item_indexes, jnp.int32)
            return pts, ids

        # ---- fit stage: build the lookup structures ----------------------
        structures = (
            Collection.from_blocked(fit)
            .split(pol)
            .map_partitions(build_structure)
            .compute(executor=ex)
            .value
        )

        # ---- kneighbors stage --------------------------------------------
        lookup_task = ex.task(lambda f, ids, q: _lookup(f, ids, q, k), key=("lk", k))
        merge_task = ex.task(lambda a, b, c, d: _merge(a, b, c, d, k), key=("mg", k))

        out_d, out_i = [], []
        for qb in queries.iter_blocks():
            cand = None
            for pts, ids in structures:
                r = lookup_task(pts, ids, qb)
                if cand is None:
                    cand = r
                else:
                    cand = merge_task(cand[0], cand[1], r[0], r[1])
                    report.merges += 1
            out_d.append(cand[0])
            out_i.append(cand[1])

        distances = jnp.concatenate(out_d, 0)
        indices = jnp.concatenate(out_i, 0)
        distances, indices = jax.block_until_ready((distances, indices))
    return KNNResult(distances=distances, indices=indices, report=report)
