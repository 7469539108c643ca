"""Cascade SVM (paper §5.3, after Graf et al.) — compute-bound, order-sensitive.

Each cascade level trains an SVM per data group and keeps its support
vectors; pairs of SV sets are unioned and retrained until one set remains;
the global loop feeds the final SVs back (few iterations).

Order sensitivity: the labels ``y`` are a *separate* blocked collection that
must stay aligned with the points ``x`` — the paper handles this with
``get_indexes`` (§4.1).  Here ``Collection.zip(x, y)`` carries both arrays
through one plan, so every :class:`~repro.api.PartitionView` yields
block-aligned (points, labels) buffers; the level-0 group list is a single
``map_partitions`` whose granularity (per block, per partition, per
rechunked block) is entirely the policy's decision.

Microkernel adaptation (DESIGN.md §2): sklearn's SMO-based SVC does not
exist on TPU; we train a bias-free RBF kernel SVM by projected gradient
ascent on the dual — O(n² d) kernel matrix + O(n²) iterations keeps the
task compute-bound, matching the paper's characterization.  "Support
vectors" are the top-m points by dual coefficient, giving static shapes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.api import Collection, Executor, ExecutionPolicy, SplIter, as_policy
from repro.api.executors import _default_local
from repro.core.blocked import BlockedArray
from repro.core.engine import EngineReport

__all__ = ["cascade_svm", "svc_train", "CascadeSVMResult"]


def _rbf(a: jax.Array, b: jax.Array, gamma: float) -> jax.Array:
    # full float32 cross term (a TPU's default rounds matmul inputs to bf16)
    d2 = (
        jnp.sum(a * a, 1)[:, None]
        - 2.0 * jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
        + jnp.sum(b * b, 1)[None, :]
    )
    return jnp.exp(-gamma * d2)


def svc_train(
    x: jax.Array,
    y: jax.Array,
    *,
    c: float = 1.0,
    gamma: float = 0.5,
    steps: int = 200,
    num_sv: int,
):
    """Train a bias-free RBF-SVM; return the ``num_sv`` strongest SVs.

    Dual projected gradient:  α ← clip(α + η(1 − Q α), 0, C) with
    Q = (y yᵀ) ⊙ K.  Returns ``(sv_x, sv_y, sv_alpha)`` with static shapes.
    """
    n = x.shape[0]
    q = _rbf(x, x, gamma) * (y[:, None] * y[None, :])
    eta = 1.0 / (jnp.linalg.norm(q, ord=jnp.inf) + 1e-6)

    def body(_, alpha):
        g = 1.0 - q @ alpha
        return jnp.clip(alpha + eta * g, 0.0, c)

    alpha = jax.lax.fori_loop(0, steps, body, jnp.zeros((n,), x.dtype))
    _, top = jax.lax.top_k(alpha, min(num_sv, n))
    return x[top], y[top], alpha[top]


@dataclasses.dataclass
class CascadeSVMResult:
    sv_x: jax.Array
    sv_y: jax.Array
    sv_alpha: jax.Array
    report: EngineReport

    def decision(self, q: jax.Array, gamma: float = 0.5) -> jax.Array:
        return _rbf(q, self.sv_x, gamma) @ (self.sv_alpha * self.sv_y)


def cascade_svm(
    x: BlockedArray,
    y: BlockedArray,
    *,
    num_sv: int = 32,
    c: float = 1.0,
    gamma: float = 0.5,
    steps: int = 200,
    iterations: int = 2,
    policy: ExecutionPolicy | str = SplIter(),
    executor: Executor | None = None,
) -> CascadeSVMResult:
    """Run the cascade under an execution policy.

    ``Baseline``: level-0 trains one task per *block* (paper Listing 8).
    ``SplIter``: level-0 trains one task per *partition* on the
    locally-concatenated blocks (paper Listing 9 — the partition is
    consumed through index-aligned x/y pairs; materialization is inherent,
    so ``SplIter(materialize=True)`` coincides with ``SplIter()``).
    ``Rechunk``: materialize one block per location first (traffic!).
    """
    assert x.num_blocks == y.num_blocks
    pol = as_policy(policy)
    ex = executor if executor is not None else _default_local()

    def train_task(bx, by, feed_x, feed_y):
        ax = jnp.concatenate([bx, feed_x], 0)
        ay = jnp.concatenate([by, feed_y], 0)
        return svc_train(ax, ay, c=c, gamma=gamma, steps=steps, num_sv=num_sv)

    def merge_task(x1, y1, x2, y2):
        return svc_train(
            jnp.concatenate([x1, x2], 0),
            jnp.concatenate([y1, y2], 0),
            c=c,
            gamma=gamma,
            steps=steps,
            num_sv=num_sv,
        )

    with ex.scope(pol.mode_name) as report:
        # Level-0 group list: aligned (points, labels) buffers per task —
        # one plan, granularity decided by the policy.
        groups = (
            Collection.zip(Collection.from_blocked(x), Collection.from_blocked(y))
            .split(pol)
            .map_partitions(lambda view: view.materialized)
            .compute(executor=ex)
            .value
        )

        d = x.row_shape[0]
        feed_x = jnp.zeros((0, d), x.dtype)
        feed_y = jnp.zeros((0,), y.dtype)

        for _ in range(iterations):
            t = ex.task(train_task, key=("train", feed_x.shape))
            level = [t(bx, by, feed_x, feed_y) for bx, by in groups]
            # Binary cascade: union pairs of SV sets and retrain (Graf et al.).
            while len(level) > 1:
                nxt = []
                mt = ex.task(merge_task, key="merge")
                for i in range(0, len(level) - 1, 2):
                    (x1, y1, _), (x2, y2, _) = level[i], level[i + 1]
                    nxt.append(mt(x1, y1, x2, y2))
                    report.merges += 1
                if len(level) % 2:
                    nxt.append(level[-1])
                level = nxt
            sv_x, sv_y, sv_a = level[0]
            feed_x, feed_y = sv_x, sv_y  # feedback loop

        # Final model: retrain on the winning SV set keeping ALL its points
        # (Graf et al.: the last cascade level's full solution is the model).
        refit = ex.task(
            lambda fx, fy: svc_train(
                fx, fy, c=c, gamma=gamma, steps=steps, num_sv=int(sv_x.shape[0])
            ),
            key=("refit", int(sv_x.shape[0])),
        )
        sv_x, sv_y, sv_a = refit(sv_x, sv_y)
        sv_x, sv_y, sv_a = jax.block_until_ready((sv_x, sv_y, sv_a))
    return CascadeSVMResult(sv_x=sv_x, sv_y=sv_y, sv_alpha=sv_a, report=report)
