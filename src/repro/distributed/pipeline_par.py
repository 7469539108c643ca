"""Pipeline parallelism: a GPipe-style stage executor over a ``pipe`` mesh
axis, built on ``shard_map`` + ``ppermute``.

For ≥1k-chip scale-out the (data, model) mesh gains a third factor: layers
split into S stages, each stage owned by one pipe rank.  Microbatches
stream through; stage s computes microbatch m at tick t = s + m, and
activations hop s→s+1 via ``collective_permute``.  Fill/drain bubbles cost
(S−1)/(T+S−1) of the ticks — amortized by the SplIter-shaped microbatch
blocking (many small blocks per step), the same granularity lever as L2.

This module is the *executor primitive*: stage-stacked params in, outputs
at the last stage.  It is exercised by a subprocess test on an 8-device
host mesh and composes with the dry-run mesh by factoring ``pipe`` out of
``model`` (see tests/test_distributed.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["gpipe"]


def gpipe(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,            # pytree; leaves (S, ...) — one slice per stage
    x_micro: jax.Array,           # (T, mb, ...) microbatch blocks
    *,
    mesh: Mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run T microbatches through S pipeline stages; returns (T, mb, ...).

    ``stage_fn(params_s, x) -> y`` must be shape-preserving (a trunk
    segment).  Stage s's params live on pipe rank s (leading dim sharded
    over ``axis``); microbatches stream via ppermute with a fill/drain
    schedule of T + S − 1 ticks.
    """
    s_count = mesh.shape[axis]
    t_count = x_micro.shape[0]

    p_specs = jax.tree.map(lambda _: P(axis), stage_params)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(p_specs, P(None)),  # every rank sees the full block stream
        out_specs=P(None),
        check_vma=False,
    )
    def run(params, xs):
        my = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], params)  # (1, ...) → (...)
        mb_shape = xs.shape[1:]
        n_ticks = t_count + s_count - 1
        fwd_perm = [(i, i + 1) for i in range(s_count - 1)]

        def tick(carry, t):
            state, outs = carry
            # stage 0 injects microbatch t (when in range); others use the
            # activation that arrived from the previous stage
            inject = jnp.where(t < t_count, t, 0)
            x_in = jnp.where(my == 0, xs[inject], state)
            y = stage_fn(params, x_in)
            # last stage records its result at tick t - (S-1) → microbatch id
            out_idx = t - (s_count - 1)
            write = jnp.logical_and(my == s_count - 1, out_idx >= 0)
            outs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0
                ),
                lambda o: o,
                outs,
            )
            # hop s → s+1 for the next tick
            state = jax.lax.ppermute(y, axis, fwd_perm)
            return (state, outs), None

        state0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((t_count,) + mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(
            tick, (state0, outs0), jnp.arange(t_count + s_count - 1)
        )
        # every rank returns outs; only the last stage wrote into its copy
        # (the rest are zeros), so a psum broadcasts it — making
        # out_specs=P(None) truthful
        return jax.lax.psum(outs, axis)

    return run(stage_params, x_micro)
