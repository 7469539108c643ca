"""Pod-aware collectives: hierarchical reductions and compressed cross-pod
hops, expressed with ``shard_map`` so the schedule is explicit.

On a 2×16×16 mesh the ``pod`` axis is the slow (DCN) dimension.  A flat
all-reduce over (pod, data) pays the slow link for the full gradient;
the hierarchical schedule reduce-scatters within the pod rows first, sends
only 1/16th of the bytes across pods, then all-gathers back — the classic
two-level schedule, here as a reusable primitive the trainer and the §Perf
iterations build on.

``compressed_psum_pod`` additionally int8-quantizes the shard before the
cross-pod hop (4× fewer DCN bytes); error feedback lives in the optimizer
(``repro.optim.compression``) because it is stateful.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.compression import int8_compress

__all__ = [
    "hierarchical_psum",
    "psum_pod_hierarchical",
    "compressed_psum_pod",
]


def hierarchical_psum(x: jax.Array, *, fast_axis: str, slow_axis: str) -> jax.Array:
    """Two-level all-reduce for use INSIDE shard_map: RS(fast) → AR(slow) →
    AG(fast).  Equivalent to ``psum(x, (fast, slow))`` with 2/W of the flat
    schedule's slow-link bytes (W = fast-axis size)."""
    w = jax.lax.axis_size(fast_axis)
    n = x.shape[0]
    if n % w:  # ragged leading dim: fall back to the flat schedule
        return jax.lax.psum(x, (fast_axis, slow_axis))
    # reduce-scatter along the leading dim within the fast axis
    shard = jax.lax.psum_scatter(
        x.reshape(w, n // w, *x.shape[1:]), fast_axis, scatter_dimension=0, tiled=False
    )
    # slow-link hop carries only the 1/w shard
    shard = jax.lax.psum(shard, slow_axis)
    # all-gather back within the fast axis
    return jax.lax.all_gather(shard, fast_axis, axis=0, tiled=False).reshape(x.shape)


def psum_pod_hierarchical(tree: Any, mesh: Mesh) -> Any:
    """jit-level helper: hierarchically all-reduce a pytree over (pod, data).

    Leaves enter replicated over (pod, data) per-shard values (e.g. local
    gradient contributions) and exit fully reduced.
    """
    axes = mesh.axis_names
    assert "pod" in axes and "data" in axes, axes
    others = tuple(a for a in axes if a not in ("pod", "data"))

    def inner(t):
        return jax.tree.map(
            lambda x: hierarchical_psum(x, fast_axis="data", slow_axis="pod"), t
        )

    specs = jax.tree.map(lambda _: P(), tree)
    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(specs,),
        out_specs=specs,
        check_vma=False,
    )(tree)


def compressed_psum_pod(x: jax.Array, *, fast_axis: str, slow_axis: str) -> jax.Array:
    """Hierarchical psum whose cross-pod hop is int8-quantized.

    For use INSIDE shard_map.  The within-pod reduction stays exact; the
    slow link carries each pod's shard as (int8 values, fp32 per-row
    scales) — ~4× fewer DCN bytes than bf16/fp32 — and the sum of the
    dequantized shards is exact *given the quantization* (each pod keeps
    its own scale; pair with error feedback in the optimizer for the
    quantization residual).
    """
    w = jax.lax.axis_size(fast_axis)
    n = x.shape[0]
    if n % w:
        return jax.lax.psum(x, (fast_axis, slow_axis))
    shard = jax.lax.psum_scatter(
        x.reshape(w, n // w, *x.shape[1:]), fast_axis, scatter_dimension=0, tiled=False
    )
    flat = shard.reshape(max(shard.shape[0], 1), -1)
    q, s = int8_compress(flat)
    # slow-link hop: gather every pod's (q, s); int8 dominates the volume
    qg = jax.lax.all_gather(q, slow_axis, axis=0, tiled=False)   # (P, r, c) int8
    sg = jax.lax.all_gather(s, slow_axis, axis=0, tiled=False)   # (P, r, 1) fp32
    deq = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)           # exact Σ pods
    shard = deq.reshape(shard.shape).astype(shard.dtype)
    return jax.lax.all_gather(shard, fast_axis, axis=0, tiled=False).reshape(x.shape)
