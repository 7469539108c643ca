"""partition_reduce — the paper's ``compute_partition`` at the VMEM level.

The SplIter idea expressed as a TPU kernel (DESIGN.md §2, layer L3): a
partition's blocks are walked in order while the reduction accumulator
stays in VMEM, in one jitted program per partition regardless of how many
HBM blocks compose it.  Block size (HBM layout granularity) is thereby
decoupled from kernel-invocation granularity — exactly the paper's
decoupling, one level down.  A partition is a logical group of blocks, as
in the paper: the blocks are read where they lie, never stacked or copied.

The same decoupling holds one level further down: the fused kernels walk
each block in *row tiles* (a ``pallas_call`` grid per block), so VMEM use
is set by the tile, never by the block's row count.  The tile is sized by
reckoning the kernel's VMEM bytes per row against
:data:`VMEM_BUDGET_BYTES`; a block that is not a multiple of the tile has
its tail masked in the kernel (the DMA of a partial tile never reads past
the block).  The kernels read each block ``(rows, d)`` as its ``(d, rows)``
view, so rows run along the 128 vector lanes and a narrow row (d = 5,
d = 20) is not padded out to 128 lanes.  On the chip a narrow block is laid
out ``{0,1:T(8,128)}``, rows minor, so that view is a bitcast.

Two ops, matching the paper's memory-bound applications:

* :func:`partition_kmeans_blocks` — fused Lloyd partial step: per row tile,
  squared distances to centroids via MXU matmul, hard assignment, one-hot
  matmul accumulation of per-centroid sums and counts in VMEM.

* :func:`partition_histogramdd_blocks` — the d-dimensional histogram of
  the histogram app's fused lowering: rows are digitized per dimension,
  combined into a flat ``bins**d`` cell index, and accumulated scatter-free.
  The cell index is split as ``cell = 128 * high + low``, so the count grid
  is one MXU matmul of two small one-hots, ``(H, tile) @ (tile, 128)``,
  instead of a ``(tile, bins**d)`` one-hot.  Bit-exact against the
  per-block ``histogramdd_block`` + sum-combine path (integer counts,
  float32 accumulation is exact below 2**24).

Each takes a run's same-shape blocks as a sequence of ``(rows, d)``
arrays, as they lie; the execution layer reaches them through the kernel
registry (``repro.api.kernels``): lowering a ``SplIter(fusion="pallas")``
plan emits one such task per same-shape run of a partition.

:func:`partition_histogram`, a scatter-free 1-d value histogram over a
stacked ``(nblocks, rows, d)`` run, is off the engine's path: no app
registers it.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_SUBLANES = 8
#: Scoped-VMEM bytes a fused kernel's row tile may use.  TPU v5e's default
#: scoped limit is 16 MiB; the rest is headroom for Mosaic's own
#: temporaries, so no kernel here needs to raise ``vmem_limit_bytes``.
VMEM_BUDGET_BYTES = 12 << 20
#: Largest row tile: beyond this the per-step overhead is already amortized.
MAX_TILE_ROWS = 8192
#: float32 counts stay exact below this many rows per partition.
EXACT_COUNT_ROWS = 1 << 24


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def row_tile(rows: int, row_bytes: int, fixed_bytes: int = 0) -> int | None:
    """The row tile for a kernel using ``row_bytes`` of VMEM per tile row.

    A block that fits whole is one tile (any row count: a full-extent block
    needs no lane alignment).  Otherwise the tile is the largest
    power-of-two multiple of 128 rows within budget.  None when not even
    128 rows fit beside ``fixed_bytes``.
    """
    room = VMEM_BUDGET_BYTES - fixed_bytes
    if rows <= MAX_TILE_ROWS and rows * row_bytes <= room:
        return rows
    tile = MAX_TILE_ROWS
    while tile >= _LANES:
        if tile * row_bytes <= room:
            return tile
        tile //= 2
    return None


def _tail_mask(rows: int, tile: int):
    """(1, tile) bool: which lanes of this row tile hold real rows."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    return pl.program_id(0) * tile + lane < rows


def _tiled_call(body, blocks, extras, out_shapes, *, tile, interpret):
    """Walk ``blocks`` in (block, row tile) order, each read where it lies.

    ``blocks`` are same-shape ``(rows, d)`` arrays.  Each goes in as its
    ``(d, rows)`` view, rows along the lanes: on the chip's layout of a
    narrow block that view is a bitcast, so no block is copied.  One
    ``pallas_call`` per block walks its row tiles and calls
    ``body(x, *extra_refs, *acc_refs)`` with each ``(d, tile)`` tile ``x``;
    the outputs are the accumulators, resident across the grid, zeroed
    before the first block and carried from each block's call into the
    next.  All calls run in the one jitted program of the caller.  (One
    call copying tiles by hand is not possible: Mosaic refuses a DMA window
    of d = 20 of a block's 24 padded sublanes, or of a 602-row tail.)
    """
    rows, d = blocks[0].shape
    n_ext, n_out = len(extras), len(out_shapes)

    def kernel(x_ref, *refs, first):
        ext, carried, accs = refs[:n_ext], refs[n_ext:-n_out], refs[-n_out:]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            for i, acc in enumerate(accs):
                acc[...] = jnp.zeros_like(acc) if first else carried[i][...]

        body(x_ref[...], *ext, *accs)

    def whole(shape):
        return pl.BlockSpec(shape, lambda t: (0,) * len(shape))

    def call(first):
        # jitted so that the program lowers each kernel once, not per block
        return jax.jit(pl.pallas_call(
            functools.partial(kernel, first=first),
            grid=(pl.cdiv(rows, tile),),
            in_specs=[pl.BlockSpec((d, tile), lambda t: (0, t))]
            + [whole(a.shape) for a in (*extras, *(() if first else out_shapes))],
            out_specs=[whole(o.shape) for o in out_shapes],
            out_shape=out_shapes,
            interpret=interpret,
        ))

    first, rest = call(True), call(False)
    outs = first(blocks[0].T, *extras)
    for blk in blocks[1:]:
        outs = rest(blk.T, *extras, *outs)
    return outs


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------


def hist_vmem(bins: int, d: int) -> int:
    """Bytes per tile row of :func:`partition_histogram`."""
    d8, b8 = _pad(d, _SUBLANES), _pad(bins, _SUBLANES)
    # double-buffered input tile; the (bins, tile) compare/one-hot temporaries
    return 4 * 2 * d8 + 4 * 4 * b8 + 4 * _SUBLANES * 4


def _hist_kernel(x, acc, *, bins, lo, hi, rows, tile):
    x = x.astype(jnp.float32)                   # (d, tile) — one row tile
    width = (hi - lo) / bins
    # bin membership per value: edges e_j = lo + j*width ; x in bin j  <=>
    # e_j <= x < e_{j+1}, outliers clamped into the edge bins (jnp.clip
    # digitize semantics).  Mosaic's iota is integer-only: build, then cast.
    bin_id = jax.lax.broadcasted_iota(jnp.int32, (bins, 1), 0)
    edges = lo + width * bin_id.astype(jnp.float32)              # (bins, 1)
    for j in range(x.shape[0]):
        xj = x[j : j + 1, :]                                      # (1, tile)
        hit = (xj >= edges) & (xj < edges + width)
        hit = hit | ((bin_id == 0) & (xj < lo + width))
        hit = hit | ((bin_id == bins - 1) & (xj >= hi - width))
        if rows % tile:
            hit = hit & _tail_mask(rows, tile)
        acc[...] += jnp.sum(hit.astype(jnp.float32), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bins", "lo", "hi", "interpret"))
def partition_histogram(
    stacked: jax.Array,  # (nblocks, rows, d)
    *,
    bins: int = 128,
    lo: float = 0.0,
    hi: float = 1.0,
    interpret: bool = True,
) -> jax.Array:
    """Per-dimension-flattened value histogram of a whole partition → (bins,)."""
    _, rows, d = stacked.shape
    tile = row_tile(rows, hist_vmem(bins, d))
    if tile is None:
        raise ValueError(f"{bins} bins do not fit the VMEM budget")
    (out,) = _tiled_call(
        functools.partial(_hist_kernel, bins=bins, lo=lo, hi=hi, rows=rows, tile=tile),
        tuple(stacked),
        [],
        [jax.ShapeDtypeStruct((bins, 1), jnp.float32)],
        tile=tile,
        interpret=interpret,
    )
    return out[:, 0]


# ---------------------------------------------------------------------------
# d-dimensional histogram (the histogram app's block fn, fused)
# ---------------------------------------------------------------------------


def histdd_high_cells(bins: int, d: int) -> int:
    """Rows ``H`` of the ``(H, 128)`` count grid holding ``bins**d`` cells."""
    return -(-(bins**d) // _LANES)


def histdd_vmem(bins: int, d: int) -> tuple[int, int]:
    """(bytes per tile row, fixed bytes) of :func:`partition_histogramdd_blocks`."""
    h = _pad(histdd_high_cells(bins, d), _SUBLANES)
    d8 = _pad(d, _SUBLANES)
    # double-buffered input tile + digitize temporaries; the two one-hots
    # as compare mask (4 B) + bf16 operand (2 B); flat/high/low row vectors
    per_row = 4 * 2 * d8 + 4 * 2 * d8 + 6 * (h + _LANES) + 4 * _SUBLANES * 4
    # the carried-in and the accumulating output block, (H, 128) f32 each,
    # both double-buffered
    fixed = 4 * 4 * h * _LANES
    return per_row, fixed


def _histdd_kernel(x, acc, *, bins, lo, hi, rows, tile):
    x = x.astype(jnp.float32)                   # (d, tile) — one row tile
    d = x.shape[0]
    # digitize per dimension exactly like histogramdd_block (truncate + clip)
    scaled = (x - lo) / (hi - lo) * bins
    idx = jnp.clip(scaled.astype(jnp.int32), 0, bins - 1)        # (d, tile)
    # flat cell id: row-major over the (bins,)*d grid (static unroll over d —
    # no captured weight constants, which pallas_call rejects)
    flat = jnp.zeros((1, tile), jnp.int32)
    for k in range(d):
        flat = flat * bins + idx[k : k + 1, :]                   # (1, tile)
    if rows % tile:
        flat = jnp.where(_tail_mask(rows, tile), flat, -1)      # matches no cell
    high = acc.shape[0]
    # cell = 128 * high + low; the arithmetic shift keeps -1 out of every row
    onehot_hi = (
        jax.lax.broadcasted_iota(jnp.int32, (high, tile), 0) == flat >> 7
    ).astype(jnp.bfloat16)                       # (H, tile)
    onehot_lo = (
        jax.lax.broadcasted_iota(jnp.int32, (_LANES, tile), 0) == flat & (_LANES - 1)
    ).astype(jnp.bfloat16)                       # (128, tile)
    # 0/1 operands are exact in bf16; counts accumulate in f32
    acc[...] += jax.lax.dot_general(
        onehot_hi, onehot_lo, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (H, 128)


@functools.partial(jax.jit, static_argnames=("bins", "lo", "hi", "interpret"))
def partition_histogramdd_blocks(
    blocks: Sequence[jax.Array],  # same-shape (rows, d), read where they lie
    *,
    bins: int = 8,
    lo: float = 0.0,
    hi: float = 1.0,
    interpret: bool = True,
) -> jax.Array:
    """d-dimensional histogram of a partition's blocks → ``(bins,)*d`` int32.

    Equals ``sum(histogramdd_block(b) for b in blocks)`` bit-exactly — the
    contract the kernel registry requires for fused/generic interchange.
    """
    rows, d = blocks[0].shape
    high = histdd_high_cells(bins, d)
    tile = row_tile(rows, *histdd_vmem(bins, d))
    if tile is None:
        raise ValueError(f"{bins}**{d} cells do not fit the VMEM budget")
    (out,) = _tiled_call(
        functools.partial(_histdd_kernel, bins=bins, lo=lo, hi=hi, rows=rows, tile=tile),
        blocks,
        [],
        [jax.ShapeDtypeStruct((high, _LANES), jnp.float32)],
        tile=tile,
        interpret=interpret,
    )
    cells = bins**d
    return out.reshape(-1)[:cells].astype(jnp.int32).reshape((bins,) * d)


# ---------------------------------------------------------------------------
# k-means partial step
# ---------------------------------------------------------------------------


def kmeans_vmem(d: int, k: int) -> int:
    """Bytes per tile row of :func:`partition_kmeans_blocks`."""
    d8, k8 = _pad(d, _SUBLANES), _pad(k, _SUBLANES)
    # double-buffered input tile + its masked copy; the (k, tile) distance,
    # index, compare and one-hot temporaries; a few (1, tile) row vectors
    return 4 * 3 * d8 + 4 * 6 * k8 + 4 * _SUBLANES * 4


def _kmeans_kernel(x, c_ref, acc_s, acc_c, *, rows, tile):
    x = x.astype(jnp.float32)                    # (d, tile)
    c = c_ref[...].astype(jnp.float32)           # (k, d)
    k = c.shape[0]
    if rows % tile:
        x = jnp.where(_tail_mask(rows, tile), x, 0.0)
    # d2 = |x|^2 - 2 c·x + |c|^2 ; |x|^2 constant per row -> drop for argmin
    cx = jax.lax.dot_general(
        c, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                             # (k, tile) MXU
    d2 = jnp.sum(c * c, axis=1, keepdims=True) - 2.0 * cx
    # first-index argmin over the k centroids (jnp.argmin's tie rule)
    cid = jax.lax.broadcasted_iota(jnp.int32, (k, tile), 0)
    best = jnp.min(d2, axis=0, keepdims=True)
    assign = jnp.min(jnp.where(d2 == best, cid, k), axis=0, keepdims=True)
    hit = cid == assign
    if rows % tile:
        hit = hit & _tail_mask(rows, tile)
    onehot = hit.astype(jnp.float32)              # (k, tile)
    acc_s[...] += jax.lax.dot_general(
        onehot, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                             # (k, d)
    acc_c[...] += jnp.sum(onehot, axis=1, keepdims=True)  # (k, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def partition_kmeans_blocks(
    blocks: Sequence[jax.Array],  # same-shape (rows, d), read where they lie
    centers: jax.Array,           # (k, d)
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused Lloyd partial step over a partition's blocks → (sums (k,d), counts (k,))."""
    rows, d = blocks[0].shape
    k = centers.shape[0]
    tile = row_tile(rows, kmeans_vmem(d, k))
    if tile is None:
        raise ValueError(f"k-means rows of d={d}, k={k} do not fit the VMEM budget")
    sums, counts = _tiled_call(
        functools.partial(_kmeans_kernel, rows=rows, tile=tile),
        blocks,
        [centers],
        [
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        tile=tile,
        interpret=interpret,
    )
    return sums, counts[:, 0]
