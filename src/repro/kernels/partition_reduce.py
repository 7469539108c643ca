"""partition_reduce — the paper's ``compute_partition`` at the VMEM level.

The SplIter idea expressed as a TPU kernel (DESIGN.md §2, layer L3): the
*grid iterates the blocks of a partition* while the reduction accumulator
stays resident in VMEM; one ``pallas_call`` per partition regardless of how
many HBM blocks compose it.  Block size (HBM layout granularity) is thereby
decoupled from kernel-invocation granularity — exactly the paper's
decoupling, one level down.

The same decoupling holds one level further down: the fused kernels walk
each block in *row tiles* (a second grid axis), so VMEM use is set by the
tile, never by the block's row count.  The tile is sized by reckoning the
kernel's VMEM bytes per row against :data:`VMEM_BUDGET_BYTES`; a block that
is not a multiple of the tile has its tail masked in the kernel (the DMA of
a partial tile never reads past the block).  The kernels read the stacked
blocks transposed, ``(nblocks, d, rows)``, so rows run along the 128 vector
lanes and a narrow row (d = 5, d = 20) is not padded out to 128 lanes.

Three ops, matching the paper's memory-bound applications:

* :func:`partition_histogram` — scatter-free 1-d value histogram: each
  row tile's values are compared against the bin edges (one-hot via two
  comparisons) and counted with a lane reduction; the (bins,) accumulator
  never leaves VMEM until the final grid step.  Off the engine's main path
  (``repro.kernels.ops`` only).

* :func:`partition_kmeans` — fused Lloyd partial step: per row tile,
  squared distances to centroids via MXU matmul, hard assignment, one-hot
  matmul accumulation of per-centroid sums and counts in VMEM.

* :func:`partition_histogramdd` — the d-dimensional generalization used by
  the histogram app's fused lowering: rows are digitized per dimension,
  combined into a flat ``bins**d`` cell index, and accumulated scatter-free.
  The cell index is split as ``cell = 128 * high + low``, so the count grid
  is one MXU matmul of two small one-hots, ``(H, tile) @ (tile, 128)``,
  instead of a ``(tile, bins**d)`` one-hot.  Bit-exact against the
  per-block ``histogramdd_block`` + sum-combine path (integer counts,
  float32 accumulation is exact below 2**24).

Inputs are the partition's stacked blocks ``(nblocks, rows, d)`` — i.e.
``Partition.stacked()`` — so the engine can hand a partition straight to
the kernel.  The execution layer reaches these through the kernel registry
(``repro.api.kernels``): lowering a ``SplIter(fusion="pallas")`` plan emits
one such call per same-shape run of a partition.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    return pl.program_id(1) * tile + lane < rows


def _tiled_call(kernel, stacked, extras, out_shapes, scratch, *, tile, interpret):
    """One ``pallas_call`` over a (block, row tile) grid of ``stacked``.

    ``stacked`` is ``(nblocks, rows, d)``; the kernel sees ``(d, tile)``
    slices of its transpose.  ``extras`` and every output are whole arrays
    resident across the grid (the accumulator pattern).
    """
    nb, rows, d = stacked.shape
    xt = jnp.swapaxes(stacked, 1, 2)  # (nb, d, rows): rows along lanes

    def whole(shape):
        return pl.BlockSpec(shape, lambda b, t: (0,) * len(shape))

    return pl.pallas_call(
        kernel,
        grid=(nb, pl.cdiv(rows, tile)),
        in_specs=[pl.BlockSpec((1, d, tile), lambda b, t: (b, 0, t))]
        + [whole(e.shape) for e in extras],
        out_specs=[whole(o.shape) for o in out_shapes],
        out_shape=out_shapes,
        scratch_shapes=scratch,
        interpret=interpret,
    )(xt, *extras)


def _first_step():
    return (pl.program_id(0) == 0) & (pl.program_id(1) == 0)


def _last_step():
    return (pl.program_id(0) == pl.num_programs(0) - 1) & (
        pl.program_id(1) == pl.num_programs(1) - 1
    )


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------


def hist_vmem(bins: int, d: int) -> int:
    """Bytes per tile row of :func:`partition_histogram`."""
    d8, b8 = _pad(d, _SUBLANES), _pad(bins, _SUBLANES)
    # double-buffered input tile; the (bins, tile) compare/one-hot temporaries
    return 4 * 2 * d8 + 4 * 4 * b8 + 4 * _SUBLANES * 4


def _hist_kernel(x_ref, o_ref, acc, *, bins, lo, hi, rows, tile):
    @pl.when(_first_step())
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[0].astype(jnp.float32)            # (d, tile) — one row tile
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

    @pl.when(_last_step())
    def _flush():
        o_ref[...] = acc[...]


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
    nb, rows, d = stacked.shape
    tile = row_tile(rows, hist_vmem(bins, d))
    if tile is None:
        raise ValueError(f"{bins} bins do not fit the VMEM budget")
    (out,) = _tiled_call(
        functools.partial(_hist_kernel, bins=bins, lo=lo, hi=hi, rows=rows, tile=tile),
        stacked,
        [],
        [jax.ShapeDtypeStruct((bins, 1), jnp.float32)],
        [pltpu.VMEM((bins, 1), jnp.float32)],
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
    """(bytes per tile row, fixed bytes) of :func:`partition_histogramdd`."""
    h = _pad(histdd_high_cells(bins, d), _SUBLANES)
    d8 = _pad(d, _SUBLANES)
    # double-buffered input tile + digitize temporaries; the two one-hots
    # as compare mask (4 B) + bf16 operand (2 B); flat/high/low row vectors
    per_row = 4 * 2 * d8 + 4 * 2 * d8 + 6 * (h + _LANES) + 4 * _SUBLANES * 4
    # accumulator + double-buffered output block, both (H, 128) f32
    fixed = 3 * 4 * h * _LANES
    return per_row, fixed


def _histdd_kernel(x_ref, o_ref, acc, *, bins, lo, hi, rows, tile):
    @pl.when(_first_step())
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[0].astype(jnp.float32)            # (d, tile) — one row tile
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

    @pl.when(_last_step())
    def _flush():
        o_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("bins", "lo", "hi", "interpret"))
def partition_histogramdd(
    stacked: jax.Array,  # (nblocks, rows, d)
    *,
    bins: int = 8,
    lo: float = 0.0,
    hi: float = 1.0,
    interpret: bool = True,
) -> jax.Array:
    """d-dimensional histogram of a whole partition → ``(bins,)*d`` int32.

    Equals ``sum(histogramdd_block(b) for b in blocks)`` bit-exactly — the
    contract the kernel registry requires for fused/generic interchange.
    """
    nb, rows, d = stacked.shape
    high = histdd_high_cells(bins, d)
    tile = row_tile(rows, *histdd_vmem(bins, d))
    if tile is None:
        raise ValueError(f"{bins}**{d} cells do not fit the VMEM budget")
    (out,) = _tiled_call(
        functools.partial(_histdd_kernel, bins=bins, lo=lo, hi=hi, rows=rows, tile=tile),
        stacked,
        [],
        [jax.ShapeDtypeStruct((high, _LANES), jnp.float32)],
        [pltpu.VMEM((high, _LANES), jnp.float32)],
        tile=tile,
        interpret=interpret,
    )
    cells = bins**d
    return out.reshape(-1)[:cells].astype(jnp.int32).reshape((bins,) * d)


# ---------------------------------------------------------------------------
# k-means partial step
# ---------------------------------------------------------------------------


def kmeans_vmem(d: int, k: int) -> int:
    """Bytes per tile row of :func:`partition_kmeans`."""
    d8, k8 = _pad(d, _SUBLANES), _pad(k, _SUBLANES)
    # double-buffered input tile + its masked copy; the (k, tile) distance,
    # index, compare and one-hot temporaries; a few (1, tile) row vectors
    return 4 * 3 * d8 + 4 * 6 * k8 + 4 * _SUBLANES * 4


def _kmeans_kernel(x_ref, c_ref, sums_ref, counts_ref, acc_s, acc_c, *, rows, tile):
    @pl.when(_first_step())
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        acc_c[...] = jnp.zeros_like(acc_c)

    x = x_ref[0].astype(jnp.float32)             # (d, tile)
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

    @pl.when(_last_step())
    def _flush():
        sums_ref[...] = acc_s[...]
        counts_ref[...] = acc_c[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def partition_kmeans(
    stacked: jax.Array,   # (nblocks, rows, d)
    centers: jax.Array,   # (k, d)
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused Lloyd partial step over a partition → (sums (k,d), counts (k,))."""
    nb, rows, d = stacked.shape
    k = centers.shape[0]
    tile = row_tile(rows, kmeans_vmem(d, k))
    if tile is None:
        raise ValueError(f"k-means rows of d={d}, k={k} do not fit the VMEM budget")
    sums, counts = _tiled_call(
        functools.partial(_kmeans_kernel, rows=rows, tile=tile),
        stacked,
        [centers],
        [
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        [pltpu.VMEM((k, d), jnp.float32), pltpu.VMEM((k, 1), jnp.float32)],
        tile=tile,
        interpret=interpret,
    )
    return sums, counts[:, 0]
