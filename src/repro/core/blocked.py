"""Blocked distributed collections — the substrate the SplIter operates on.

The paper's frameworks (COMPSs+dataClay, Dask) hold a dataset as a set of
*blocks* distributed across *nodes*.  Here a :class:`BlockedArray` holds a
dataset as a sequence of row-blocks, each with an explicit *placement* — a
logical location id that models "which node/backend holds this block".

Two execution substrates consume this metadata:

* the paper-faithful task engine (``repro.core.engine``) which dispatches
  work per block / per partition and uses placements for locality, and
* the mesh substrate (``repro.data.pipeline``) where placement is derived
  from a ``jax.sharding.NamedSharding`` over a device mesh (the production
  path), so placement queries are exact — the JAX analogue of Dask
  ``who_has`` / dataClay metadata lookups.

On several devices a location is also a device: :func:`location_device`
names the device of each location, and :meth:`BlockedArray.placed_on` puts
every block on it, where the mesh backend then reads it in place.

Blocks are dense ``(block_rows, *row_shape)`` arrays.  The *global order* of
rows (paper §4.1) is ``block_id``-major: row ``r`` of block ``b`` has global
index ``offset[b] + r``.

**Out-of-core blocks.**  A block may also be a
:class:`~repro.api.chunkstore.ChunkRef` — a metadata handle (same
``shape``/``dtype``/``nbytes`` surface as an array) whose buffer lives in a
:class:`~repro.api.chunkstore.ChunkStore` and materializes only at dispatch
time.  All geometry here (placements, row offsets, ``blocks_at``) is
metadata-only and works on refs without loading a byte; anything that needs
buffer contents goes through :meth:`BlockedArray.block` /
:meth:`BlockedArray.iter_blocks`, which resolve refs transparently.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BlockedArray",
    "PlacementPolicy",
    "round_robin_placement",
    "contiguous_placement",
    "location_device",
]

# A placement policy maps (num_blocks, num_locations) -> per-block location ids.
PlacementPolicy = Callable[[int, int], np.ndarray]


def round_robin_placement(num_blocks: int, num_locations: int) -> np.ndarray:
    """Block *b* lives on location ``b % L`` — models Dask's default scatter."""
    return np.arange(num_blocks, dtype=np.int32) % num_locations


def contiguous_placement(num_blocks: int, num_locations: int) -> np.ndarray:
    """Consecutive runs of blocks per location — models dislib/dataClay fills."""
    per = math.ceil(num_blocks / num_locations)
    return (np.arange(num_blocks, dtype=np.int32) // per).clip(0, num_locations - 1)


def location_device(location: int, num_locations: int, devices: Sequence):
    """The device that holds ``location``'s blocks.

    Of L locations on M devices, location ``l`` goes to device
    ``l * M // L``: contiguous runs of locations share a device, the
    grouping in which :class:`~repro.api.mesh_executor.MeshExecutor` deals
    a location-major task list over its mesh ranks.  Whoever makes or
    places blocks and the mesh both ask this one function.

    >>> [location_device(l, 8, "ab") for l in range(8)]
    ['a', 'a', 'a', 'a', 'b', 'b', 'b', 'b']
    """
    if not 0 <= location < num_locations:
        raise ValueError(f"location {location} is not in [0, {num_locations})")
    return devices[location * len(devices) // num_locations]


@dataclasses.dataclass(frozen=True)
class BlockedArray:
    """A row-blocked dataset with explicit block placement.

    Attributes:
      blocks: tuple of ``(rows_b, *row_shape)`` jax arrays — or
        :class:`~repro.api.chunkstore.ChunkRef` handles to store-held
        buffers — in global order.
      placements: int32 array ``(num_blocks,)`` — logical location per block.
      num_locations: number of logical locations (nodes/backends/devices).
    """

    blocks: tuple
    placements: np.ndarray
    num_locations: int

    def __post_init__(self):
        assert len(self.blocks) == len(self.placements), (
            len(self.blocks),
            len(self.placements),
        )
        assert len(self.blocks) > 0, "empty BlockedArray"
        row_shape = self.blocks[0].shape[1:]
        for b in self.blocks:
            assert b.shape[1:] == row_shape, "inconsistent row shapes"

    # -- construction ------------------------------------------------------

    @classmethod
    def from_array(
        cls,
        x: jax.Array,
        block_rows: int,
        *,
        num_locations: int = 1,
        policy: PlacementPolicy = contiguous_placement,
        store=None,
    ) -> "BlockedArray":
        """Split ``x`` along axis 0 into blocks of ``block_rows`` rows.

        The final block may be short (ragged tail), exactly like a Dask
        array whose shape is not a multiple of the chunk size.  With
        ``store`` (a :class:`~repro.api.chunkstore.ChunkStore`) each block
        is ``put`` into the store and the collection holds
        :class:`~repro.api.chunkstore.ChunkRef` handles instead of
        resident buffers — a ``DiskStore`` then bounds how much of the
        dataset is in memory at once.
        """
        n = x.shape[0]
        assert block_rows >= 1
        nb = math.ceil(n / block_rows)
        blocks = tuple(x[i * block_rows : (i + 1) * block_rows] for i in range(nb))
        if store is not None:
            blocks = tuple(store.put(b) for b in blocks)
        return cls(blocks, policy(nb, num_locations), num_locations)

    @classmethod
    def from_blocks(
        cls,
        blocks: Sequence[jax.Array],
        placements: Sequence[int] | np.ndarray,
        num_locations: int,
    ) -> "BlockedArray":
        return cls(tuple(blocks), np.asarray(placements, np.int32), num_locations)

    # -- geometry ----------------------------------------------------------

    @property
    def num_blocks(self) -> int:
        return len(self.blocks)

    @property
    def row_shape(self) -> tuple[int, ...]:
        return self.blocks[0].shape[1:]

    @property
    def dtype(self):
        return self.blocks[0].dtype

    @property
    def block_rows(self) -> tuple[int, ...]:
        return tuple(b.shape[0] for b in self.blocks)

    @property
    def num_rows(self) -> int:
        return sum(self.block_rows)

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(b.shape)) * b.dtype.itemsize for b in self.blocks)

    @property
    def uniform(self) -> bool:
        """True when every block has the same number of rows."""
        rows = self.block_rows
        return all(r == rows[0] for r in rows)

    def row_offsets(self) -> np.ndarray:
        """Global row index of the first row of each block (paper §4.1)."""
        return np.concatenate([[0], np.cumsum(self.block_rows)[:-1]]).astype(np.int64)

    def blocks_at(self, location: int) -> list[int]:
        """The block ids resident at ``location`` — the `who_has` query."""
        return [int(i) for i in np.nonzero(self.placements == location)[0]]

    # -- buffer access (resolves chunk refs) --------------------------------

    def block(self, i: int) -> jax.Array:
        """Block ``i``'s buffer, resolving a chunk ref if necessary."""
        from repro.api.chunkstore import resolve_chunk

        return resolve_chunk(self.blocks[i])

    def iter_blocks(self):
        """Yield resolved block buffers in global order, one at a time.

        The streaming-friendly accessor: out-of-core consumers touch one
        block's bytes at a time instead of holding ``self.blocks``.
        """
        for i in range(len(self.blocks)):
            yield self.block(i)

    @property
    def is_chunked(self) -> bool:
        """True when any block is a store-held chunk reference."""
        from repro.api.chunkstore import ChunkRef

        return any(isinstance(b, ChunkRef) for b in self.blocks)

    def to_store(self, store) -> "BlockedArray":
        """Move every block into ``store``; same blocking, ref-backed."""
        from repro.api.chunkstore import resolve_chunk

        refs = tuple(store.put(resolve_chunk(b)) for b in self.blocks)
        return BlockedArray(refs, self.placements, self.num_locations)

    # -- conversions -------------------------------------------------------

    def collect(self) -> jax.Array:
        """Concatenate all blocks in global order (a full gather)."""
        return jnp.concatenate(list(self.iter_blocks()), axis=0)

    def stacked(self) -> jax.Array:
        """Stack uniform blocks into ``(num_blocks, block_rows, *row_shape)``."""
        assert self.uniform, "stacked() requires uniform block sizes"
        return jnp.stack(list(self.iter_blocks()), axis=0)

    def placed_on(self, devices: Sequence) -> "BlockedArray":
        """The same blocks, each on the device its location names
        (:func:`location_device`).

        A block that already lies there is kept as it is; chunk refs are
        resolved.  The placements and the global order are unchanged.
        """
        blocks = tuple(
            jax.device_put(self.block(i), location_device(int(loc), self.num_locations, devices))
            for i, loc in enumerate(self.placements)
        )
        return BlockedArray(blocks, self.placements, self.num_locations)

    def with_placements(self, placements: np.ndarray, num_locations: int) -> "BlockedArray":
        return BlockedArray(self.blocks, np.asarray(placements, np.int32), num_locations)
