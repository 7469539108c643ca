"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: each test compiles one kernel for a *described* v5e (the TPU
compiler is installed even where no chip is attached) and checks that the
Mosaic kernel is in the program.  This is what the interpreter cannot
show: a kernel that asks for more VMEM than the chip has, or that uses an
op Mosaic refuses, fails here.  Shapes are the per-partition blocks of
``chip_smoke.py`` and of the benches' ``--full`` sizes.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU runtime, and the test workers must all
collect the same tests.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.partition_reduce import (
    partition_histogram,
    partition_histogramdd,
    partition_histogramdd_blocks,
    partition_kmeans,
    partition_kmeans_blocks,
)

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Described-chip programs can be written to the cache but not read back."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile_blocks(fn, one_chip, run, *shapes):
    """Compile ``fn(blocks, *rest)`` for a run ``(nblocks, rows, d)`` of blocks."""
    nb, rows, d = run
    block = jax.ShapeDtypeStruct((rows, d), jnp.float32, sharding=one_chip)
    rest = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in shapes]
    return jax.jit(fn).lower((block,) * nb, *rest).compile().as_text()


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([\w-]+)\(", re.M)


def _block_sized_results(hlo: str, rows: int) -> list[str]:
    """Opcodes of the instructions that make an array with a block's rows
    other than a parameter or a bitcast: a copy, a transpose, a fusion."""
    dims = re.compile(rf"\[(?:\d+,)*{rows}(?:,\d+)*\]")
    return [
        op for shape, op in _INSTRUCTION.findall(hlo)
        if dims.search(shape) and op not in ("parameter", "bitcast")
    ]


@pytest.mark.parametrize(
    "shape,k",
    [
        ((16, 204800, 20), 8),   # chip_smoke kmeans partition
        ((16, 4096, 20), 8),     # bench_kmeans --full, 16 blocks per location
        ((1, 65536, 20), 8),     # bench_kmeans --full, 1 block per location
        ((2, 10000, 20), 8),     # rows not a multiple of the tile: masked tail
    ],
)
def test_partition_kmeans_compiles(one_chip, no_persistent_cache, shape, k):
    hlo = _compile(
        lambda s, c: partition_kmeans(s, c, interpret=False),
        one_chip, shape, (k, shape[-1]),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize(
    "run,k",
    [
        ((16, 156250, 20), 10),  # HiBench large: a kmeans.spliter partition
        ((16, 204800, 20), 8),   # chip_smoke kmeans partition
        ((2, 10000, 20), 8),     # masked tail
    ],
)
def test_partition_kmeans_block_entry_reads_blocks_in_place(one_chip, no_persistent_cache, run, k):
    hlo = _compile_blocks(
        lambda b, c: partition_kmeans_blocks(b, c, interpret=False),
        one_chip, run, (k, run[-1]),
    )
    assert "tpu_custom_call" in hlo
    assert _block_sized_results(hlo, run[1]) == []


@pytest.mark.parametrize(
    "shape,bins",
    [
        ((16, 32768, 5), 8),     # chip_smoke histogram partition (32,768 cells)
        ((16, 8192, 5), 8),      # bench_histogram --full, 16 blocks per location
        ((1, 131072, 5), 8),     # bench_histogram --full, 1 block per location
        ((2, 10000, 5), 8),      # masked tail
        ((4, 4096, 6), 8),       # the most cells the app's guard admits at bins=8
    ],
)
def test_partition_histogramdd_compiles(one_chip, no_persistent_cache, shape, bins):
    hlo = _compile(
        lambda s: partition_histogramdd(s, bins=bins, interpret=False),
        one_chip, shape,
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("run", [(16, 32768, 5), (2, 10000, 5), (4, 4096, 6)])
def test_partition_histogramdd_block_entry_reads_blocks_in_place(
    one_chip, no_persistent_cache, run
):
    hlo = _compile_blocks(
        lambda b: partition_histogramdd_blocks(b, bins=8, interpret=False), one_chip, run
    )
    assert "tpu_custom_call" in hlo
    assert _block_sized_results(hlo, run[1]) == []


def test_partition_histogram_compiles(one_chip, no_persistent_cache):
    hlo = _compile(
        lambda s: partition_histogram(s, bins=128, interpret=False),
        one_chip, (16, 32768, 5),
    )
    assert "tpu_custom_call" in hlo
