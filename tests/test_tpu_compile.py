"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: each test compiles one kernel for a *described* v5e (the TPU
compiler is installed even where no chip is attached) and checks that the
Mosaic kernel is in the program.  This is what the interpreter cannot
show: a kernel that asks for more VMEM than the chip has, or that uses an
op Mosaic refuses, fails here.  Shapes are the per-partition runs of the
cells in ``chipbench/`` (HiBench k-means large and gigantic), with the
benches' ``--full`` sizes and masked tails beside them.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU runtime, and the test workers must all
collect the same tests.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.partition_reduce import (
    partition_histogram,
    partition_histogramdd_blocks,
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
def four_chips(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices), ("loc",))


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


def _compile_blocks(fn, one_chip, form, run, *shapes):
    """Compile ``fn(blocks, *rest)`` for a run ``(nblocks, rows, d)`` of blocks.

    ``form`` "blocks" passes separately allocated buffers, as the engine
    does; "stacked" passes slices of one ``(nblocks, rows, d)`` buffer.
    """
    nb, rows, d = run
    rest = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in shapes]
    if form == "stacked":
        stacked = jax.ShapeDtypeStruct(run, jnp.float32, sharding=one_chip)
        program = jax.jit(lambda s, *r: fn(tuple(s), *r))
        return program.lower(stacked, *rest).compile().as_text()
    block = jax.ShapeDtypeStruct((rows, d), jnp.float32, sharding=one_chip)
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


# Each run compiles as separate buffers, where the kernel must read every
# block in place, or as slices of one stacked buffer.
@pytest.mark.parametrize(
    "form,run,k",
    [
        ("blocks", (16, 156250, 20), 10),  # HiBench large: a kmeans.spliter partition
        ("blocks", (16, 204800, 20), 8),   # chip_smoke kmeans partition
        ("blocks", (2, 10000, 20), 8),     # masked tail
        ("stacked", (16, 204800, 20), 8),
        ("stacked", (16, 4096, 20), 8),    # bench_kmeans --full, 16 blocks per location
        ("stacked", (1, 65536, 20), 8),    # bench_kmeans --full, 1 block per location
        ("stacked", (2, 10000, 20), 8),    # rows not a multiple of the tile
    ],
)
def test_partition_kmeans_compiles(one_chip, no_persistent_cache, form, run, k):
    hlo = _compile_blocks(
        lambda b, c: partition_kmeans_blocks(b, c, interpret=False),
        one_chip, form, run, (k, run[-1]),
    )
    assert "tpu_custom_call" in hlo
    if form == "blocks":
        assert _block_sized_results(hlo, run[1]) == []


@pytest.mark.parametrize(
    "form,run",
    [
        ("blocks", (16, 32768, 5)),   # chip_smoke histogram partition (32,768 cells)
        ("blocks", (2, 10000, 5)),    # masked tail
        ("blocks", (4, 4096, 6)),     # the most cells the app's guard admits at bins=8
        ("stacked", (16, 32768, 5)),
        ("stacked", (16, 8192, 5)),   # bench_histogram --full, 16 blocks per location
        ("stacked", (1, 131072, 5)),  # bench_histogram --full, 1 block per location
        ("stacked", (2, 10000, 5)),
        ("stacked", (4, 4096, 6)),
    ],
)
def test_partition_histogramdd_compiles(one_chip, no_persistent_cache, form, run):
    hlo = _compile_blocks(
        lambda b: partition_histogramdd_blocks(b, bins=8, interpret=False),
        one_chip, form, run,
    )
    assert "tpu_custom_call" in hlo
    if form == "blocks":
        assert _block_sized_results(hlo, run[1]) == []


def test_partition_histogram_compiles(one_chip, no_persistent_cache):
    hlo = _compile(
        lambda s: partition_histogram(s, bins=128, interpret=False),
        one_chip, (16, 32768, 5),
    )
    assert "tpu_custom_call" in hlo


def test_mesh_program_runs_the_block_entries_in_place_on_each_chip(four_chips, no_persistent_cache):
    """The mesh's one program of a HiBench gigantic k-means iteration: each of
    the four chips runs the Pallas block entry over its own 32 blocks of
    1,562,500 x 20, as they lie, then the ranks all-gather their partials."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.api.mesh_executor import _sharded_program
    from repro.core.apps.kmeans import _combine

    nb, rows, d, k = 32, 1_562_500, 20, 10
    program = _sharded_program(
        lambda b, c: partition_kmeans_blocks(b, c, interpret=False),
        _combine, 1, 1, four_chips, "loc", 1,
    )
    block = jax.ShapeDtypeStruct((4 * rows, d), jnp.float32,
                                 sharding=NamedSharding(four_chips, P("loc")))
    centers = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=NamedSharding(four_chips, P()))
    compiled = jax.jit(program).lower((block,) * nb, centers).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= nb
    assert "all-gather" in hlo
    assert _block_sized_results(hlo, rows) == []
    # nothing block-sized is made: no stack or copy in flight beside the data
    assert compiled.memory_analysis().temp_size_in_bytes < rows * d * 4
