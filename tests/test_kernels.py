"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles.

All kernels run in interpret=True (Pallas interpreter on CPU); the same
kernel bodies compile to Mosaic on TPU.
"""

import jax.numpy as jnp
import numpy as np
import pytest

try:  # only the property sweep at the bottom needs hypothesis
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.partition_reduce import (
    MAX_TILE_ROWS,
    partition_histogram,
    partition_kmeans,
)
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(0)


def randn(*shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("b,lq,lk,h,hkv,d", [
        (1, 32, 32, 2, 2, 8),      # MHA
        (2, 64, 64, 4, 2, 16),     # GQA 2:1
        (1, 128, 128, 8, 1, 32),   # MQA
        (2, 48, 96, 4, 4, 64),     # cross-length (q_offset-free, non-causal)
    ])
    def test_shapes_vs_ref(self, b, lq, lk, h, hkv, d):
        q, k, v = randn(b, lq, h, d), randn(b, lk, hkv, d), randn(b, lk, hkv, d)
        causal = lq == lk
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        r = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), **TOL[jnp.float32])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q = randn(2, 64, 4, 16).astype(dtype)
        k = randn(2, 64, 2, 16).astype(dtype)
        v = randn(2, 64, 2, 16).astype(dtype)
        o = flash_attention(q, k, v, block_q=32, block_k=32)
        r = ref.attention_ref(q, k, v)
        assert o.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(r, np.float32), **TOL[dtype]
        )

    @pytest.mark.parametrize("window", [8, 24, 64])
    def test_sliding_window(self, window):
        q, k, v = randn(1, 64, 2, 16), randn(1, 64, 2, 16), randn(1, 64, 2, 16)
        o = flash_attention(q, k, v, window=window, block_q=16, block_k=16)
        r = ref.attention_ref(q, k, v, window=window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), **TOL[jnp.float32])

    @pytest.mark.parametrize("bq,bk", [(8, 8), (16, 32), (32, 16), (64, 64)])
    def test_block_shape_invariance(self, bq, bk):
        """Output must not depend on the BlockSpec tiling."""
        q, k, v = randn(1, 64, 2, 16), randn(1, 64, 2, 16), randn(1, 64, 2, 16)
        o = flash_attention(q, k, v, block_q=bq, block_k=bk)
        r = ref.attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), **TOL[jnp.float32])


class TestPartitionReduce:
    @pytest.mark.parametrize("nb,rows,d,bins", [
        (1, 16, 2, 8), (4, 32, 4, 16), (8, 64, 1, 128), (3, 8, 8, 32),
    ])
    def test_histogram_shapes(self, nb, rows, d, bins):
        st_ = jnp.asarray(RNG.uniform(0, 1, (nb, rows, d)).astype(np.float32))
        h = partition_histogram(st_, bins=bins, lo=0.0, hi=1.0)
        r = ref.histogram_ref(st_, bins=bins, lo=0.0, hi=1.0)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(r))
        assert int(h.sum()) == nb * rows * d

    def test_histogram_outliers_clamped(self):
        st_ = jnp.asarray(RNG.normal(0.5, 2.0, (2, 32, 2)).astype(np.float32))
        h = partition_histogram(st_, bins=8, lo=0.0, hi=1.0)
        r = ref.histogram_ref(st_, bins=8, lo=0.0, hi=1.0)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(r))

    @pytest.mark.parametrize("nb,rows,d,k", [
        (1, 16, 4, 2), (4, 32, 8, 4), (6, 24, 3, 8),
    ])
    def test_kmeans_shapes(self, nb, rows, d, k):
        st_ = randn(nb, rows, d)
        cen = randn(k, d)
        sums, counts = partition_kmeans(st_, cen)
        rs, rc = ref.kmeans_ref(st_, cen)
        np.testing.assert_allclose(np.asarray(sums), np.asarray(rs), rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(rc))

    @pytest.mark.parametrize("nb,rows,d,bins", [
        (1, 16, 1, 8), (4, 32, 2, 4), (3, 8, 3, 4), (2, 64, 1, 128),
    ])
    def test_histogramdd_matches_block_fn(self, nb, rows, d, bins):
        """The fused-kernel contract: partition_histogramdd == folding the
        app's histogramdd_block over the stacked blocks with + (bit-exact)."""
        from repro.core.apps.histogram import histogramdd_block
        from repro.kernels.partition_reduce import partition_histogramdd

        st_ = jnp.asarray(RNG.uniform(0, 1, (nb, rows, d)).astype(np.float32))
        h = partition_histogramdd(st_, bins=bins, lo=0.0, hi=1.0)
        want = sum(
            histogramdd_block(st_[i], bins=bins, lo=0.0, hi=1.0) for i in range(nb)
        )
        assert h.shape == (bins,) * d and h.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(h), np.asarray(want))
        assert int(h.sum()) == nb * rows          # one cell per row

    def test_histogramdd_outliers_clamped(self):
        from repro.core.apps.histogram import histogramdd_block
        from repro.kernels.partition_reduce import partition_histogramdd

        st_ = jnp.asarray(RNG.normal(0.5, 2.0, (3, 16, 2)).astype(np.float32))
        h = partition_histogramdd(st_, bins=4, lo=0.0, hi=1.0)
        want = sum(histogramdd_block(st_[i], bins=4, lo=0.0, hi=1.0) for i in range(3))
        np.testing.assert_array_equal(np.asarray(h), np.asarray(want))

    def test_histogramdd_block_count_invariance(self):
        """Same data, different block counts → identical flat grid (the
        kernel-level granularity-decoupling claim, d-dimensional)."""
        from repro.kernels.partition_reduce import partition_histogramdd

        x = jnp.asarray(RNG.uniform(0, 1, (64, 2)).astype(np.float32))
        outs = [
            partition_histogramdd(x.reshape(nb, -1, 2), bins=4, lo=0.0, hi=1.0)
            for nb in (1, 2, 4, 8)
        ]
        for h in outs[1:]:
            np.testing.assert_array_equal(np.asarray(h), np.asarray(outs[0]))

    def test_kmeans_block_count_invariance(self):
        """Same data split into different block counts → identical result
        (the kernel-level SplIter granularity-decoupling claim)."""
        x = randn(8 * 16, 4)
        cen = randn(4, 4)
        outs = []
        for nb in (1, 2, 4, 8):
            st_ = x.reshape(nb, -1, 4)
            outs.append(partition_kmeans(st_, cen))
        for s, c in outs[1:]:
            np.testing.assert_allclose(
                np.asarray(s), np.asarray(outs[0][0]), rtol=1e-5, atol=1e-5
            )
            np.testing.assert_array_equal(np.asarray(c), np.asarray(outs[0][1]))

    # rows past MAX_TILE_ROWS walk the block in tiles: 8,192 + 300 leaves a
    # masked 300-row tail, 2 x 8,192 splits evenly
    @pytest.mark.parametrize("nb,rows,d,k", [
        (2, MAX_TILE_ROWS + 300, 20, 8), (1, 2 * MAX_TILE_ROWS, 5, 3),
    ])
    def test_kmeans_row_tiles_mask_the_tail(self, nb, rows, d, k):
        """No row of a partial last tile's padding reaches a sum or a count."""
        st_ = randn(nb, rows, d)
        cen = randn(k, d)
        sums, counts = partition_kmeans(st_, cen)
        rs, rc = ref.kmeans_ref(st_, cen)
        np.testing.assert_allclose(np.asarray(sums), np.asarray(rs), rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(rc))

    @pytest.mark.parametrize("nb,rows,d", [
        (2, MAX_TILE_ROWS + 300, 3), (1, 2 * MAX_TILE_ROWS, 5),
    ])
    def test_histogramdd_row_tiles_mask_the_tail(self, nb, rows, d):
        from repro.core.apps.histogram import histogramdd_block
        from repro.kernels.partition_reduce import partition_histogramdd

        st_ = jnp.asarray(RNG.normal(0.5, 0.7, (nb, rows, d)).astype(np.float32))
        h = partition_histogramdd(st_, bins=8, lo=0.0, hi=1.0)
        want = sum(
            histogramdd_block(st_[i], bins=8, lo=0.0, hi=1.0) for i in range(nb)
        )
        np.testing.assert_array_equal(np.asarray(h), np.asarray(want))

    def test_histogram_row_tiles_mask_the_tail(self):
        st_ = jnp.asarray(
            RNG.normal(0.5, 0.7, (2, MAX_TILE_ROWS + 300, 3)).astype(np.float32)
        )
        h = partition_histogram(st_, bins=8, lo=0.0, hi=1.0)
        r = ref.histogram_ref(st_, bins=8, lo=0.0, hi=1.0)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(r))

    def test_row_tile_is_bounded_by_vmem_not_block_rows(self):
        from repro.kernels.partition_reduce import (
            VMEM_BUDGET_BYTES,
            kmeans_vmem,
            row_tile,
        )

        per_row = kmeans_vmem(20, 8)
        assert row_tile(1000, per_row) == 1000      # a small block is one tile
        for rows in (204800, 1 << 22):
            tile = row_tile(rows, per_row)
            assert tile % 128 == 0 and tile * per_row <= VMEM_BUDGET_BYTES
        assert row_tile(204800, VMEM_BUDGET_BYTES) is None  # not even 128 rows

    @pytest.mark.parametrize("d,nb,rows,admitted", [
        (5, 16, 32768, True),     # 32,768 cells
        (6, 4, 4096, True),       # 262,144 cells
        (7, 4, 4096, False),      # 2**21 cells: the accumulator alone overflows
        (3, 1024, 1 << 14, False),  # 2**24 rows: f32 counts no longer exact
    ])
    def test_histogram_guard_reckons_vmem_bytes(self, d, nb, rows, admitted):
        import functools

        from repro.api.kernels import partition_kernel_for
        from repro.core.apps.histogram import histogramdd_block

        kernel = partition_kernel_for(
            functools.partial(histogramdd_block, bins=8, lo=0.0, hi=1.0)
        )
        assert kernel.supported((nb, rows, d), ()) is admitted


class TestSSDScan:
    @pytest.mark.parametrize("b,l,nh,p,n,chunk", [
        (1, 32, 1, 4, 8, 8),
        (2, 64, 3, 8, 16, 16),
        (1, 128, 2, 16, 32, 32),
        (2, 64, 4, 8, 16, 64),   # single chunk
    ])
    def test_shapes_vs_sequential_ref(self, b, l, nh, p, n, chunk):
        x = randn(b, l, nh, p)
        dt = jnp.asarray(RNG.uniform(0.1, 0.9, (b, l, nh)).astype(np.float32))
        a = jnp.asarray(-RNG.uniform(0.5, 1.5, (nh,)).astype(np.float32))
        bm, cm = randn(b, l, n), randn(b, l, n)
        y, hf = ssd_scan(x, dt, a, bm, cm, chunk=chunk)
        yr, hr = ref.ssd_ref(x, dt, a, bm, cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), rtol=3e-4, atol=3e-4)

    def test_chunk_invariance(self):
        """Output independent of the chunking (BlockSpec) choice."""
        b, l, nh, p, n = 1, 64, 2, 8, 16
        x = randn(b, l, nh, p)
        dt = jnp.asarray(RNG.uniform(0.1, 0.9, (b, l, nh)).astype(np.float32))
        a = jnp.asarray(-RNG.uniform(0.5, 1.5, (nh,)).astype(np.float32))
        bm, cm = randn(b, l, n), randn(b, l, n)
        base, hbase = ssd_scan(x, dt, a, bm, cm, chunk=8)
        for chunk in (16, 32, 64):
            y, hf = ssd_scan(x, dt, a, bm, cm, chunk=chunk)
            np.testing.assert_allclose(np.asarray(y), np.asarray(base), rtol=3e-4, atol=3e-4)
            np.testing.assert_allclose(np.asarray(hf), np.asarray(hbase), rtol=3e-4, atol=3e-4)


if HAVE_HYPOTHESIS:

    @given(
        lq=st.sampled_from([16, 32, 64]),
        h=st.sampled_from([2, 4]),
        hkv=st.sampled_from([1, 2]),
        d=st.sampled_from([8, 16]),
        causal=st.booleans(),
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=12, deadline=None)
    def test_flash_attention_property(lq, h, hkv, d, causal, seed):
        """Hypothesis sweep: kernel == oracle over random geometry."""
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(1, lq, h, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, lq, hkv, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, lq, hkv, d)).astype(np.float32))
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        r = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=3e-5, atol=3e-5)

else:  # keep the skip visible in the report when hypothesis is absent

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_flash_attention_property():
        pass
