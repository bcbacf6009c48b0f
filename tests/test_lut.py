"""Tests for the LUT GEMM/GEMV algorithms: T-SAR on-the-fly vs memory-LUT
baseline vs dense reference, including the single-shared-LUT compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import lut, ternary


def _setup(seed, n, k, m):
    t = ternary.random_ternary(jax.random.PRNGKey(seed), (k, m))
    a = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, k))
    ref = np.asarray(a @ t.astype(jnp.float32))
    return t, a, ref


class TestTSARLut:
    @pytest.mark.parametrize("c", [2, 4, 8])
    @pytest.mark.parametrize("n,k,m", [(1, 64, 32), (8, 256, 48), (128, 512, 64)])
    def test_matches_dense(self, c, n, k, m):
        t, a, ref = _setup(c * 100 + n, n, k, m)
        ip, iz = ternary.pack_indices(t, c)
        y = lut.tsar_lut_matmul(a, ip, iz, c)
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-3)

    def test_single_lut_equals_two_lut(self):
        """Our compressed shared-LUT identity == the paper's two-LUT form."""
        t, a, _ = _setup(7, 4, 128, 32)
        ip, iz = ternary.pack_indices(t, 4)
        y1 = lut.tsar_lut_matmul(a, ip, iz, 4)
        y2 = lut.tsar_lut_matmul_twolut(a, ip, iz, 4)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-4)

    def test_with_scale(self):
        t, a, ref = _setup(11, 2, 64, 16)
        scale = jnp.linspace(0.5, 2.0, 16)
        ip, iz = ternary.pack_indices(t, 4)
        y = lut.tsar_lut_matmul(a, ip, iz, 4, w_scale=scale)
        np.testing.assert_allclose(np.asarray(y), ref * np.asarray(scale), rtol=1e-4, atol=1e-3)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10**6), n=st.integers(1, 4),
           blocks=st.integers(1, 32), m=st.integers(1, 40),
           c=st.sampled_from([2, 4]))
    def test_property_random_shapes(self, seed, n, blocks, m, c):
        k = blocks * c
        t, a, ref = _setup(seed, n, k, m)
        ip, iz = ternary.pack_indices(t, c)
        y = lut.tsar_lut_matmul(a, ip, iz, c)
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-3, atol=1e-2)


class TestMemoryLUTBaseline:
    @pytest.mark.parametrize("c", [2, 4])
    def test_matches_dense(self, c):
        t, a, ref = _setup(21, 4, 128, 32)
        li = lut.ternary_lut_indices(t, c)
        y = lut.memory_lut_matmul(a, li, c)
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-3)

    def test_precomputed_lut_reuse(self):
        """Steady-state decode: baseline reuses the stored TLUT."""
        t, a, ref = _setup(22, 1, 64, 16)
        li = lut.ternary_lut_indices(t, 4)
        stored = lut.memory_lut_precompute(a, 4)
        y = lut.memory_lut_matmul(a, li, 4, precomputed_lut=stored)
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-3)

    def test_lut_sizes_match_paper(self):
        """Baseline stores 3^c entries/block; T-SAR needs 2^c (shared)."""
        a = jax.random.normal(jax.random.PRNGKey(0), (1, 64))
        assert lut.memory_lut_precompute(a, 4).shape == (1, 16, 81)   # 3^4
        assert lut.build_lut(a, 4).shape == (1, 16, 16)               # 2^4


class TestIntPipeline:
    def test_exact_int8_pipeline_close_to_fp(self):
        # int8 absmax quantization: per-element error ~ scale/2, accumulated
        # over K=256 -> relative error stays within a few percent.
        t, a, ref = _setup(31, 8, 256, 64)
        y = lut.bitlinear_matmul_exact_int(a, t, jnp.ones(64))
        denom = np.maximum(np.abs(ref), 1.0)
        assert float(np.max(np.abs(np.asarray(y) - ref) / denom)) < 0.3
        assert float(np.mean(np.abs(np.asarray(y) - ref) / denom)) < 0.02
