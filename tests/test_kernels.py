"""Pallas kernel validation: interpret-mode shape/dtype sweeps vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ternary
from repro.kernels import ops, ref


def _mk(seed, n, k, m):
    t = ternary.random_ternary(jax.random.PRNGKey(seed), (k, m))
    scale = jax.random.uniform(jax.random.PRNGKey(seed + 1), (m,), minval=0.25, maxval=2.0)
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (n, k))
    return t, scale, x


class TestTSARMatmulKernel:
    @pytest.mark.parametrize("n,k,m", [
        (1, 128, 128), (1, 256, 256), (8, 512, 384),
        (16, 1024, 256), (3, 136, 72), (128, 256, 128),
    ])
    @pytest.mark.parametrize("dataflow", ["AP", "OP"])
    def test_sweep_vs_oracle(self, n, k, m, dataflow):
        t, scale, x = _mk(n * 7 + k, n, k, m)
        tw = ternary.pack(t.astype(jnp.float32), scale)
        got = ops.tsar_matmul(x, tw, dataflow=dataflow, interpret=True)
        want = ref.quantized_matmul_ref(x, tw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)

    # Note: the dtype sweep (f32/bf16) moved to the cross-kernel conformance
    # suite (tests/test_conformance.py::test_kernel_conformance_bf16), which
    # covers every registry kernel, not just this one.

    def test_leading_batch_dims(self):
        t, scale, x = _mk(9, 6, 128, 64)
        tw = ternary.pack(t.astype(jnp.float32), scale)
        x3 = x.reshape(2, 3, 128)
        got = ops.tsar_matmul(x3, tw, interpret=True)
        assert got.shape == (2, 3, 64)
        want = ref.quantized_matmul_ref(x, tw).reshape(2, 3, 64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10**6), n=st.integers(1, 9),
           kb=st.integers(1, 6), mb=st.integers(1, 4))
    def test_property_shapes(self, seed, n, kb, mb):
        k, m = kb * 128, mb * 128
        t, scale, x = _mk(seed, n, k, m)
        tw = ternary.pack(t.astype(jnp.float32), scale)
        got = ops.tsar_matmul(x, tw, interpret=True)
        want = ref.quantized_matmul_ref(x, tw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)


class TestTSARLutKernel:
    @pytest.mark.parametrize("n,k,m", [
        (1, 128, 128), (4, 512, 384), (8, 256, 256), (2, 132, 70),
    ])
    def test_sweep_vs_oracle(self, n, k, m):
        t, scale, x = _mk(n * 13 + m, n, k, m)
        ip, iz = ternary.pack_indices(t, 4)
        got = ops.tsar_lut_gemv(x, ip, iz, scale, c=4, interpret=True)
        want = ref.ternary_matmul_ref(x, t, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("c", [2, 4])
    def test_block_sizes(self, c):
        t, scale, x = _mk(77, 2, 256, 128)
        ip, iz = ternary.pack_indices(t, c)
        got = ops.tsar_lut_gemv(x, ip, iz, scale, c=c, interpret=True)
        want = ref.ternary_matmul_ref(x, t, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)

    def test_paper_gemv_shape(self):
        """The paper's Fig. 10 GEMV shape (scaled): 1 x 2560 -> 6912/4."""
        t, scale, x = _mk(99, 1, 2560, 1728)
        ip, iz = ternary.pack_indices(t, 4)
        got = ops.tsar_lut_gemv(x, ip, iz, scale, c=4, interpret=True)
        want = ref.ternary_matmul_ref(x, t, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-3)
