"""Unit + property tests for the T-SAR algorithmic core (paper Sec. III-A)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import lut, ternary


def _rand_ternary(seed, k, m):
    return ternary.random_ternary(jax.random.PRNGKey(seed), (k, m))


class TestDecomposition:
    def test_dense_sparse_identity(self):
        t = _rand_ternary(0, 64, 32).astype(jnp.float32)
        wd, ws = ternary.decompose(t)
        assert set(np.unique(np.asarray(wd))) <= {-1.0, 1.0}
        assert set(np.unique(np.asarray(ws))) <= {0.0, 1.0}
        np.testing.assert_array_equal(np.asarray(ternary.recompose(wd, ws)), np.asarray(t))

    def test_dot_product_decomposition(self):
        """The paper's core identity: <w,a> = <w_D,a> - <w_S,a>."""
        t = _rand_ternary(1, 128, 16).astype(jnp.float32)
        a = jax.random.normal(jax.random.PRNGKey(2), (128,))
        wd, ws = ternary.decompose(t)
        np.testing.assert_allclose(
            np.asarray(a @ t), np.asarray(a @ wd - a @ ws), rtol=1e-5, atol=1e-4)


class TestPacking:
    @pytest.mark.parametrize("k,m", [(8, 4), (64, 32), (256, 100), (1024, 7)])
    def test_roundtrip(self, k, m):
        t = _rand_ternary(k + m, k, m)
        tw = ternary.pack(t.astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(ternary.unpack(tw)), np.asarray(t))

    def test_matches_numpy_packbits(self):
        t = np.asarray(_rand_ternary(3, 128, 24))
        tw = ternary.pack(jnp.asarray(t, jnp.float32))
        sp, zp = ternary.np_pack_reference(t)
        np.testing.assert_array_equal(np.asarray(tw.sign_plane), sp)
        np.testing.assert_array_equal(np.asarray(tw.zero_plane), zp)

    def test_two_bits_per_weight(self):
        t = _rand_ternary(4, 1024, 512)
        tw = ternary.pack(t.astype(jnp.float32))
        plane_bytes = tw.sign_plane.size + tw.zero_plane.size
        assert plane_bytes * 8 == 2 * 1024 * 512  # 2 bits/weight exactly

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           kb=st.integers(1, 16), m=st.integers(1, 64))
    def test_roundtrip_property(self, seed, kb, m):
        t = _rand_ternary(seed, kb * 8, m)
        tw = ternary.pack(t.astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(ternary.unpack(tw)), np.asarray(t))


class TestAbsmean:
    def test_values_are_ternary(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        t, scale = ternary.absmean_ternarize(w)
        assert set(np.unique(np.asarray(t))) <= {-1.0, 0.0, 1.0}
        assert scale.shape == (32,)

    def test_batched_leading_dims(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 64, 32))
        t, scale = ternary.absmean_ternarize(w)
        assert t.shape == w.shape and scale.shape == (3, 5, 32)
        # per-matrix gamma: each (64, 32) block independently thresholded
        t0, s0 = ternary.absmean_ternarize(w[1, 2])
        np.testing.assert_array_equal(np.asarray(t[1, 2]), np.asarray(t0))

    def test_reconstruction_error_reasonable(self):
        w = jax.random.normal(jax.random.PRNGKey(1), (512, 256))
        t, scale = ternary.absmean_ternarize(w)
        rel = float(jnp.linalg.norm(w - t * scale[None, :]) / jnp.linalg.norm(w))
        assert rel < 0.65  # ternary keeps the bulk of the signal


class TestActivationQuant:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 8), k=st.integers(1, 300))
    def test_bounded_error(self, seed, n, k):
        a = jax.random.normal(jax.random.PRNGKey(seed), (n, k)) * 3.0
        q, scale = ternary.quantize_activations(a)
        assert q.dtype == jnp.int8
        err = np.abs(np.asarray(q, np.float32) * np.asarray(scale) - np.asarray(a))
        # absmax quant: error bounded by scale/2 per element
        assert (err <= np.asarray(scale) * 0.51 + 1e-6).all()


class TestRaggedPacking:
    """Edge cases: K not a multiple of the bit-pack width (8) or LUT block (c).

    Ragged tails are zero-padded at pack time; the unpackers slice them off,
    so round-trips are exact at any K.
    """

    @pytest.mark.parametrize("k", [1, 3, 7, 9, 13, 127, 133])
    def test_pack_unpack_ragged_k(self, k):
        t = _rand_ternary(k, k, 12)
        tw = ternary.pack(t.astype(jnp.float32))
        assert tw.sign_plane.shape[0] == -(-k // ternary.PACK)
        assert ternary.unpack(tw).shape == (k, 12)
        np.testing.assert_array_equal(np.asarray(ternary.unpack(tw)), np.asarray(t))

    @pytest.mark.parametrize("k,c", [(10, 4), (7, 2), (65, 8), (130, 4), (5, 3)])
    def test_pack_indices_roundtrip_ragged_k(self, k, c):
        t = _rand_ternary(k * 7 + c, k, 9)
        ip, iz = ternary.pack_indices(t, c)
        assert ip.shape == (-(-k // c), 9)
        back = ternary.unpack_indices(ip, iz, c, k)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(t))

    @pytest.mark.parametrize("k,c", [(64, 4), (128, 2), (48, 8)])
    def test_pack_indices_roundtrip_aligned(self, k, c):
        t = _rand_ternary(k + c, k, 16)
        ip, iz = ternary.pack_indices(t, c)
        back = ternary.unpack_indices(ip, iz, c)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(t))

    def test_ragged_pad_bits_are_marked_zero_in_indices(self):
        """pack_indices pads with idx_s bits so the LUT identity contributes
        exactly 0 per pad position."""
        t = jnp.ones((5, 3), jnp.int8)
        ip, iz = ternary.pack_indices(t, 4)
        # last block: rows 4..7 -> row 4 live (+1), rows 5..7 padded zeros
        assert int(ip[1, 0]) == 0b0001
        assert int(iz[1, 0]) == 0b1110

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), k=st.integers(1, 200),
           m=st.integers(1, 16))
    def test_roundtrip_property_any_k(self, seed, k, m):
        t = _rand_ternary(seed, k, m)
        tw = ternary.pack(t.astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(ternary.unpack(tw)), np.asarray(t))

    def test_zero_plane_density(self):
        t = _rand_ternary(42, 133, 10)
        tw = ternary.pack(t.astype(jnp.float32))
        want = float(np.count_nonzero(np.asarray(t))) / t.size
        got = float(ternary.zero_plane_density(tw.zero_plane, 133))
        assert got == pytest.approx(want)


class TestLUTIndices:
    @pytest.mark.parametrize("c", [2, 4, 8])
    def test_index_encoding_bounds(self, c):
        t = _rand_ternary(0, 64, 16)
        ip, iz = ternary.pack_indices(t, c)
        assert ip.shape == (64 // c, 16)
        assert int(jnp.max(ip)) < 2 ** c and int(jnp.max(iz)) < 2 ** c
        # positive and zero encodings are disjoint bitmasks
        assert int(jnp.max(jnp.bitwise_and(ip, iz))) == 0
