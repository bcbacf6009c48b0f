"""Sparsity subsystem: block-sparse format round-trips, zero-skipping kernel
exactness vs the dense path, density-driven dispatch, and profiling stats."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitlinear, dataflow, ternary
from repro.kernels import ops, ref
from repro.sparse import format as sparse_format
from repro.sparse import stats as sparse_stats

P_ZERO_SWEEP = (0.1, 1.0 / 3.0, 0.6, 0.9)


def _rand(seed, k, m, p_zero=1.0 / 3.0):
    return ternary.random_ternary(jax.random.PRNGKey(seed), (k, m), p_zero)


class TestBlockSparseFormat:
    @pytest.mark.parametrize("k,m,bk,bm", [
        (256, 256, 128, 128), (512, 384, 256, 128),
        (300, 200, 128, 128),            # ragged K and M
        (128, 128, 128, 128),            # single block
    ])
    def test_roundtrip_to_ternary(self, k, m, bk, bm):
        t = _rand(k + m, k, m)
        bst = sparse_format.from_ternary(t, bk=bk, bm=bm)
        np.testing.assert_array_equal(np.asarray(sparse_format.to_ternary(bst)),
                                      np.asarray(t))

    @pytest.mark.parametrize("p_zero", [0.0, 1.0])
    def test_roundtrip_extreme_densities(self, p_zero):
        """Density 1.0 (no zeros: every block live) and 0.0 (all zeros:
        empty pool) both round-trip exactly."""
        t = _rand(7, 384, 256, p_zero=p_zero)
        bst = sparse_format.from_ternary(t, bk=128, bm=128)
        kb, mb = bst.grid
        if p_zero == 1.0:
            assert bst.n_live == 0 and bst.block_density == 0.0
        else:
            assert bst.n_live == kb * mb and bst.block_density == 1.0
        np.testing.assert_array_equal(np.asarray(sparse_format.to_ternary(bst)),
                                      np.asarray(t))

    def test_roundtrip_to_packed(self):
        t = _rand(11, 512, 256)
        scale = jax.random.uniform(jax.random.PRNGKey(1), (256,), minval=0.5, maxval=2.0)
        tw = ternary.pack(t.astype(jnp.float32), scale)
        bst = sparse_format.from_packed(tw, bk=128, bm=128)
        tw2 = sparse_format.to_packed(bst)
        np.testing.assert_array_equal(np.asarray(tw2.sign_plane), np.asarray(tw.sign_plane))
        np.testing.assert_array_equal(np.asarray(tw2.zero_plane), np.asarray(tw.zero_plane))
        np.testing.assert_allclose(np.asarray(tw2.scale), np.asarray(tw.scale))

    def test_dead_blocks_cost_no_pool_bytes(self):
        key = jax.random.PRNGKey(3)
        t_dense = sparse_format.random_block_sparse_ternary(
            key, (512, 512), bk=128, bm=128, p_zero_block=0.0)
        t_half = t_dense * sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(4), (512, 512), bk=128, bm=128,
            p_zero_block=0.75, p_zero=0.0)
        b_dense = sparse_format.from_ternary(t_dense, bk=128, bm=128)
        b_half = sparse_format.from_ternary(t_half, bk=128, bm=128)
        assert b_half.n_live < b_dense.n_live
        assert b_half.nbytes() < b_dense.nbytes()

    def test_occupancy_matches_blocks(self):
        t = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(5), (384, 256), bk=128, bm=128, p_zero_block=0.5)
        bst = sparse_format.from_ternary(t, bk=128, bm=128)
        occ = sparse_stats.block_occupancy(t, 128, 128)
        np.testing.assert_allclose(np.asarray(bst.occupancy), occ, rtol=1e-6)
        assert ((occ > 0) == (np.asarray(bst.block_map) >= 0)).all()

    def test_strip_schedule_covers_live_blocks(self):
        t = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(6), (512, 384), bk=128, bm=128, p_zero_block=0.5)
        bst = sparse_format.from_ternary(t, bk=128, bm=128)
        kids, slots, counts, s_max = sparse_format.strip_schedule(bst)
        bmap = np.asarray(bst.block_map)
        assert int(np.asarray(counts).sum()) == bst.n_live
        assert s_max == int((bmap >= 0).sum(axis=0).max())
        for j in range(bmap.shape[1]):
            c = int(np.asarray(counts)[j])
            live_k = np.nonzero(bmap[:, j] >= 0)[0]
            np.testing.assert_array_equal(np.asarray(kids)[j, :c], live_k)
            np.testing.assert_array_equal(np.asarray(slots)[j, :c], bmap[live_k, j])


class TestSparseKernel:
    # Note: the bit-identity sweeps vs the dense kernel (unstructured p_zero
    # grid, hypothesis shape exactness) moved to the cross-kernel
    # conformance suite (tests/test_conformance.py), which covers every
    # registry kernel on a shared shapes x densities x dtypes grid.

    @pytest.mark.parametrize("p_zero_block", [0.0, 0.5, 1.0])
    def test_exact_vs_ref_block_structured(self, p_zero_block):
        n, k, m = 3, 640, 256
        t = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(10), (k, m), bk=128, bm=128,
            p_zero_block=p_zero_block)
        bst = sparse_format.from_ternary(t, bk=128, bm=128)
        x = jax.random.normal(jax.random.PRNGKey(11), (n, k))
        got = ops.tsar_sparse_matmul(x, bst, interpret=True)
        want = ref.block_sparse_matmul_ref(x, bst)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_ragged_shapes_and_leading_dims(self):
        t = _rand(12, 300, 200)
        bst = sparse_format.from_ternary(t, bk=128, bm=128)
        x = jax.random.normal(jax.random.PRNGKey(13), (2, 3, 300))
        got = ops.tsar_sparse_matmul(x, bst, interpret=True)
        assert got.shape == (2, 3, 200)
        want = ref.block_sparse_matmul_ref(x.reshape(6, 300), bst).reshape(2, 3, 200)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

class TestPaddedPool:
    """PaddedBlockSparseTernary: static-shape (vmappable) pool properties."""

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 10**6), kb=st.integers(1, 4),
           mb=st.integers(1, 3), pzb=st.sampled_from((0.0, 0.5, 1.0)))
    def test_roundtrip_to_ternary_and_packed(self, seed, kb, mb, pzb):
        """pad -> decode is exact, pad -> TernaryWeights matches the dense
        packing bit-for-bit, and compact() recovers the compacted format."""
        k, m = kb * 64 - 3, mb * 64          # ragged K on purpose
        t = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(seed), (k, m), bk=64, bm=64, p_zero_block=pzb)
        scale = jax.random.uniform(jax.random.PRNGKey(seed + 1), (m,),
                                   minval=0.25, maxval=2.0)
        pbst = sparse_format.pad_from_ternary(t, scale, bk=64, bm=64)
        np.testing.assert_array_equal(
            np.asarray(sparse_format.padded_to_ternary(pbst)), np.asarray(t))
        tw = ternary.pack(t.astype(jnp.float32), scale)
        tw2 = sparse_format.padded_to_packed(pbst)
        np.testing.assert_array_equal(np.asarray(tw2.sign_plane),
                                      np.asarray(tw.sign_plane))
        np.testing.assert_array_equal(np.asarray(tw2.zero_plane),
                                      np.asarray(tw.zero_plane))
        compacted = sparse_format.compact(pbst)
        np.testing.assert_array_equal(
            np.asarray(sparse_format.to_ternary(compacted)), np.asarray(t))

    def test_pad_pool_from_compacted_is_exact_and_tight(self):
        t = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(2), (320, 192), bk=64, bm=64, p_zero_block=0.6)
        bst = sparse_format.from_ternary(t, bk=64, bm=64)
        pbst = sparse_format.pad_pool(bst)
        assert pbst.max_live == max(bst.n_live, 1)
        assert pbst.s_steps == max(bst.s_max, 1)
        np.testing.assert_array_equal(
            np.asarray(sparse_format.padded_to_ternary(pbst)), np.asarray(t))

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 10**6), extra=st.integers(0, 7))
    def test_nbytes_monotonic_in_max_live(self, seed, extra):
        """More pad slots never cost fewer bytes — max_live trades memory
        for the static shape."""
        t = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(seed), (256, 192), bk=64, bm=64,
            p_zero_block=0.5)
        bst = sparse_format.from_ternary(t, bk=64, bm=64)
        base = max(bst.n_live, 1)
        sizes = [sparse_format.pad_from_ternary(t, bk=64, bm=64,
                                                max_live=base + d).nbytes()
                 for d in (0, extra, extra + 1)]
        assert sizes == sorted(sizes)
        assert sizes[0] < sizes[-1] or extra == 0

    def test_undersized_pool_raises_on_concrete(self):
        t = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(5), (256, 192), bk=64, bm=64, p_zero_block=0.2)
        bst = sparse_format.from_ternary(t, bk=64, bm=64)
        with pytest.raises(ValueError, match="max_live"):
            sparse_format.pad_from_ternary(t, bk=64, bm=64,
                                           max_live=bst.n_live - 1)
        with pytest.raises(ValueError, match="s_steps"):
            sparse_format.pad_from_ternary(t, bk=64, bm=64,
                                           s_steps=bst.s_max - 1)

    def test_traced_undersized_bounds_truncate_consistently(self):
        """Under tracing the undersized-bound raise is unavailable, so an
        overflowing strip is deterministically TRUNCATED — and the kernel
        walk, the block map, and the jnp decode must all see the SAME
        truncated matrix (a schedule-only truncation would make the Pallas
        and jnp realizations of tsar_sparse_padded disagree)."""
        t = _rand(3, 256, 128, p_zero=0.2)      # all 4 k-blocks live per strip
        pbst = jax.jit(lambda w: sparse_format.pad_from_ternary(
            w, bk=64, bm=64, s_steps=2))(t)
        bmap = np.asarray(pbst.block_map)
        assert int((bmap >= 0).sum(axis=0).max()) <= 2   # map truncated too
        dec = sparse_format.padded_to_ternary(pbst)
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 256))
        kernel_y = ops.tsar_sparse_padded_matmul(x, pbst, interpret=True)
        a_q, a_scale = ternary.quantize_activations(x)
        acc = jax.lax.dot_general(
            a_q, dec, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        jnp_y = acc.astype(jnp.float32) * a_scale * pbst.scale
        np.testing.assert_array_equal(np.asarray(kernel_y), np.asarray(jnp_y))

    def test_freeze_padded_true_shapes_match_traced(self):
        """freeze(padded=True) must produce the SAME sidecar shapes eagerly
        and under eval_shape/jit — eval_shape-driven buffer allocation and
        jit(freeze) outputs would otherwise disagree with eager freezes."""
        w = {"w": jax.random.normal(jax.random.PRNGKey(30), (128, 128)) * 0.1}
        fn = lambda p: bitlinear.freeze(p, block_shape=(64, 64), padded=True)
        eager = fn(w)
        traced = jax.eval_shape(fn, w)
        assert eager.padded.sign_pool.shape == traced.padded.sign_pool.shape
        assert eager.padded.kids.shape == traced.padded.kids.shape
        assert eager.padded.max_live == 4          # full grid, not n_live

    def test_construction_is_traceable(self):
        """The whole point: pad_from_ternary runs under tracing (vmap/jit),
        unlike the data-dependent compacted builder."""
        t = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(6), (128, 128), bk=64, bm=64, p_zero_block=0.5)
        fn = jax.jit(lambda w: sparse_format.pad_from_ternary(w, bk=64, bm=64))
        pbst = fn(t)
        np.testing.assert_array_equal(
            np.asarray(sparse_format.padded_to_ternary(pbst)), np.asarray(t))
        # and abstractly (shape-only), the freeze-under-tracing contract
        abs_p = jax.eval_shape(fn, t)
        assert abs_p.sign_pool.shape == pbst.sign_pool.shape

    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(0, 10**6), n=st.integers(1, 4))
    def test_vmap_over_stacked_layers_equals_loop(self, seed, n):
        """Acceptance: stacked scan-layer pools built and consumed under
        vmap match a Python loop of per-layer sparse matmuls bit-for-bit."""
        L = 3
        ts = jnp.stack([
            sparse_format.random_block_sparse_ternary(
                jax.random.PRNGKey(seed + i), (192, 128), bk=64, bm=64,
                p_zero_block=0.5)
            for i in range(L)])
        pools = jax.vmap(
            lambda w: sparse_format.pad_from_ternary(w, bk=64, bm=64))(ts)
        xs = jax.random.normal(jax.random.PRNGKey(seed + 9), (L, n, 192))
        ys = jax.vmap(lambda p, x: ops.tsar_sparse_padded_matmul(
            x, p, interpret=True))(pools, xs)
        for i in range(L):
            per_layer = sparse_format.pad_from_ternary(ts[i], bk=64, bm=64)
            want = ops.tsar_sparse_padded_matmul(xs[i], per_layer,
                                                 interpret=True)
            np.testing.assert_array_equal(np.asarray(ys[i]), np.asarray(want))

    def test_pad_slots_and_schedule_pads_are_inert(self):
        """Oversized pools: pad slots decode to zero blocks and padded
        schedule entries are masked — output identical to the tight pool."""
        t = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(7), (256, 128), bk=64, bm=64, p_zero_block=0.5)
        tight = sparse_format.pad_from_ternary(t, bk=64, bm=64)
        loose = sparse_format.pad_from_ternary(
            t, bk=64, bm=64, max_live=int(np.asarray(tight.n_live)) + 5)
        x = jax.random.normal(jax.random.PRNGKey(8), (2, 256))
        np.testing.assert_array_equal(
            np.asarray(ops.tsar_sparse_padded_matmul(x, tight, interpret=True)),
            np.asarray(ops.tsar_sparse_padded_matmul(x, loose, interpret=True)))


class TestDensityDispatch:
    @pytest.mark.parametrize("n,k,m", [(1, 2560, 6912), (128, 2560, 6912),
                                       (8, 4096, 4096)])
    def test_break_even_is_respected(self, n, k, m):
        """Acceptance: sparse below the analytic break-even, never above."""
        be = dataflow.sparse_break_even(n, k, m)
        assert 0.0 < be < 1.0
        below = dataflow.select_kernel(n, k, m, block_density=be * 0.9)
        above = dataflow.select_kernel(n, k, m,
                                       block_density=min(1.0, be * 1.1))
        at_full = dataflow.select_kernel(n, k, m, block_density=1.0)
        assert below.kernel == "tsar_sparse"
        assert above.kernel != "tsar_sparse"
        assert at_full.kernel != "tsar_sparse"

    def test_default_density_never_speculates_sparse(self):
        """Unstructured zeros leave every block live, so with no measured
        block density the selector must never pick the sparse path."""
        for (n, k, m) in [(1, 2560, 6912), (64, 1024, 1024), (128, 8192, 8192)]:
            assert dataflow.select_kernel(n, k, m).kernel != "tsar_sparse"

    def test_sparse_cost_monotone_in_density(self):
        costs = [max(*dataflow._tsar_sparse_cost(8, 4096, 4096, bd))
                 for bd in (0.1, 0.4, 0.7, 1.0)]
        assert costs == sorted(costs)

    def test_frozen_auto_dispatch_picks_sparse_when_blocks_die(self):
        """End-to-end threading: a checkpoint with structurally dead blocks is
        served by tsar_sparse under kernel='auto' with no caller change."""
        key = jax.random.PRNGKey(20)
        k, m = 512, 512
        w = jax.random.normal(key, (k, m)) * 0.1
        mask = sparse_format.random_block_sparse_ternary(
            jax.random.PRNGKey(21), (k, m), bk=256, bm=256,
            p_zero_block=0.75, p_zero=0.0).astype(jnp.float32)
        fz = bitlinear.freeze({"w": w * jnp.abs(mask)})
        assert fz.block_density is not None and fz.block_density < 0.5
        x = jax.random.normal(jax.random.PRNGKey(22), (4, k))
        choice = dataflow.select_kernel(
            n=4, k=k, m=m, c=fz.c, density=fz.density,
            block_density=fz.block_density, block_shape=fz.sparse.block_shape)
        assert choice.kernel == "tsar_sparse"
        y_auto = bitlinear.apply_frozen(fz, x)   # plan=None -> auto-select
        y_dense = bitlinear.apply_frozen(fz, x, plan="tsar_mxu")
        np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_dense),
                                   rtol=1e-5, atol=1e-4)

    def test_frozen_without_sidecar_falls_back(self):
        fz = bitlinear.freeze(bitlinear.init(jax.random.PRNGKey(0), 128, 64))
        fz = fz._replace(sparse=None, block_density=0.01)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 128))
        y = bitlinear.apply_frozen(fz, x)                  # must not raise
        assert y.shape == (2, 64)


class TestCalibration:
    """The issue-tax calibration plumbing: fit -> install (core/hw) ->
    every registry cost model reads the fitted value -> save/load."""

    @pytest.fixture(autouse=True)
    def _clean(self):
        from repro.core import hw

        hw.clear_calibration()
        yield
        hw.clear_calibration()

    def test_fit_issue_tax_recovers_planted_constant(self):
        from benchmarks.bench_kernels import fit_issue_tax

        td = 2.0
        rows = [(bd, 1.3 * bd * td, td) for bd in (0.1, 0.4, 0.7, 1.0)]
        assert fit_issue_tax(rows) == pytest.approx(1.3)
        # outlier-robust: one corrupt row does not move the median
        rows.append((0.5, 50.0, td))
        assert fit_issue_tax(rows) == pytest.approx(1.3)
        with pytest.raises(ValueError, match="no usable"):
            fit_issue_tax([(0.0, 1.0, 1.0)])

    def test_calibrated_tax_reaches_cost_models_and_break_even(self):
        from repro.core import hw
        from repro.plan import registry

        n, k, m = 8, 4096, 4096
        base_cost = registry.get("tsar_sparse").cost(n, k, m,
                                                     block_density=0.5)
        base_be = dataflow.sparse_break_even(n, k, m)
        hw.set_calibration(sparse_issue_tax=hw.SPARSE_ISSUE_TAX * 2)
        assert hw.sparse_issue_tax() == pytest.approx(2.2)
        up_cost = registry.get("tsar_sparse").cost(n, k, m, block_density=0.5)
        assert up_cost[0] > base_cost[0]        # compute scaled by the tax
        assert dataflow.sparse_break_even(n, k, m) < base_be
        # the padded kernel reads the same knob
        up_pad = registry.get("tsar_sparse_padded").cost(n, k, m,
                                                         block_density=0.5)
        assert up_pad[0] > up_cost[0]           # pad-walk overhead on top
        hw.clear_calibration("sparse_issue_tax")
        assert registry.get("tsar_sparse").cost(
            n, k, m, block_density=0.5) == base_cost

    def test_save_load_roundtrip_and_validation(self, tmp_path):
        from repro.core import hw

        hw.set_calibration(sparse_issue_tax=1.37)
        path = tmp_path / "calibration.json"
        hw.save_calibration(path)
        hw.clear_calibration()
        assert hw.sparse_issue_tax() == hw.SPARSE_ISSUE_TAX
        loaded = hw.load_calibration(path)
        assert loaded == {"sparse_issue_tax": 1.37}
        assert hw.sparse_issue_tax() == 1.37
        with pytest.raises(ValueError, match="unknown calibration key"):
            hw.set_calibration(bogus=1.0)
        with pytest.raises(ValueError, match="must be > 0"):
            hw.set_calibration(sparse_issue_tax=0.0)

    def test_calibrate_installs_fitted_tax(self, monkeypatch, tmp_path):
        """The bench entry point wires measure -> fit -> install; timings
        are stubbed so the test pins plumbing, not this container's clock."""
        import benchmarks.bench_kernels as bench
        from repro.core import hw

        monkeypatch.setattr(
            bench, "measure_issue_tax_samples",
            lambda quick=True, reps=3: [(0.5, 1.25 * 0.5 * 2.0, 2.0)])
        tax = bench.calibrate(quick=True)
        assert tax == pytest.approx(1.25)
        assert hw.sparse_issue_tax() == pytest.approx(1.25)
        # save is honored even on a dry run (apply=False): fit-and-persist
        # must not require mutating the process-global calibration.
        hw.clear_calibration()
        path = tmp_path / "cal.json"
        bench.calibrate(quick=True, save=path, apply=False)
        assert hw.sparse_issue_tax() == hw.SPARSE_ISSUE_TAX   # untouched
        assert hw.load_calibration(path) == {
            "sparse_issue_tax": pytest.approx(1.25)}


class TestStats:
    def test_profile_packed_tree(self):
        from repro.models import layers
        w1 = jax.random.normal(jax.random.PRNGKey(0), (256, 128)) * 0.1
        w_stack = jax.random.normal(jax.random.PRNGKey(1), (3, 256, 128)) * 0.1
        tree = {"attn": layers.pack_linear({"w": w1}),
                "mlp": jax.vmap(layers.pack_linear)({"w": w_stack}),
                "embed": {"wd": jnp.zeros((10, 4))}}
        prof = sparse_stats.profile_params(tree)
        assert {p["path"] for p in prof} == {"attn", "mlp"}
        kb_one = -(-256 // sparse_format.DEFAULT_BK)   # blocks along K per layer
        mb_one = -(-128 // sparse_format.DEFAULT_BM)
        expect_blocks = {"attn": kb_one * mb_one, "mlp": 3 * kb_one * mb_one}
        for p in prof:
            assert 0.0 < p["density"] < 1.0
            assert int(p["hist"].sum()) == expect_blocks[p["path"]]
        summ = sparse_stats.summarize(prof)
        assert summ["layers"] == 2
        assert 0.0 < summ["density_mean"] < 1.0
        assert len(sparse_stats.format_report(prof).splitlines()) == 4

    def test_density_leaf_measures_zeros(self):
        from repro.models import layers
        packed = layers.pack_linear({"w": jax.random.normal(jax.random.PRNGKey(2), (256, 128))})
        assert "density" in packed
        d = float(packed["density"])
        assert 0.4 < d < 0.95   # absmean keeps roughly 2/3 nonzero

    def test_block_occupancy_ragged(self):
        t = np.zeros((200, 100), np.int8)
        t[:128, :64] = 1
        occ = sparse_stats.block_occupancy(t, 128, 128)
        assert occ.shape == (2, 1)
        assert occ[0, 0] == pytest.approx(64 * 128 / (128 * 128))
        assert occ[1, 0] == 0.0
