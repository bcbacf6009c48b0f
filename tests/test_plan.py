"""Execution plans: kernel registry completeness, ModelPlan JSON round-trip,
deprecation-shim equivalence, n-bucket selection, and the serve-path
acceptance — zero ``select_kernel`` calls after engine init, and JSON-loaded
plans serving identically to in-memory ones."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.core import bitlinear, dataflow
from repro.models import layers, model_zoo as zoo
from repro.plan import (
    BatchProfile,
    LayerPlan,
    ModelPlan,
    compile_plan,
    registry,
    runtime,
)
from repro.serving import Request, ServingEngine
from repro.sparse import format as sparse_format

SERVABLE = {"tsar_mxu", "tsar_lut", "tsar_sparse", "tsar_sparse_padded",
            "memory_lut", "dense"}


@pytest.fixture(scope="module")
def frozen_layer():
    p = bitlinear.init(jax.random.PRNGKey(0), 128, 64)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 128))
    return bitlinear.freeze(p), x


@pytest.fixture(scope="module")
def frozen_sparse_layer():
    """A layer frozen with structurally dead blocks (sparse sidecar present)."""
    k = m = 512
    w = jax.random.normal(jax.random.PRNGKey(2), (k, m)) * 0.1
    mask = sparse_format.random_block_sparse_ternary(
        jax.random.PRNGKey(3), (k, m), bk=256, bm=256,
        p_zero_block=0.75, p_zero=0.0).astype(jnp.float32)
    fz = bitlinear.freeze({"w": w * jnp.abs(mask)})
    assert fz.sparse is not None
    x = jax.random.normal(jax.random.PRNGKey(4), (4, k))
    return fz, x


class TestRegistry:
    def test_registry_is_complete(self):
        """Every servable kernel name is registered and vice versa."""
        assert set(registry.names()) == SERVABLE
        assert set(registry.selectable_names()) == {
            "tsar_mxu", "tsar_lut", "tsar_sparse", "tsar_sparse_padded"}
        assert set(registry.SPARSE_KERNELS) == {
            "tsar_sparse", "tsar_sparse_padded"}

    def test_every_registered_kernel_serves(self, frozen_layer,
                                            frozen_sparse_layer):
        """supports() gates lower(): every supported kernel produces the
        right shape through apply_frozen(plan=name)."""
        for frozen, x in (frozen_layer, frozen_sparse_layer):
            names = registry.available(frozen)
            assert set(names) >= SERVABLE - set(registry.SPARSE_KERNELS)
            for name in names:
                y = bitlinear.apply_frozen(frozen, x, plan=name)
                assert y.shape == x.shape[:-1] + (frozen.shape[1],), name

    def test_sparse_gated_by_sidecar(self, frozen_layer, frozen_sparse_layer):
        assert "tsar_sparse" not in registry.available(frozen_layer[0])
        assert "tsar_sparse_padded" not in registry.available(frozen_layer[0])
        assert "tsar_sparse" in registry.available(frozen_sparse_layer[0])
        # freeze emits the padded twin alongside the compacted pool
        assert "tsar_sparse_padded" in registry.available(
            frozen_sparse_layer[0])

    def test_unknown_kernel_raises(self, frozen_layer):
        fz, x = frozen_layer
        with pytest.raises(ValueError, match="unknown kernel"):
            bitlinear.apply_frozen(fz, x, plan="tsar_gpu")

    def test_select_kernel_only_returns_registered(self):
        for n, k, m in [(1, 2560, 6912), (128, 2560, 6912), (8, 4096, 4096)]:
            choice = dataflow.select_kernel(n, k, m)
            assert choice.kernel in registry.selectable_names()
            assert set(choice.detail["candidates"]) == set(
                registry.selectable_names())

    def test_interpret_forces_pallas_off_tpu(self, frozen_layer, monkeypatch):
        """An explicit interpret= request must run the Pallas kernel (that is
        the off-TPU validation path), not the jnp fallback."""
        from repro.kernels import ops

        fz, x = frozen_layer
        called = {"n": 0}
        orig = ops.tsar_matmul

        def spy(*a, **kw):
            called["n"] += 1
            return orig(*a, **kw)

        monkeypatch.setattr(ops, "tsar_matmul", spy)
        y_pal = bitlinear.apply_frozen(fz, x, plan="tsar_mxu", interpret=True)
        assert called["n"] == 1
        # interpret=False means "not interpret mode", NOT "force compiled
        # Pallas" — off-TPU it must keep the jnp fallback, not crash.
        y_no = bitlinear.apply_frozen(fz, x, plan="tsar_mxu", interpret=False)
        assert called["n"] == 1
        # the Pallas kernel is bit-identical to the jnp spelling
        y_jnp = bitlinear.apply_frozen(fz, x, plan="tsar_mxu")
        np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(y_jnp))
        np.testing.assert_array_equal(np.asarray(y_no), np.asarray(y_jnp))

    def test_available_kernels_lower_on_packed_dicts(self):
        """supports() and lower() agree for pack_linear-style plane dicts,
        including ragged K (planes store the padded ceil(K/8)*8)."""
        for k, m in ((128, 64), (133, 64)):
            w = jax.random.normal(jax.random.PRNGKey(11), (k, m)) * 0.1
            p = layers.pack_linear({"w": w})
            x = jax.random.normal(jax.random.PRNGKey(12), (4, k))
            names = registry.available(p)
            assert "tsar_mxu" in names and "dense" in names
            for name in names:
                y = registry.get(name).lower(p, x)
                assert y.shape == (4, m), (name, k)
        # stacked (vmapped) plane dicts are not lowerable directly
        stacked = jax.vmap(layers.pack_linear)(
            {"w": jax.random.normal(jax.random.PRNGKey(13), (2, 64, 32))})
        assert registry.available(stacked) == ()

    def test_cost_methods_match_dataflow_aliases(self):
        n, k, m = 16, 1024, 2048
        assert dataflow._tsar_mxu_cost(n, k, m) == \
            registry.get("tsar_mxu").cost(n, k, m)
        assert dataflow._tsar_lut_cost(n, k, m, 4) == \
            registry.get("tsar_lut").cost(n, k, m, 4)


class TestDeprecationShim:
    """The old string-keyed apply_frozen signature warns but bit-matches."""

    @pytest.mark.parametrize("kernel", ["tsar_mxu", "tsar_lut", "memory_lut",
                                        "dense"])
    def test_old_kernel_arg_bit_matches(self, frozen_layer, kernel):
        fz, x = frozen_layer
        with pytest.warns(DeprecationWarning, match="^repro\\."):
            y_old = bitlinear.apply_frozen(fz, x, kernel=kernel)
        y_new = bitlinear.apply_frozen(fz, x, plan=kernel)
        np.testing.assert_array_equal(np.asarray(y_old), np.asarray(y_new))

    def test_old_use_pallas_false_bit_matches(self, frozen_layer):
        fz, x = frozen_layer
        with pytest.warns(DeprecationWarning):
            y_old = bitlinear.apply_frozen(fz, x, kernel="tsar_mxu",
                                           use_pallas=False)
        y_new = bitlinear.apply_frozen(fz, x, plan="tsar_mxu")
        np.testing.assert_array_equal(np.asarray(y_old), np.asarray(y_new))

    def test_old_auto_bit_matches(self, frozen_sparse_layer):
        fz, x = frozen_sparse_layer
        with pytest.warns(DeprecationWarning):
            y_old = bitlinear.apply_frozen(fz, x, kernel="auto")
        y_new = bitlinear.apply_frozen(fz, x)
        np.testing.assert_array_equal(np.asarray(y_old), np.asarray(y_new))

    def test_new_signature_does_not_warn(self, frozen_layer):
        import warnings

        fz, x = frozen_layer
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            bitlinear.apply_frozen(fz, x, plan="tsar_mxu")
            bitlinear.apply_frozen(fz, x)


class TestModelPlan:
    @pytest.fixture(scope="class")
    def packed_tree(self):
        cfg = configs.get("bitnet-2b-4t").reduced()
        params = zoo.init_params(cfg, jax.random.PRNGKey(0))
        from repro.serving import freeze_params

        return freeze_params(params)

    def test_json_round_trip_equality(self, packed_tree):
        plan = compile_plan(packed_tree,
                            BatchProfile(decode_ns=(1, 4), prefill_ns=(16, 64)))
        assert plan.layers and plan.buckets == (1, 4, 16, 64)
        assert ModelPlan.from_json(plan.to_json()) == plan

    def test_save_load_file(self, packed_tree, tmp_path):
        plan = compile_plan(packed_tree)
        path = tmp_path / "plan.json"
        plan.save(path)
        assert ModelPlan.load(path) == plan

    def test_version_mismatch_raises(self, packed_tree):
        plan = compile_plan(packed_tree)
        bad = plan.to_json().replace('"version": 1', '"version": 99', 1)
        with pytest.raises(ValueError, match="version"):
            ModelPlan.from_json(bad)

    def test_per_layer_density_is_measured(self, packed_tree):
        """compile_plan feeds each layer's stamped density, not one global."""
        plan = compile_plan(packed_tree)
        densities = {lp.density for by_b in plan.layers.values()
                     for lp in by_b.values()}
        assert len(densities) > 1          # layers measured individually
        assert all(0.0 < d <= 1.0 for d in densities)

    def test_bucket_resolution(self, packed_tree):
        plan = compile_plan(packed_tree,
                            BatchProfile(decode_ns=(1, 8), prefill_ns=(64,)))
        assert plan.bucket_for(1) == 1
        assert plan.bucket_for(3) == 8     # smallest bucket >= n
        assert plan.bucket_for(64) == 64
        assert plan.bucket_for(999) == 64  # overflow -> largest

    def test_nbucket_selection_decode_vs_prefill(self):
        """Decode (n=1) and prefill (n=128) buckets commit to different
        dataflows for the same layer (paper Fig. 7)."""
        w = jax.random.normal(jax.random.PRNGKey(0), (512, 2048)) * 0.05
        plan = compile_plan({"proj": {"w": w}},
                            BatchProfile(decode_ns=(1,), prefill_ns=(128,)))
        lp_dec = plan.lookup("proj", 1)
        lp_pre = plan.lookup("proj", 128)
        assert lp_dec.dataflow == "OP"
        assert lp_pre.dataflow == "AP"
        assert lp_dec.kernel in registry.selectable_names()
        assert lp_dec.est_time_s < lp_pre.est_time_s

    def test_layer_plan_wrapper_per_layer_c_and_density(self):
        """The satellite fix: per-layer c / measured densities change the
        per-layer costs instead of one global default."""
        plan = dataflow.layer_plan({
            "dense_mlp": (1, 2560, 6912),
            "expert_c2": {"n": 1, "k": 2560, "m": 6912, "c": 2},
            "pruned": {"n": 1, "k": 2560, "m": 6912, "density": 0.3,
                       "block_density": 0.3},
        })
        assert set(plan) == {"dense_mlp", "expert_c2", "pruned"}
        assert plan["pruned"].kernel == "tsar_sparse"
        assert plan["dense_mlp"].kernel != "tsar_sparse"
        # c rescales the LUT candidate cost
        base = plan["dense_mlp"].detail
        assert plan["expert_c2"].detail["tile_sizes"] is not None
        assert base["bucket"] == 1


class TestPlannedDispatch:
    def test_packed_linear_honors_dense_plan(self):
        """An active plan pinning a layer to 'dense' switches the packed
        forward to the dequantized fp path (observably different math)."""
        w = jax.random.normal(jax.random.PRNGKey(5), (128, 64)) * 0.1
        packed = layers.pack_linear({"w": w})
        x = jax.random.normal(jax.random.PRNGKey(6), (4, 128))
        lp = LayerPlan(kernel="dense", dataflow="OP", tile_sizes=(8, 128, 64),
                       est_time_s=0.0, bound="memory", density=0.66)
        plan = ModelPlan(buckets=(4,), shapes={"l": (128, 64, 4)},
                         layers={"l": {4: lp}})
        y_default = layers.linear(packed, x, train=False)
        with runtime.activate(plan):
            y_planned = layers.linear(packed, x, train=False)
        assert y_planned.shape == y_default.shape
        # fp path: exact dequantized matmul; int8 path: activation-quantized
        np.testing.assert_allclose(np.asarray(y_planned), np.asarray(y_default),
                                   rtol=0.1, atol=0.1)
        assert not np.array_equal(np.asarray(y_planned), np.asarray(y_default))

    def test_conflicting_same_shape_layers_fall_back(self):
        """Two layers sharing (k, m) with DIFFERENT plans: the nameless
        shape lookup must refuse to guess (returns None -> default
        realization), never serve one layer with the other's plan."""
        mk = lambda kern: {1: LayerPlan(kernel=kern, dataflow="OP",
                                        tile_sizes=(), est_time_s=0.0,
                                        bound="memory", density=0.66)}
        plan = ModelPlan(buckets=(1,),
                         shapes={"wk": (128, 64, 4), "wv": (128, 64, 4)},
                         layers={"wk": mk("tsar_mxu"), "wv": mk("dense")})
        assert plan.lookup_shape(128, 64, 1) is None
        assert plan.shape_conflicts() == ((128, 64),)
        # named lookups still resolve per layer
        assert plan.lookup("wv", 1).kernel == "dense"
        # agreeing same-shape layers keep resolving
        ok = ModelPlan(buckets=(1,),
                       shapes={"wk": (128, 64, 4), "wv": (128, 64, 4)},
                       layers={"wk": mk("dense"), "wv": mk("dense")})
        assert ok.lookup_shape(128, 64, 1).kernel == "dense"
        assert ok.shape_conflicts() == ()

    def test_ragged_k_layers_resolve_via_padded_planes(self):
        """Plan shapes store the bitplane-padded K, and lookups accept the
        true K — a ragged-K layer's plan is not silently ignored."""
        w = jax.random.normal(jax.random.PRNGKey(10), (300, 64)) * 0.1
        plan = compile_plan({"proj": {"w": w}},
                            BatchProfile(decode_ns=(1,), prefill_ns=(16,)))
        assert plan.shapes["proj"][0] == 304          # ceil(300/8)*8
        assert plan.lookup_shape(300, 64, 1) is not None
        assert plan.lookup_shape(304, 64, 1) is not None

    def test_planned_sparse_degrades_without_sidecar(self, frozen_layer):
        """A saved plan that picked tsar_sparse, applied to a layer frozen
        without a sidecar (e.g. re-frozen under tracing), degrades to
        tsar_mxu; only the explicit string still raises."""
        fz, x = frozen_layer
        assert fz.sparse is None
        lp = LayerPlan(kernel="tsar_sparse", dataflow="OP", tile_sizes=(),
                       est_time_s=0.0, bound="memory", density=0.5)
        y = bitlinear.apply_frozen(fz, x, plan=lp)     # degrades, same math
        np.testing.assert_array_equal(
            np.asarray(y),
            np.asarray(bitlinear.apply_frozen(fz, x, plan="tsar_mxu")))
        with pytest.raises(ValueError, match="sidecar"):
            bitlinear.apply_frozen(fz, x, plan="tsar_sparse")

    def test_packed_linear_honors_memory_lut_plan(self):
        """A plan pinning 'memory_lut' (the A/B baseline) must actually run
        the DRAM-LUT gather, not the int8-dot path with a wrong label."""
        w = jax.random.normal(jax.random.PRNGKey(15), (128, 64)) * 0.1
        packed = layers.pack_linear({"w": w})
        x = jax.random.normal(jax.random.PRNGKey(16), (4, 128))
        lp = LayerPlan(kernel="memory_lut", dataflow="OP", tile_sizes=(),
                       est_time_s=0.0, bound="memory", density=0.66)
        plan = ModelPlan(buckets=(4,), shapes={"l": (128, 64, 4)},
                         layers={"l": {4: lp}})
        y_default = layers.linear(packed, x, train=False)
        with runtime.activate(plan):
            y_mlut = layers.linear(packed, x, train=False)
        # fp LUT gather vs int8 pipeline: close but not the same bits
        np.testing.assert_allclose(np.asarray(y_mlut), np.asarray(y_default),
                                   rtol=0.1, atol=0.1)
        assert not np.array_equal(np.asarray(y_mlut), np.asarray(y_default))

    def test_layer_plan_dataflow_reaches_pallas_kernel(self, frozen_layer,
                                                       monkeypatch):
        """The LayerPlan's dataflow/tile decisions are executed, not just
        recorded: the Pallas wrapper receives them."""
        from repro.kernels import ops

        fz, x = frozen_layer
        seen = {}
        orig = ops.tsar_matmul

        def spy(*a, **kw):
            seen.update(kw)
            return orig(*a, **kw)

        monkeypatch.setattr(ops, "tsar_matmul", spy)
        lp = LayerPlan(kernel="tsar_mxu", dataflow="OP",
                       tile_sizes=(8, 128, 128), est_time_s=0.0,
                       bound="memory", density=0.66)
        bitlinear.apply_frozen(fz, x, plan=lp, interpret=True)
        assert seen["dataflow"] == "OP"
        assert (seen["bn"], seen["bk"], seen["bm"]) == (8, 128, 128)

    def test_activate_none_is_transparent(self):
        lp = LayerPlan(kernel="tsar_mxu", dataflow="OP", tile_sizes=(),
                       est_time_s=0.0, bound="memory", density=0.66)
        plan = ModelPlan(buckets=(1,), shapes={"l": (8, 8, 4)},
                         layers={"l": {1: lp}})
        with runtime.activate(plan):
            with runtime.activate(None):       # must keep the outer plan
                assert runtime.current() is plan
            assert runtime.current() is plan
        assert runtime.current() is None

    def test_pack_linear_plan_directed_dense(self):
        """A layer the plan pins to 'dense' keeps fp weights at pack time."""
        w = jax.random.normal(jax.random.PRNGKey(7), (64, 32)) * 0.1
        p = layers.pack_linear({"w": w}, lp="dense")
        assert set(p) == {"wd"}
        x = jax.random.normal(jax.random.PRNGKey(8), (2, 64))
        y = layers.linear(p, x, train=False)
        assert y.shape == (2, 32)

    def test_pack_linear_accepts_model_plan(self):
        """pack_linear resolves a whole ModelPlan through the layer name."""
        w = jax.random.normal(jax.random.PRNGKey(9), (64, 32)) * 0.1
        mk = lambda kern: ModelPlan(
            buckets=(1,), shapes={"proj": (64, 32, 4)},
            layers={"proj": {1: LayerPlan(kernel=kern, dataflow="OP",
                                          tile_sizes=(), est_time_s=0.0,
                                          bound="memory", density=0.66)}})
        assert set(layers.pack_linear({"w": w}, mk("dense"),
                                      name="proj")) == {"wd"}
        assert "sign" in layers.pack_linear({"w": w}, mk("tsar_mxu"),
                                            name="proj")
        assert "sign" in layers.pack_linear({"w": w}, mk("dense"))  # no name


class TestServingWithPlan:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = configs.get("bitnet-2b-4t").reduced()
        params = zoo.init_params(cfg, jax.random.PRNGKey(0))
        return cfg, params

    def _reqs(self, n=3):
        return [Request(uid=i, prompt=np.arange(4 + i) % 100, max_new_tokens=5)
                for i in range(n)]

    def test_zero_select_kernel_calls_after_init(self, model, monkeypatch):
        """Acceptance: the plan is compiled once at init; serving performs
        ZERO select_kernel calls afterwards."""
        cfg, params = model
        init_calls = {"n": 0}
        orig = dataflow.select_kernel

        def counting(*a, **kw):
            init_calls["n"] += 1
            return orig(*a, **kw)

        monkeypatch.setattr(dataflow, "select_kernel", counting)
        eng = ServingEngine(cfg, params, max_len=48, batch_slots=2, packed=True)
        assert init_calls["n"] > 0            # plan compilation costed layers
        assert eng.plan is not None
        assert eng.stats["plan_layers"] == len(eng.plan.layers)

        run_calls = {"n": 0}

        def forbidden(*a, **kw):
            run_calls["n"] += 1
            return orig(*a, **kw)

        monkeypatch.setattr(dataflow, "select_kernel", forbidden)
        out = eng.run(self._reqs())
        assert all(r.done for r in out)
        assert run_calls["n"] == 0

    def test_json_loaded_plan_serves_identically(self, model):
        """Acceptance: to_json -> from_json -> serve == in-memory planning."""
        cfg, params = model
        eng_mem = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                                packed=True)
        out_mem = eng_mem.run(self._reqs())
        plan = ModelPlan.from_json(eng_mem.plan.to_json())
        eng_json = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                                 packed=True, plan=plan)
        out_json = eng_json.run(self._reqs())
        for a, b in zip(out_mem, out_json):
            assert a.out_tokens == b.out_tokens

    def test_hand_edited_dense_plan_serves(self, model):
        """The plan is a first-class artifact: an operator can pin layers to
        the dense escape hatch and the engine honors it."""
        cfg, params = model
        base = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                             packed=True)
        dense_layers = {
            name: {n: dataclasses.replace(lp, kernel="dense")
                   for n, lp in by_b.items()}
            for name, by_b in base.plan.layers.items()}
        dense_plan = ModelPlan(buckets=base.plan.buckets,
                               shapes=dict(base.plan.shapes),
                               layers=dense_layers)
        eng = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                            packed=True, plan=dense_plan)
        out = eng.run(self._reqs())
        assert all(r.done for r in out)
        assert eng.plan.dominant_kernel(1) == "dense"

    def test_qat_engine_has_no_plan(self, model):
        cfg, params = model
        eng = ServingEngine(cfg, params, max_len=48, batch_slots=2)
        assert eng.plan is None

    def test_mismatched_plan_warns(self, model):
        """A plan saved for a different config resolves nothing — the engine
        must say so instead of silently serving un-planned."""
        cfg, params = model
        lp = LayerPlan(kernel="tsar_mxu", dataflow="OP", tile_sizes=(),
                       est_time_s=0.0, bound="memory", density=0.66)
        alien = ModelPlan(buckets=(1,), shapes={"other": (4096, 9999, 4)},
                          layers={"other": {1: lp}})
        with pytest.warns(UserWarning, match="resolves only 0/"):
            eng = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                                packed=True, plan=alien)
        assert eng.stats["plan_matched_layers"] == 0
        # a matching plan (round-tripped) raises no warning
        good = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                             packed=True)
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            eng2 = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                                 packed=True,
                                 plan=ModelPlan.from_json(good.plan.to_json()))
        assert eng2.stats["plan_matched_layers"] == eng2.stats["plan_layers"]

    def test_plan_with_qat_weights_warns(self, model):
        cfg, params = model
        base = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                             packed=True)
        with pytest.warns(UserWarning, match="packed=False"):
            ServingEngine(cfg, params, max_len=48, batch_slots=2,
                          plan=base.plan)


class TestSparseServing:
    """The padded-pool sparse path through the serving loop: freeze emits
    vmappable pools, the plan commits to ``tsar_sparse_padded``, and the
    jitted step dispatches it with output token-identical to a dense plan."""

    BK = 64   # reduced-config dims (128/256) need a finer grid than 256x256

    @pytest.fixture(scope="class")
    def sparse_model(self):
        """Reduced bitnet checkpoint with ~half the (64, 64) weight blocks
        structurally dead in every BitLinear layer.  Seeds derive from a
        deterministic digest of the layer path (``hash()`` is randomized per
        process) and the first block is force-killed so every layer is
        guaranteed below the sparse threshold."""
        import zlib

        cfg = configs.get("bitnet-2b-4t").reduced()
        params = zoo.init_params(cfg, jax.random.PRNGKey(0))

        def blockify(node, path=""):
            if isinstance(node, dict):
                if set(node) == {"w"}:
                    w = node["w"]
                    k, m = w.shape[-2:]
                    seed = zlib.crc32(path.encode()) % 2**31
                    mask = jnp.abs(sparse_format.random_block_sparse_ternary(
                        jax.random.PRNGKey(seed), (k, m),
                        bk=self.BK, bm=self.BK, p_zero_block=0.5,
                        p_zero=0.0).astype(jnp.float32))
                    mask = mask.at[:self.BK, :self.BK].set(0.0)
                    return {"w": w * mask}
                return {k2: blockify(v, f"{path}/{k2}")
                        for k2, v in node.items()}
            return node

        return cfg, blockify(params)

    def _reqs(self, n=3):
        return [Request(uid=i, prompt=np.arange(4 + i) % 100, max_new_tokens=5)
                for i in range(n)]

    def test_freeze_params_emits_stacked_padded_pools(self, sparse_model):
        """Acceptance: freeze_params on a stacked (vmapped) scan model emits
        padded-pool sidecars — per-layer pools with UNIFORM static shapes,
        sized by the host-side measurement pass."""
        from repro.serving import freeze_params

        cfg, params = sparse_model
        packed = freeze_params(params, block_shape=(self.BK, self.BK))
        wq = packed["blocks"]["attn"]["wq"]
        assert {"sp_sign", "sp_zero", "sp_map", "sp_kids", "sp_slots",
                "sp_counts", "block_density"} <= set(wq)
        # stacked: leading dim = n_layers, pool dims shared across the stack
        assert wq["sp_sign"].shape[0] == cfg.n_layers
        assert wq["sp_sign"].shape[1:] == wq["sp_zero"].shape[1:]
        # the measured pool is TIGHT: no larger than the full block grid
        kb = -(-128 // self.BK)
        mb = -(-128 // self.BK)
        assert wq["sp_sign"].shape[1] <= kb * mb
        assert float(np.mean(np.asarray(wq["block_density"]))) < 0.95

    def test_freeze_params_emits_padded_pools_under_tracing(self, sparse_model):
        """sparse=True freezes are fully traceable (static pool shapes), so
        freeze_params can run under jit/eval_shape — no data-dependent
        compaction on the trace path."""
        from repro.serving import freeze_params

        cfg, params = sparse_model
        fn = lambda p: freeze_params(p, sparse=True,
                                     block_shape=(self.BK, self.BK))
        abstract = jax.eval_shape(fn, params)
        wq = abstract["blocks"]["attn"]["wq"]
        assert "sp_sign" in wq
        concrete = jax.jit(fn)(params)
        got = concrete["blocks"]["attn"]["wq"]["sp_sign"]
        assert got.shape == wq["sp_sign"].shape

    def test_sparse_plan_dispatches_padded_kernel(self, sparse_model,
                                                  monkeypatch):
        """Acceptance: the engine's compiled plan commits BitLinear layers to
        ``tsar_sparse_padded``, serves through it in the jitted step with
        ZERO select_kernel calls after init, and the output is
        token-identical to a dense-plan engine on the same checkpoint."""
        cfg, params = sparse_model
        eng = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                            packed=True, sparse_block=(self.BK, self.BK))
        counts = eng.plan.kernel_counts(1)
        assert counts.get("tsar_sparse_padded", 0) > 0, counts

        orig = dataflow.select_kernel
        run_calls = {"n": 0}

        def forbidden(*a, **kw):
            run_calls["n"] += 1
            return orig(*a, **kw)

        monkeypatch.setattr(dataflow, "select_kernel", forbidden)
        out_sparse = eng.run(self._reqs())
        assert all(r.done for r in out_sparse)
        assert run_calls["n"] == 0

        # dense plan on the SAME packed checkpoint: pin every layer/bucket
        # to tsar_mxu and compare tokens (the padded pool decodes to the
        # same ternary matrix, so greedy decode must match bit-for-bit).
        monkeypatch.setattr(dataflow, "select_kernel", orig)
        dense_layers = {
            name: {n: dataclasses.replace(lp, kernel="tsar_mxu")
                   for n, lp in by_b.items()}
            for name, by_b in eng.plan.layers.items()}
        dense_plan = ModelPlan(buckets=eng.plan.buckets,
                               shapes=dict(eng.plan.shapes),
                               layers=dense_layers)
        eng_dense = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                                  packed=True, sparse_block=(self.BK, self.BK),
                                  plan=dense_plan)
        out_dense = eng_dense.run(self._reqs())
        for a, b in zip(out_sparse, out_dense):
            assert a.out_tokens == b.out_tokens

    def test_sparse_plan_json_roundtrip_serves_identically(self, sparse_model):
        """The sparse-kernel plan survives to_json/from_json and serves the
        same tokens (extends TestServingWithPlan's invariant)."""
        cfg, params = sparse_model
        eng = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                            packed=True, sparse_block=(self.BK, self.BK))
        out_mem = eng.run(self._reqs())
        plan = ModelPlan.from_json(eng.plan.to_json())
        assert plan.kernel_counts(1).get("tsar_sparse_padded", 0) > 0
        eng2 = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                             packed=True, sparse_block=(self.BK, self.BK),
                             plan=plan)
        out_json = eng2.run(self._reqs())
        for a, b in zip(out_mem, out_json):
            assert a.out_tokens == b.out_tokens

    def test_sparse_false_keeps_planes_only(self, sparse_model):
        from repro.serving import freeze_params

        cfg, params = sparse_model
        packed = freeze_params(params, sparse=False)
        wq = packed["blocks"]["attn"]["wq"]
        assert set(wq) == {"sign", "zero", "scale", "density"}

    def test_outlier_slice_does_not_emit_pools(self):
        """The auto pre-pass gates on the MEAN live-block fraction (the
        planner's signal): one sparse outlier slice in a dense stack must
        not stamp near-full-grid pools the plan would never dispatch."""
        from repro.serving import freeze_params

        k = m = 128
        dense_w = jax.random.normal(jax.random.PRNGKey(40), (k, m)) * 0.1
        sparse_w = dense_w * jnp.zeros((k, m)).at[:64, :64].set(1.0)
        # 1 slice at bd=0.25 among 19 dense slices: mean ~ 0.96 >= 0.95
        # threshold -> no pools, even though the outlier alone sits far
        # below it.
        stack = {"proj": {"w": jnp.stack([sparse_w] + [dense_w] * 19)}}
        packed = freeze_params(stack, block_shape=(64, 64))
        assert "sp_sign" not in packed["proj"]
        # a uniformly sparse stack still emits
        stack = {"proj": {"w": jnp.stack([sparse_w] * 4)}}
        packed = freeze_params(stack, block_shape=(64, 64))
        assert "sp_sign" in packed["proj"]

    def test_unrecognized_sparse_value_raises(self, sparse_model):
        """A typo'd sparse= must not silently freeze planes-only while the
        operator believes the sparse path is active."""
        from repro.serving import freeze_params

        cfg, params = sparse_model
        with pytest.raises(ValueError, match="sparse="):
            freeze_params(params, sparse="Auto")

    def test_undersized_max_live_raises_on_concrete_stack(self, sparse_model):
        """sparse=True with a too-small bound must raise host-side — the
        vmapped construction traces even concrete stacks, so without this
        check live blocks would be silently dropped."""
        from repro.serving import freeze_params

        cfg, params = sparse_model
        with pytest.raises(ValueError, match="max_live"):
            freeze_params(params, sparse=True,
                          block_shape=(self.BK, self.BK), max_live=1)

    def test_auto_bound_floors_give_uniform_pools(self, sparse_model):
        """Under sparse='auto' caller max_live/s_steps floor the measured
        sizes, so re-freezes can keep EVERY sp_* leaf shape uniform (the
        kids/slots schedules are shaped by s_steps, not just the pools)."""
        from repro.serving import freeze_params

        cfg, params = sparse_model
        live_floor, step_floor = 7, 2
        packed = freeze_params(params, block_shape=(self.BK, self.BK),
                               max_live=live_floor, s_steps=step_floor)
        for proj in ("wq", "wk", "wv", "wo"):
            leaf = packed["blocks"]["attn"][proj]
            if "sp_sign" in leaf:
                assert leaf["sp_sign"].shape[1] >= live_floor
                assert leaf["sp_kids"].shape[-1] >= step_floor


def test_chip_peaks_keyed_by_device_kind(monkeypatch):
    """Planning reads the peaks of its own TPU kind, refuses a kind the
    table lacks, and off the TPU names the v5e entry."""
    import types

    from repro.core import hw

    hw.chip_peaks.cache_clear()
    try:
        assert hw.chip_peaks() is hw.PEAKS[hw.OFF_TPU_KIND]   # CPU here
        dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])
        hw.chip_peaks.cache_clear()
        assert hw.chip_peaks().int8_ops == 393e12
        dev.device_kind = "TPU v9 unknown"
        hw.chip_peaks.cache_clear()
        with pytest.raises(ValueError, match="TPU v9 unknown"):
            compile_plan({"proj": {"w": jnp.ones((256, 256))}})
    finally:
        hw.chip_peaks.cache_clear()
