"""Compile-only checks for one TPU v5e chip, with no chip attached.

The TPU compiler ships with libtpu, so these tests describe a ``v5e:2x2``
topology and compile for one of its chips: the four Pallas kernels at
bitnet-2b-4t's 2560 x 6912 BitLinear shape, and full-width flat serving
steps.  Mosaic refuses here what it would refuse on the chip (shape casts it
cannot lay out, more VMEM than a kernel may use), and the compiler reports
the step's device memory and the copies it makes.  Nothing runs, so nothing
here is a time.

The topology is described only inside the ``one_chip`` fixture: only one
process at a time may load the TPU library, and only the test worker that
runs this file should.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.core import hw, ternary
from repro.kernels import ops, tsar_lut, tsar_matmul, tsar_sparse
from repro.models import model_zoo as zoo
from repro.plan import BatchProfile, registry
from repro.plan.plan import compile_plan_from_shapes
from repro.serving.engine import _flat_call, freeze_params
from repro.sparse import format as sparse_format

K, M = 2560, 6912                 # bitnet-2b-4t d_model x d_ff
V5E = hw.PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache off around these.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _specs(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile_kernel(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _pad(x: int, t: int) -> int:
    return -(-x // t) * t


def _mxu(n, k, m, tiles, dataflow, sharding):
    bn, bk, bm = tiles
    mem = tsar_matmul.vmem_bytes(bn, bk, bm)
    assert mem <= V5E.vmem_scoped_bytes, mem
    _compile_kernel(
        lambda x, s, z, sc: ops.tsar_matmul(
            x, ternary.TernaryWeights(s, z, sc, (k, m)), dataflow=dataflow,
            bn=bn, bk=bk, bm=bm, interpret=False),
        sharding, ((n, k), jnp.float32), ((k // 8, m), jnp.uint8),
        ((k // 8, m), jnp.uint8), ((m,), jnp.float32))


def _lut(n, k, m, tiles, sharding, c=4):
    bb, bm = tiles
    mem = tsar_lut.vmem_bytes(n, bb, bm, c)
    assert mem <= V5E.vmem_scoped_bytes, mem
    _compile_kernel(
        lambda x, ip, iz, sc: ops.tsar_lut_gemv(x, ip, iz, sc, c=c, bb=bb,
                                                bm=bm, interpret=False),
        sharding, ((n, k), jnp.float32), ((k // c, m), jnp.uint8),
        ((k // c, m), jnp.uint8), ((m,), jnp.float32))


@pytest.mark.parametrize("n", [1, 128], ids=["decode", "prefill"])
@pytest.mark.parametrize("kernel", [
    "tsar_matmul_AP", "tsar_matmul_OP", "tsar_lut_gemv",
    "tsar_sparse_matmul", "tsar_sparse_padded_matmul"])
def test_kernel_compiles_at_ops_tiles(one_chip, kernel, n):
    """Each kernel at the tiles ``ops.py`` picks for a decode GEMV and a
    prefill GEMM at 2560 x 6912."""
    if kernel.startswith("tsar_matmul"):
        _mxu(n, K, M, registry.get("tsar_mxu").tiles(n, K, M), kernel[-2:],
             one_chip)
    elif kernel == "tsar_lut_gemv":
        _lut(n, K, M, registry.get("tsar_lut").tiles(n, K, M), one_chip)
    elif kernel == "tsar_sparse_matmul":
        bn, bk, bm = registry.get("tsar_sparse").tiles(n, K, M)
        assert tsar_matmul.vmem_bytes(bn, bk, bm) <= V5E.vmem_scoped_bytes
        kb, mb = K // bk, M // bm
        _compile_kernel(
            lambda a, asc, sp, zp, kids, slots, counts, wsc:
            tsar_sparse.tsar_sparse_matmul_packed(
                a, asc, sp, zp, kids, slots, counts, wsc, bn=bn, bk=bk,
                bm=bm, s_steps=kb, interpret=False),
            one_chip, ((_pad(n, bn), K), jnp.int8),
            ((_pad(n, bn), 1), jnp.float32),
            ((kb * mb, bk // 8, bm), jnp.uint8),
            ((kb * mb, bk // 8, bm), jnp.uint8),
            ((mb, kb), jnp.int32), ((mb, kb), jnp.int32), ((mb,), jnp.int32),
            ((1, M), jnp.float32))
    else:
        bn = registry.get("tsar_sparse_padded").tiles(n, K, M)[0]
        pbst = jax.eval_shape(
            lambda t, s: sparse_format.pad_from_ternary(t, s),
            jax.ShapeDtypeStruct((K, M), jnp.int8),
            jax.ShapeDtypeStruct((M,), jnp.float32))
        text = jax.jit(
            lambda x, p: ops.tsar_sparse_padded_matmul(
                x, p, bn=bn, interpret=False)).lower(
            jax.ShapeDtypeStruct((n, K), jnp.float32, sharding=one_chip),
            _specs(pbst, one_chip)).compile().as_text()
        assert "tpu_custom_call" in text


def _engine_buckets(slots=4, prefill_chunk=16):
    """The n-buckets ``ServingEngine`` plans for with its default options."""
    budget = prefill_chunk + slots
    return BatchProfile(
        decode_ns=(1, slots),
        prefill_ns=(prefill_chunk, slots * (prefill_chunk + 1), budget),
    ).buckets


def test_kernels_compile_at_planned_tiles(one_chip):
    """The kernel, dataflow and tiles ``compile_plan`` picks for each of
    bitnet-2b-4t's BitLinear shapes at the engine's buckets all compile."""
    cfg = configs.get("bitnet-2b-4t")
    d, ff, kv = cfg.d_model, cfg.d_ff, cfg.n_kv_heads * cfg.head_dim
    shapes = {"wq": (d, d), "wk": (d, kv), "w_up": (d, ff), "w_down": (ff, d)}
    done = set()
    for n in _engine_buckets():
        plan = compile_plan_from_shapes(
            {name: (n, k, m) for name, (k, m) in shapes.items()})
        for name, (k, m) in shapes.items():
            lp = plan.lookup(name, n)
            key = (lp.kernel, lp.dataflow, lp.tile_sizes, n, k, m)
            if key in done:
                continue
            done.add(key)
            assert lp.kernel in ("tsar_mxu", "tsar_lut"), lp.kernel
            if lp.kernel == "tsar_mxu":
                _mxu(n, k, m, lp.tile_sizes, lp.dataflow, one_chip)
            else:
                _lut(n, k, m, lp.tile_sizes, one_chip)
    assert done


def test_full_width_flat_step_fits_one_chip(one_chip):
    """One bitnet-2b-4t flat serving step at published widths compiles for
    v5e, and its arguments plus temporaries fit in 16 GB of HBM."""
    cfg = configs.get("bitnet-2b-4t")
    slots, t, view_blocks, block = 4, 20, 8, 16
    params = jax.eval_shape(
        lambda k: freeze_params(zoo.init_params(cfg, k), sparse=False),
        jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: zoo.init_paged_cache(
        cfg, slots, slots * view_blocks + 1, block))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda p, pools, tbl, tk, sl, ps, er:
        _flat_call(cfg, p, pools, tbl, tk, sl, ps, er)).lower(
        _specs(params, one_chip), _specs(pools, one_chip),
        i32(slots, view_blocks), i32(t), i32(t), i32(t), i32(slots)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 3e9      # the real widths, not a cut
    assert used < V5E.hbm_bytes, used


@pytest.fixture(scope="module")
def chat_step(one_chip):
    """bitnet-2b-4t at published widths in the chat cell's shape: 10 slots,
    block size 16, 1131 pool blocks (10 slots x 113 blocks of max context
    1808, + scratch), view bucket 128.  Returns a compile of the flat step
    with the pools donated, as the engine runs it, at a given width."""
    cfg = configs.get("bitnet-2b-4t")
    slots, view_blocks, num_blocks, block = 10, 128, 1131, 16
    params = jax.eval_shape(
        lambda k: freeze_params(zoo.init_params(cfg, k), sparse=False),
        jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: zoo.init_paged_cache(
        cfg, slots, num_blocks, block))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731

    def compile_at(t):
        return jax.jit(
            lambda p, pools, tbl, tk, sl, ps, er:
            _flat_call(cfg, p, pools, tbl, tk, sl, ps, er),
            donate_argnums=(1,)).lower(
            _specs(params, one_chip), _specs(pools, one_chip),
            i32(slots, view_blocks), i32(t), i32(t), i32(t),
            i32(slots)).compile()
    return pools["k"], compile_at


@pytest.mark.parametrize("width,temp_limit", [(266, 1e9), (10, 1e8)],
                         ids=["chunk", "decode"])
def test_flat_step_updates_pool_in_place(chat_step, width, temp_limit):
    """The flat step writes and reads the block pools in place: the
    compiled program copies no pool leaf into or out of its layer loop,
    and its temporaries stay under one pool leaf."""
    import re

    leaf, compile_at = chat_step
    compiled = compile_at(width)
    shape = "f32[" + ",".join(map(str, leaf.shape)) + "]"
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r" copy(-start)?\(", line) and shape in line]
    assert copies == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < leaf.size * leaf.dtype.itemsize, temp
    assert temp < temp_limit, temp


def test_packed_init_fits_one_chip(one_chip):
    """``init_packed_params`` at published widths: the frozen tree plus the
    program's temporaries fit well inside one chip's HBM."""
    from repro.serving import init_packed_params

    cfg = configs.get("bitnet-2b-4t")
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(lambda k: init_packed_params(cfg, k)).lower(
        key).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes > 3e9         # the real widths, not a cut
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes \
        < 0.6 * V5E.hbm_bytes


def test_flat_step_matmuls_carry_a_step_scope(one_chip):
    """Compiled for v5e, every matmul of the flat step keeps its part's
    name in its ``op_name`` (``obs.trace.STEP_SCOPES``, innermost wins),
    and the KV copies keep theirs: a chip trace can split the step by
    part."""
    import re

    from repro.obs.trace import STEP_SCOPES

    cfg = configs.get("bitnet-2b-4t").reduced()
    slots, t, view_blocks, block = 2, 10, 2, 16
    params = jax.eval_shape(
        lambda k: freeze_params(zoo.init_params(cfg, k), sparse=False),
        jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: zoo.init_paged_cache(
        cfg, slots, slots * view_blocks + 1, block))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    text = jax.jit(
        lambda p, pools, tbl, tk, sl, ps, er:
        _flat_call(cfg, p, pools, tbl, tk, sl, ps, er)).lower(
        _specs(params, one_chip), _specs(pools, one_chip),
        i32(slots, view_blocks), i32(t), i32(t), i32(t), i32(slots)).compile(
    ).as_text()

    def innermost(line):
        m = re.search(r'op_name="([^"]*)"', line)
        parts = m.group(1).split("/") if m else []
        return next((p for p in reversed(parts) if p in STEP_SCOPES), None)

    matmuls = [innermost(line) for line in text.splitlines()
               if re.search(r" (convolution|dot)\(", line)]
    assert len(matmuls) == 10     # q, k, v, o, gate, up, down, QK^T, PV, head
    assert set(matmuls) == {"bitlinear", "attention", "head"}
    assert {innermost(line) for line in text.splitlines()} >= set(STEP_SCOPES)
