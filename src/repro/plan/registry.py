"""The kernel registry: every servable BitLinear kernel, declared once.

The paper's offline phase "empirically selects the fastest kernel for each
layer" (Sec. III-D / Fig. 5) and the runtime then just executes the choice.
This module is the repo's single source of truth for what "a kernel" is:

* :class:`KernelImpl` — the protocol every implementation satisfies:
  ``name``, an analytic ``cost(n, k, m, c, density, block_density)`` against
  the shared roofline constants, a ``supports(frozen)`` capability gate, a
  ``tiles(n, k, m, c)`` default tile pick, and ``lower(frozen, x)`` — the
  actual computation on a frozen layer.
* the six implementations (``tsar_mxu``, ``tsar_lut``, ``tsar_sparse``,
  ``tsar_sparse_padded``, ``memory_lut``, ``dense``) registered
  declaratively at import time.

``core/dataflow.select_kernel`` reduces to an argmin over the registry's
``selectable`` costs; ``core/bitlinear.apply_frozen`` reduces to
``registry.get(name).lower(...)``; ``repro.plan.plan.compile_plan`` freezes
the per-layer argmin into a durable :class:`~repro.plan.plan.ModelPlan`.

Import-graph note: this module sits BELOW ``repro.core`` (core imports it),
so everything from ``repro.core``/``repro.kernels`` is imported lazily
inside methods.
"""
from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

# The BitNet-b1.58 prior: absmean ternarization zeroes ~1/3 of the weights.
# Used when no measured density is supplied.
DEFAULT_DENSITY = 2.0 / 3.0

# Canonical block-sparse tiling default; sparse/format re-exports it (via
# core/dataflow) as DEFAULT_BLOCK_SHAPE.
SPARSE_BLOCK = (256, 256)

# The issue-efficiency tax on the sparse kernels' live-block work lives in
# ``repro.core.hw`` (SPARSE_ISSUE_TAX analytic default, overridable by the
# bench_kernels --calibrate fit); cost models read it via
# ``hw.sparse_issue_tax()``.  No alias here — this module sits below
# repro.core in the import graph and a second literal would desynchronize;
# ``core/dataflow`` re-exports the hw constant for back-compat.

# The sparse kernel family.  select_kernel treats these specially (strict
# improvement over the best dense kernel required) and planners restrict
# them to the formats a layer actually carries.
SPARSE_KERNELS = ("tsar_sparse", "tsar_sparse_padded")


def _hw():
    from repro.core import hw

    return hw


def _leaf(frozen, key: str):
    """Uniform access to FrozenBitLinear fields / packed-param dict leaves."""
    if isinstance(frozen, dict):
        return frozen.get(key)
    return getattr(frozen, key, None)


def has_planes(frozen) -> bool:
    if isinstance(frozen, dict):
        # Stacked (scan/expert) plane dicts need a vmap wrapper, not lower().
        return ("sign" in frozen and "zero" in frozen
                and getattr(frozen["sign"], "ndim", 0) == 2)
    return _leaf(frozen, "packed") is not None


def _packed_of(frozen, x):
    """The layer's TernaryWeights: FrozenBitLinear carries it; packed-param
    dicts (``layers.pack_linear`` output) rebuild it from the planes, taking
    the true K from the activations (planes store the padded ceil(K/8)*8)."""
    packed = _leaf(frozen, "packed")
    if packed is not None:
        return packed
    from repro.core import ternary

    return ternary.TernaryWeights(
        frozen["sign"], frozen["zero"], frozen["scale"],
        (x.shape[-1], frozen["sign"].shape[-1]))


def _c_of(frozen) -> int:
    c = _leaf(frozen, "c")
    return 4 if c is None else c


def resolve_use_pallas(use_pallas: bool | None,
                       interpret: bool | None = None) -> bool:
    """``None`` auto-resolves from the backend: Pallas on TPU, the traceable
    jnp spelling elsewhere.  Explicit True/False still forces — and so does
    ``interpret=True``: requesting interpret mode means running the Pallas
    kernel (that is how the kernels are validated off-TPU).  ``interpret=
    False`` does NOT force Pallas — off-TPU the compiled Pallas path cannot
    run, so it keeps the backend auto-resolution (jnp fallback on CPU)."""
    if use_pallas is None:
        if interpret:
            return True
        from repro.kernels import ops

        return not ops._auto_interpret()
    return use_pallas


@runtime_checkable
class KernelImpl(Protocol):
    """What the planner and the runtime need from one kernel."""

    name: str
    selectable: bool  # costed by select_kernel (baselines are not)
    # Serve-path flag: when a plan names this kernel inside the jitted
    # serving step (models.layers._packed_linear), should the step call this
    # impl's lower() on the packed-dict leaves?  False for the dense T-SAR
    # families, whose planes spelling inlined in _packed_linear IS their
    # exact realization (and stays SPMD-shardable); True for kernels whose
    # lowering genuinely differs (fp escape hatch, DRAM-LUT baseline,
    # padded-pool sparse).  Declared here so the registry stays the single
    # source of per-kernel dispatch knowledge.
    serve_via_registry: bool

    def cost(self, n: int, k: int, m: int, c: int = 4,
             density: float = DEFAULT_DENSITY,
             block_density: float | None = None,
             block_shape: tuple = SPARSE_BLOCK) -> tuple[float, float]:
        """(compute_s, memory_s) roofline estimate."""
        ...

    def supports(self, frozen) -> bool:
        """Can this kernel serve this frozen layer (encodings present)?"""
        ...

    def tiles(self, n: int, k: int, m: int, c: int = 4) -> tuple[int, ...]:
        """Default tile sizes the Pallas wrapper would pick for this shape."""
        ...

    def lower(self, frozen, x: jax.Array, *, use_pallas: bool | None = None,
              interpret: bool | None = None, lp=None) -> jax.Array:
        """Run the kernel on a frozen layer: x (..., K) -> (..., M) f32.

        ``lp`` (a ``repro.plan.LayerPlan``) carries the planned dataflow and
        tile sizes; Pallas-bound lowerings execute them (grid order + tiling),
        the jnp spellings ignore them (no grid to order)."""
        ...


def _int8_dot(frozen, x32):
    """Shared exact decode->int8-dot spelling (traceable realization of the
    decode-near-datapath kernels off-TPU; bit-equal to the Pallas output)."""
    from repro.core import lut, ternary

    packed = _packed_of(frozen, x32)
    a_q, a_scale = ternary.quantize_activations(x32)
    t = ternary.unpack(packed)
    return lut.dense_int8_matmul(a_q, a_scale, t, packed.scale)


def _ops_tiles(n: int, k: int, m: int) -> tuple[int, int, int]:
    from repro.kernels import ops

    return (ops._tile(n, 128, 8), ops._tile(k, 512, 128), ops._tile(m, 256, 128))


class TsarMXU:
    """Decode 2-bit planes to {-1,0,+1} int8 in VMEM, feed the MXU."""

    name = "tsar_mxu"
    selectable = True
    serve_via_registry = False

    def cost(self, n, k, m, c=4, density=DEFAULT_DENSITY, block_density=None,
             block_shape=SPARSE_BLOCK):
        pk = _hw().chip_peaks()
        flops = 2.0 * n * k * m                      # int8 MACs on the MXU
        decode_ops = k * m * 4.0                     # bitplane unpack ALU ops
        compute = flops / pk.int8_ops + decode_ops / (pk.int8_ops / 2)
        bytes_moved = (
            k * m * 0.25                             # 2-bit packed weights
            + n * k * 1.0                            # int8 activations
            + n * m * 2.0                            # bf16 outputs
            + m * 4.0                                # scales
        )
        return compute, bytes_moved / pk.hbm_bw

    def supports(self, frozen):
        return has_planes(frozen)

    def tiles(self, n, k, m, c=4):
        return _ops_tiles(n, k, m)

    def lower(self, frozen, x, *, use_pallas=None, interpret=None, lp=None):
        x32 = x.astype(jnp.float32)
        if resolve_use_pallas(use_pallas, interpret):
            from repro.kernels import ops

            kw = {}
            if lp is not None:      # execute the planned grid order + tiling
                kw["dataflow"] = lp.dataflow
                if len(lp.tile_sizes) == 3:
                    kw["bn"], kw["bk"], kw["bm"] = lp.tile_sizes
            return ops.tsar_matmul(x32, _packed_of(frozen, x),
                                   interpret=interpret, **kw)
        return _int8_dot(frozen, x32)


class TsarLUT:
    """Paper-faithful in-VMEM shared-LUT kernel (TLUT build + TGEMV gather)."""

    name = "tsar_lut"
    selectable = True
    serve_via_registry = False

    def cost(self, n, k, m, c=4, density=DEFAULT_DENSITY, block_density=None,
             block_shape=SPARSE_BLOCK):
        pk = _hw().chip_peaks()
        blocks = k / c
        lut_build = n * blocks * (2 ** c) * 1.0      # TLUT expansion ops
        # Each gather lowered as one-hot x LUT: 2^c MACs per (block, m) pair,
        # two gathers per block (pos/zero) fused into one 2^c-wide matmul.
        gather = 2.0 * n * blocks * m * (2 ** c) / 8.0
        compute = (lut_build + gather) / pk.int8_ops
        bytes_moved = (
            2.0 * (k / c) * m * 1.0                  # idx_pos + idx_zero, 1B each
            + n * k * 1.0
            + n * m * 2.0
            + m * 4.0
        )
        return compute, bytes_moved / pk.hbm_bw

    def supports(self, frozen):
        return _leaf(frozen, "idx_pos") is not None

    def tiles(self, n, k, m, c=4):
        from repro.kernels import ops

        return (ops._tile(-(-k // c), 128, 8), ops._tile(m, 256, 128))

    def lower(self, frozen, x, *, use_pallas=None, interpret=None, lp=None):
        from repro.core import lut

        x32 = x.astype(jnp.float32)
        c = _c_of(frozen)
        scale = _packed_of(frozen, x).scale
        if resolve_use_pallas(use_pallas, interpret):
            from repro.kernels import ops

            kw = {}
            if lp is not None and len(lp.tile_sizes) == 2:
                kw["bb"], kw["bm"] = lp.tile_sizes
            return ops.tsar_lut_gemv(x32, _leaf(frozen, "idx_pos"),
                                     _leaf(frozen, "idx_zero"), scale,
                                     c=c, interpret=interpret, **kw)
        return lut.tsar_lut_matmul(x32, _leaf(frozen, "idx_pos"),
                                   _leaf(frozen, "idx_zero"), c, scale)


class TsarSparse:
    """Zero-block-skipping matmul over a compacted BlockSparseTernary pool."""

    name = "tsar_sparse"
    selectable = True
    serve_via_registry = False

    def cost(self, n, k, m, c=4, density=DEFAULT_DENSITY, block_density=None,
             block_shape=SPARSE_BLOCK):
        """MXU work and weight bytes scale with the LIVE-block fraction; the
        index map (int32 per block) and per-strip gather lists are the
        sparsity tax, which is why the dense kernel wins at density ~ 1."""
        hw = _hw()
        pk = hw.chip_peaks()
        tax = hw.sparse_issue_tax()
        if block_density is None:
            block_density = estimate_block_density(density, block_shape)
        bk, bm = block_shape
        kb, mb = max(k / bk, 1.0), max(m / bm, 1.0)
        live = block_density * kb * mb
        flops = 2.0 * n * bk * bm * live             # int8 MACs, live blocks only
        decode_ops = bk * bm * live * 4.0            # bitplane unpack, live only
        compute = tax * (
            flops / pk.int8_ops + decode_ops / (pk.int8_ops / 2))
        bytes_moved = (
            tax * live * bk * bm * 0.25              # 2-bit planes, live blocks
            + kb * mb * 4.0                          # block-index map (int32)
            + 2.0 * live * 4.0                       # kids+slots gather lists
            + n * k * 1.0                            # int8 activations
            + n * m * 2.0                            # bf16 outputs
            + m * 4.0                                # scales
        )
        return compute, bytes_moved / pk.hbm_bw

    def supports(self, frozen):
        return _leaf(frozen, "sparse") is not None

    def tiles(self, n, k, m, c=4):
        from repro.kernels import ops

        bk, bm = SPARSE_BLOCK
        return (ops._tile(n, 128, 8), bk, bm)

    def lower(self, frozen, x, *, use_pallas=None, interpret=None, lp=None):
        sparse = _leaf(frozen, "sparse")
        if sparse is None:
            raise ValueError("layer was frozen without a block-sparse sidecar")
        x32 = x.astype(jnp.float32)
        if resolve_use_pallas(use_pallas, interpret):
            from repro.kernels import ops

            kw = {}
            if lp is not None and lp.tile_sizes:
                kw["bn"] = lp.tile_sizes[0]   # bk/bm are fixed by the format
            return ops.tsar_sparse_matmul(x32, sparse, interpret=interpret,
                                          **kw)
        # Traceable jnp fallback: identical math to the sparse kernel (the
        # planes decode to the same ternary matrix, and skipped blocks
        # contribute exact int32 zeros either way).  The zero-skip advantage
        # itself only materializes in the Pallas kernel.
        return _int8_dot(frozen, x32)


def _padded_of(frozen, x):
    """The layer's PaddedBlockSparseTernary: FrozenBitLinear carries the
    object; packed-param dicts (``layers.pack_linear`` sparse output) rebuild
    it from the ``sp_*`` leaves, taking the true K/M from activations and
    scales (pool shapes store only the block-padded grid)."""
    padded = _leaf(frozen, "padded")
    if padded is not None:
        return padded
    from repro.sparse import format as sparse_format

    sp = frozen["sp_sign"]
    from repro.core import ternary as _t

    bk, bm = sp.shape[-2] * _t.PACK, sp.shape[-1]
    kb, mb = frozen["sp_map"].shape
    return sparse_format.PaddedBlockSparseTernary(
        sign_pool=sp, zero_pool=frozen["sp_zero"],
        block_map=frozen["sp_map"],
        occupancy=jnp.zeros((kb, mb), jnp.float32),  # telemetry; not stored
        scale=frozen["scale"],
        kids=frozen["sp_kids"], slots=frozen["sp_slots"],
        counts=frozen["sp_counts"],
        shape=(x.shape[-1], frozen["scale"].shape[-1]),
        block_shape=(bk, bm),
        max_live=sp.shape[0], s_steps=frozen["sp_kids"].shape[-1])


class TsarSparsePadded(TsarSparse):
    """2-D zero-skip matmul over a PADDED (static-shape, vmappable) pool.

    Same live-block math as ``tsar_sparse``; the pool is padded to a static
    ``max_live`` and the walk to a static ``s_steps``, so stacked scan-layer
    weights carry per-layer pools through vmap — this is the sparse kernel
    the SERVING path can actually plan and dispatch (compacted pools are
    data-dependent and cannot ride a scanned params tree).
    """

    name = "tsar_sparse_padded"
    selectable = True
    serve_via_registry = True

    def cost(self, n, k, m, c=4, density=DEFAULT_DENSITY, block_density=None,
             block_shape=SPARSE_BLOCK):
        """Compacted cost + the pad-walk overhead: the static s_steps walk
        issues its masked (dead) steps too, at a calibratable fraction of a
        live block's compute.  Strictly above ``tsar_sparse`` at every
        density — when both formats are present, the compacted pool wins."""
        comp, mem = TsarSparse.cost(self, n, k, m, c, density=density,
                                    block_density=block_density,
                                    block_shape=block_shape)
        hw = _hw()
        pk = hw.chip_peaks()
        if block_density is None:
            block_density = estimate_block_density(density, block_shape)
        bk, bm = block_shape
        kb, mb = max(k / bk, 1.0), max(m / bm, 1.0)
        dead = (1.0 - block_density) * kb * mb
        per_block = (2.0 * n * bk * bm / pk.int8_ops
                     + bk * bm * 4.0 / (pk.int8_ops / 2))
        comp += hw.sparse_pad_step_frac() * dead * per_block
        return comp, mem

    def supports(self, frozen):
        if isinstance(frozen, dict):
            sp = frozen.get("sp_sign")
            return sp is not None and getattr(sp, "ndim", 0) == 3
        return _leaf(frozen, "padded") is not None

    def lower(self, frozen, x, *, use_pallas=None, interpret=None, lp=None):
        pbst = _padded_of(frozen, x)
        x32 = x.astype(jnp.float32)
        if resolve_use_pallas(use_pallas, interpret):
            from repro.kernels import ops

            kw = {}
            if lp is not None and lp.tile_sizes:
                kw["bn"] = lp.tile_sizes[0]   # bk/bm are fixed by the format
            return ops.tsar_sparse_padded_matmul(x32, pbst,
                                                 interpret=interpret, **kw)
        # Traceable spelling that decodes FROM THE POOL (so vmap-carried
        # pools are load-bearing in the jitted serving step) then runs the
        # exact int8 pipeline — bit-identical to the dense planes path
        # because the padded pool round-trips the ternary matrix exactly.
        from repro.core import lut, ternary
        from repro.sparse import format as sparse_format

        t = sparse_format.padded_to_ternary(pbst)
        a_q, a_scale = ternary.quantize_activations(x32)
        return lut.dense_int8_matmul(a_q, a_scale, t, pbst.scale)


class MemoryLUT:
    """DRAM-resident 3^c-entry LUT gather — the bitnet.cpp-style baseline the
    paper beats; kept servable for A/B runs, never chosen by the planner."""

    name = "memory_lut"
    selectable = False
    serve_via_registry = True

    def cost(self, n, k, m, c=4, density=DEFAULT_DENSITY, block_density=None,
             block_shape=SPARSE_BLOCK):
        pk = _hw().chip_peaks()
        blocks = k / c
        compute = 2.0 * n * blocks * m / pk.int8_ops
        bytes_moved = (
            n * blocks * (3 ** c) * 4.0              # DRAM-resident LUT tables
            + blocks * m * 1.0                       # index stream
            + n * k * 1.0 + n * m * 2.0 + m * 4.0
        )
        return compute, bytes_moved / pk.hbm_bw

    def supports(self, frozen):
        return has_planes(frozen)

    def tiles(self, n, k, m, c=4):
        return _ops_tiles(n, k, m)

    def lower(self, frozen, x, *, use_pallas=None, interpret=None, lp=None):
        from repro.core import lut, ternary

        packed = _packed_of(frozen, x)
        c = _c_of(frozen)
        x32 = x.astype(jnp.float32)
        t = ternary.unpack(packed)
        pad = (-t.shape[0]) % c   # ragged K: zero channels x zero weights = 0
        if pad:
            t = jnp.pad(t, ((0, pad), (0, 0)))
            x32 = jnp.pad(x32, [(0, 0)] * (x32.ndim - 1) + [(0, pad)])
        li = lut.ternary_lut_indices(t, c)
        return lut.memory_lut_matmul(x32, li, c, packed.scale)


class Dense:
    """Dequantize to fp and run a plain matmul — the correctness oracle and
    the escape hatch a hand-edited plan can force per layer."""

    name = "dense"
    selectable = False
    serve_via_registry = True

    def cost(self, n, k, m, c=4, density=DEFAULT_DENSITY, block_density=None,
             block_shape=SPARSE_BLOCK):
        pk = _hw().chip_peaks()
        compute = 2.0 * n * k * m / pk.bf16_flops
        bytes_moved = k * m * 2.0 + n * k * 2.0 + n * m * 2.0
        return compute, bytes_moved / pk.hbm_bw

    def supports(self, frozen):
        return has_planes(frozen)

    def tiles(self, n, k, m, c=4):
        return _ops_tiles(n, k, m)

    def lower(self, frozen, x, *, use_pallas=None, interpret=None, lp=None):
        from repro.core import lut, ternary

        w = ternary.unpack_dequant(_packed_of(frozen, x))
        return lut.dense_matmul(x.astype(jnp.float32), w)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, KernelImpl] = {}


def register(impl: KernelImpl) -> KernelImpl:
    """Register a kernel implementation (later registrations override)."""
    _REGISTRY[impl.name] = impl
    return impl


def get(name: str) -> KernelImpl:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel {name!r}; registered: {names()}") from None


def names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def selectable_names() -> tuple[str, ...]:
    return tuple(n for n in names() if _REGISTRY[n].selectable)


def available(frozen) -> tuple[str, ...]:
    """Kernel names whose encodings are present on this frozen layer."""
    return tuple(n for n in names() if _REGISTRY[n].supports(frozen))


def estimate_block_density(density: float, block_shape: tuple = SPARSE_BLOCK) -> float:
    """Live-block fraction under UNSTRUCTURED zeros at this density — which
    makes essentially every block live (``1 - (1-d)^(bk*bm) ~ 1``), so the
    sparse path is only chosen on *measured* structured sparsity."""
    bk, bm = block_shape
    return 1.0 - (1.0 - min(density, 1.0 - 1e-12)) ** (bk * bm)


def candidate_costs(n: int, k: int, m: int, c: int = 4,
                    density: float = DEFAULT_DENSITY,
                    block_density: float | None = None,
                    block_shape: tuple = SPARSE_BLOCK,
                    ) -> dict[str, tuple[float, float]]:
    """(compute_s, memory_s) per selectable kernel — the planner's input."""
    return {
        name: _REGISTRY[name].cost(n, k, m, c, density=density,
                                   block_density=block_density,
                                   block_shape=block_shape)
        for name in selectable_names()
    }


for _impl in (TsarMXU(), TsarLUT(), TsarSparse(), TsarSparsePadded(),
              MemoryLUT(), Dense()):
    register(_impl)
del _impl
