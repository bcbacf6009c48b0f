"""Sparsity-aware T-SAR kernel: zero-block-skipping packed-ternary matmul.

Same inner tile as ``tsar_matmul`` (2-bit bitplanes decoded to {-1,0,+1} int8
in VMEM, consumed by the MXU, int32 accumulation, fused dequant) — but the
weight operand is a :class:`repro.sparse.format.BlockSparseTernary` compacted
pool, and the kernel *never touches dead blocks*:

* the grid's inner extent is ``s_max`` — the max number of LIVE k-blocks in
  any m-strip — not ``K / bk``.  A model whose FFN block columns are 30% dead
  runs a 30% shorter grid;
* per-step, scalar-prefetched index maps (``pltpu.PrefetchScalarGridSpec``)
  gather the s-th live block's activation k-slice and pool slot, so only live
  blocks' bytes ever cross HBM -> VMEM;
* strips with fewer live blocks than ``s_max`` mask the tail contributions
  with ``s < counts[j]`` (the padded DMA reads slot 0, a valid block, and the
  mask drops it).

Skipped blocks contribute exactly 0 to the int32 accumulator, so the output
is bit-identical to the dense ``tsar_matmul`` path.

The **padded-pool 2-D schedule** (:func:`tsar_sparse_padded_matmul_packed`)
extends the skip to the activation side: besides the weight-side
``s < counts[j]`` guard, a scalar-prefetched ``(n-strip, k-block)`` liveness
map — computed from the quantized activations before the call — drops the
dot for any (bn, bk) activation tile that is entirely zero (padded batch
rows, padded K channels, genuinely silent token tiles).  Both guards drop
exact int32 zeros, so the output stays bit-identical to ``tsar_matmul``.
``s_steps`` is STATIC here (the padded format's uniform walk width), which
is what lets stacked scan layers run this kernel with per-layer pools
carried through ``vmap``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Same in-VMEM bitplane decode as the dense kernel — one definition, so the
# two kernels can't drift from core/ternary._pack_bits's LSB-first layout.
from repro.kernels.tsar_matmul import PACK, decode_tile, deinterleave


def _kernel(kids_ref, slots_ref, counts_ref, a_ref, sign_ref, zero_ref,
            asc_ref, wsc_ref, o_ref, acc_ref, *, s_steps: int):
    """One (m_tile, n_tile, live-block step)."""
    j = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < counts_ref[j])
    def _accumulate():
        vals = decode_tile(sign_ref[0], zero_ref[0])
        acc_ref[...] += jax.lax.dot_general(
            a_ref[...], vals,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    @pl.when(s == s_steps - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32)
            * asc_ref[...].astype(jnp.float32)          # (bn, 1) per-token
            * wsc_ref[...].astype(jnp.float32)          # (1, bm) per-channel
        )


@functools.partial(
    jax.jit,
    static_argnames=("bn", "bk", "bm", "s_steps", "interpret"),
)
def tsar_sparse_matmul_packed(
    a_q: jax.Array,        # int8 (N, Kp)  Kp = kb * bk (zero-padded)
    a_scale: jax.Array,    # f32  (N, 1)
    sign_pool: jax.Array,  # uint8 (n_slots, bk//8, bm)
    zero_pool: jax.Array,  # uint8 (n_slots, bk//8, bm)
    kids: jax.Array,       # int32 (mb, s_steps)  k-block index per live step
    slots: jax.Array,      # int32 (mb, s_steps)  pool slot per live step
    counts: jax.Array,     # int32 (mb,)          live blocks per m-strip
    w_scale: jax.Array,    # f32  (1, Mp)  Mp = mb * bm
    *,
    bn: int,
    bk: int,
    bm: int,
    s_steps: int,
    interpret: bool = False,
) -> jax.Array:
    """(N, Kp) int8 x block-sparse ternary pool -> (N, Mp) f32.

    Caller guarantees N % bn == 0, Kp == kb*bk, Mp == mb*bm, s_steps >= 1
    (ops.py pads / clamps).
    """
    n = a_q.shape[0]
    mb = kids.shape[0]
    n_t = n // bn
    grid = (mb, n_t, s_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # kids, slots, counts
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), lambda j, i, s, kids, slots, counts: (i, kids[j, s])),
            pl.BlockSpec((1, bk // PACK, bm),
                         lambda j, i, s, kids, slots, counts: (slots[j, s], 0, 0)),
            pl.BlockSpec((1, bk // PACK, bm),
                         lambda j, i, s, kids, slots, counts: (slots[j, s], 0, 0)),
            pl.BlockSpec((bn, 1), lambda j, i, s, kids, slots, counts: (i, 0)),
            pl.BlockSpec((1, bm), lambda j, i, s, kids, slots, counts: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda j, i, s, kids, slots, counts: (i, j)),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, s_steps=s_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, mb * bm), jnp.float32),
        interpret=interpret,
    )(kids, slots, counts, deinterleave(a_q, bk, PACK), sign_pool, zero_pool,
      a_scale, w_scale)
    return out


# ---------------------------------------------------------------------------
# Padded-pool kernel: 2-D (n-strip x m-strip) zero-skip schedule
# ---------------------------------------------------------------------------

def _kernel_2d(kids_ref, slots_ref, counts_ref, act_live_ref, a_ref, sign_ref,
               zero_ref, asc_ref, wsc_ref, o_ref, acc_ref, *, s_steps: int):
    """One (m_tile, n_tile, walk step) — dead WEIGHT blocks are masked by
    ``counts`` exactly like :func:`_kernel`; dead ACTIVATION tiles by the
    scalar-prefetched per-(n-strip, k-block) liveness map."""
    j = pl.program_id(0)
    i = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (s < counts_ref[j]) & (act_live_ref[i, kids_ref[j, s]] > 0)

    @pl.when(live)
    def _accumulate():
        vals = decode_tile(sign_ref[0], zero_ref[0])
        acc_ref[...] += jax.lax.dot_general(
            a_ref[...], vals,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    @pl.when(s == s_steps - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32)
            * asc_ref[...].astype(jnp.float32)          # (bn, 1) per-token
            * wsc_ref[...].astype(jnp.float32)          # (1, bm) per-channel
        )


@functools.partial(
    jax.jit,
    static_argnames=("bn", "bk", "bm", "s_steps", "interpret"),
)
def tsar_sparse_padded_matmul_packed(
    a_q: jax.Array,        # int8 (N, Kp)  Kp = kb * bk (zero-padded)
    a_scale: jax.Array,    # f32  (N, 1)
    sign_pool: jax.Array,  # uint8 (max_live, bk//8, bm)
    zero_pool: jax.Array,  # uint8 (max_live, bk//8, bm)
    kids: jax.Array,       # int32 (mb, s_steps)  k-block index per walk step
    slots: jax.Array,      # int32 (mb, s_steps)  pool slot per walk step
    counts: jax.Array,     # int32 (mb,)          live blocks per m-strip
    act_live: jax.Array,   # int32 (N//bn, kb)    1 = activation tile nonzero
    w_scale: jax.Array,    # f32  (1, Mp)  Mp = mb * bm
    *,
    bn: int,
    bk: int,
    bm: int,
    s_steps: int,
    interpret: bool = False,
) -> jax.Array:
    """(N, Kp) int8 x padded block-sparse ternary pool -> (N, Mp) f32.

    Identical contract to :func:`tsar_sparse_matmul_packed`, but ``s_steps``
    is the padded format's STATIC walk width and the extra ``act_live`` map
    adds the activation-side skip.  Caller guarantees N % bn == 0,
    Kp == kb*bk, Mp == mb*bm, s_steps >= 1 (ops.py pads / clamps).
    """
    n = a_q.shape[0]
    mb = kids.shape[0]
    n_t = n // bn
    grid = (mb, n_t, s_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # kids, slots, counts, act_live
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk),
                         lambda j, i, s, kids, slots, counts, al: (i, kids[j, s])),
            pl.BlockSpec((1, bk // PACK, bm),
                         lambda j, i, s, kids, slots, counts, al: (slots[j, s], 0, 0)),
            pl.BlockSpec((1, bk // PACK, bm),
                         lambda j, i, s, kids, slots, counts, al: (slots[j, s], 0, 0)),
            pl.BlockSpec((bn, 1),
                         lambda j, i, s, kids, slots, counts, al: (i, 0)),
            pl.BlockSpec((1, bm),
                         lambda j, i, s, kids, slots, counts, al: (0, j)),
        ],
        out_specs=pl.BlockSpec(
            (bn, bm), lambda j, i, s, kids, slots, counts, al: (i, j)),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_2d, s_steps=s_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, mb * bm), jnp.float32),
        interpret=interpret,
    )(kids, slots, counts, act_live, deinterleave(a_q, bk, PACK), sign_pool,
      zero_pool, a_scale, w_scale)
    return out
