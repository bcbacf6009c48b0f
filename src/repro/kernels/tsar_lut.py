"""Paper-faithful T-SAR LUT kernel: in-VMEM TLUT build + TGEMV consume.

This kernel is the literal transcription of the paper's two-instruction
pipeline onto Pallas/TPU:

* **TLUT_cxs** (Fig. 6(b)) — for every activation block of size ``c``, build
  the shared binary LUT ``S[p] = sum_i bit_i(p) * a_i`` (2^c entries), each
  entry one vector add from a smaller one.  The activations arrive in the
  channel-major order of ``tsar_matmul.deinterleave``, so ``a_i`` for a
  whole tile of blocks is one lane-aligned slice and the LUT is 2^c - 1
  VMEM vectors; it never exists outside the kernel, exactly like the
  YMM-resident tables.
* **TGEMV_kxm** (Fig. 6(c)) — consume the LUTs against pre-encoded weight
  indices with fused accumulation.  A gather from a 2^c-entry table is, on
  TPU, a one-hot matmul — the MXU plays the role of the SIMD adder trees.
  We fuse the paper's two gathers (dense/sparse planes) into a single
  combined one-hot operand per LUT entry: ``comb_p = 2*(idx_pos == p) +
  (idx_zero == p)`` so that ``y = sum_p S_p @ comb_p - sum(a)``
  (DESIGN.md Sec. 2.1 single-LUT identity).

Grid: (m_tiles, b_tiles) with the block axis innermost; the (N, bm) f32
accumulator lives in VMEM scratch and is written back once (fused
accumulation, no intermediate write-back — the OP dataflow of Fig. 7(b)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tsar_matmul import deinterleave


def vmem_bytes(n: int, bb: int, bm: int, c: int) -> int:
    """VMEM one grid step of :func:`tsar_lut_gemv` needs: its in/out blocks,
    double-buffered (rows pad to 8 sublanes), the f32 accumulator, the
    2^c - 1 LUT entries, the widened indices and one one-hot operand.
    Compare with the chip's scoped VMEM limit (``repro.core.hw``)."""
    rows = -(-n // 8) * 8
    blocks = rows * bb * c * 4 + 2 * bb * bm + 8 * bm * 4 + rows * bm * 4
    temps = ((1 << c) - 1) * rows * bb * 4 + 2 * bb * bm * 4 + 2 * bb * bm * 4
    return 2 * blocks + rows * bm * 4 + temps


def _kernel(a_ref, ipos_ref, izero_ref, wsc_ref, o_ref, acc_ref, *,
            c: int, b_steps: int):
    bstep = pl.program_id(1)

    @pl.when(bstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bb = ipos_ref.shape[0]          # blocks in this tile
    a = a_ref[...]                  # (n, c*bb), channel-major (see deinterleave)
    # a_i[:, b] is channel i of block b: one lane-aligned slice per bit.
    a_bits = [a[:, i * bb:(i + 1) * bb] for i in range(c)]

    # ---- TLUT: build the shared binary LUT in VMEM ----------------------
    # S_p = sum_i bit_i(p) * a_i, each entry one add from a smaller one
    # (S_0 = 0 is never materialized: it gathers nothing).
    lut = [None] * (1 << c)
    for p in range(1, 1 << c):
        low = p & -p
        a_low = a_bits[low.bit_length() - 1]
        lut[p] = a_low if p == low else lut[p ^ low] + a_low    # (n, bb)
    tot = jnp.sum(lut[(1 << c) - 1], axis=1, keepdims=True)     # (n, 1)

    # ---- TGEMV: combined one-hot gather + fused accumulation ------------
    # y[n, m] += sum_p S_p[n, :] @ comb_p[:, m] - tot[n], with
    # comb_p = 2*onehot(idx_pos == p) + onehot(idx_zero == p).
    ip = ipos_ref[...].astype(jnp.int32)                          # (bb, bm)
    iz = izero_ref[...].astype(jnp.int32)
    acc = acc_ref[...] - tot
    for p in range(1, 1 << c):
        comb = (2.0 * (ip == p).astype(jnp.float32)
                + (iz == p).astype(jnp.float32))
        acc += jax.lax.dot_general(
            lut[p], comb,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    acc_ref[...] = acc

    @pl.when(bstep == b_steps - 1)
    def _finish():
        o_ref[...] = acc_ref[...] * wsc_ref[...].astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("c", "bb", "bm", "interpret")
)
def tsar_lut_gemv(
    a: jax.Array,          # f32 (N, K) — N small (decode batch)
    idx_pos: jax.Array,    # uint8 (K//c, M)
    idx_zero: jax.Array,   # uint8 (K//c, M)
    w_scale: jax.Array,    # f32 (M,)
    *,
    c: int = 4,
    bb: int = 128,         # blocks per tile (bb*c input channels)
    bm: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """(N, K) x encoded ternary (K, M) -> (N, M) f32 via in-VMEM LUTs.

    Caller guarantees (K//c) % bb == 0 and M % bm == 0 (ops.py pads).
    """
    n, k = a.shape
    blocks, m = idx_pos.shape
    assert blocks * c == k, (blocks, c, k)
    b_t, m_t = blocks // bb, m // bm

    out = pl.pallas_call(
        functools.partial(_kernel, c=c, b_steps=b_t),
        grid=(m_t, b_t),
        in_specs=[
            pl.BlockSpec((n, bb * c), lambda mi, bi: (0, bi)),
            pl.BlockSpec((bb, bm), lambda mi, bi: (bi, mi)),
            pl.BlockSpec((bb, bm), lambda mi, bi: (bi, mi)),
            pl.BlockSpec((1, bm), lambda mi, bi: (0, mi)),
        ],
        out_specs=pl.BlockSpec((n, bm), lambda mi, bi: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, bm), jnp.float32)],
        interpret=interpret,
    )(deinterleave(a.astype(jnp.float32), bb * c, c), idx_pos, idx_zero,
      w_scale.reshape(1, m))
    return out
