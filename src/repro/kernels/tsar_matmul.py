"""T-SAR MXU kernel: packed-ternary matmul, decode-in-VMEM -> MXU.

The TPU realization of the paper's in-register dataflow: the 2-bit weight
bitplanes are the ONLY weight bytes that cross HBM; they are expanded to
{-1,0,+1} int8 values inside VMEM, right next to the MXU, and consumed
immediately — the analogue of TLUT/TGEMV building and consuming tables
inside the SIMD register file instead of DRAM.  The activations arrive in
the bit-major channel order of :func:`deinterleave`, so the decode needs no
in-kernel reshape.

The serving engine's jitted step does not call this kernel: its packed
layers run the jnp planes spelling in ``models.layers._packed_linear``,
which computes the same integers.  This kernel runs when called through
``kernels.ops`` (or a registry lowering with ``use_pallas``).

Dataflow (paper Sec. III-D) maps to the grid iteration order:

* AP (activation-persistent): grid = (n, m, k) — the activation tile loaded
  for an ``n`` index is reused across all ``m`` tiles before moving on.
* OP (output-persistent): grid = (m, n, k) — the output accumulator for an
  ``m`` tile is completed before any other output tile is touched, and
  weight-plane tiles are reused across ``n``.

``k`` is always innermost: partial products accumulate in an int32 VMEM
scratch and the output is written once, on the final ``k`` step (the paper's
fused accumulation — no intermediate write-back).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK = 8


def deinterleave(a: jax.Array, tile: int, group: int) -> jax.Array:
    """Reorder each ``tile``-column tile of ``a`` (N, K) from channel order
    ``group*j + i`` to ``i * (tile//group) + j``.

    A packed weight row mixes ``group`` neighbouring channels: bit ``i`` of
    plane byte ``j`` holds channel ``8*j + i`` (LSB-first, as
    ``repro.core.ternary._pack_bits`` writes it), and bit ``i`` of LUT index
    ``j`` holds channel ``c*j + i``.  With the activations in this order, a
    kernel reads the channels that pair with bit ``i`` as one contiguous,
    lane-aligned slice, so it needs no lane or sublane reshape, which Mosaic
    refuses.  The permutation only changes the order of a sum.
    """
    n, k = a.shape
    return (a.reshape(n, k // tile, tile // group, group)
            .swapaxes(2, 3).reshape(n, k))


def decode_tile(sign: jax.Array, zero: jax.Array) -> jax.Array:
    """(bk//8, bm) uint8 sign/zero planes -> (bk, bm) int8 in {-1, 0, +1},
    rows in the bit-major order of :func:`deinterleave` (group 8)."""
    s = sign.astype(jnp.int32)
    z = zero.astype(jnp.int32)
    groups = [(1 - 2 * ((s >> i) & 1)) * (1 - ((z >> i) & 1))
              for i in range(PACK)]
    return jnp.concatenate(groups, axis=0).astype(jnp.int8)


def vmem_bytes(bn: int, bk: int, bm: int) -> int:
    """VMEM one grid step of :func:`tsar_matmul_packed` needs: its in/out
    blocks, double-buffered (a (bn, 1) column pads to 128 lanes, a (1, bm)
    row to 8 sublanes), the int32 accumulator, and the decoded tile (int32
    bit groups, then the int8 stack).  Compare with the chip's scoped VMEM
    limit (``repro.core.hw``), not its whole VMEM."""
    blocks = (bn * bk + 2 * (bk // PACK) * bm + bn * 128 * 4 + 8 * bm * 4
              + bn * bm * 4)
    return 2 * blocks + bn * bm * 4 + bk * bm * 5 + 2 * (bk // PACK) * bm * 4


def _kernel(a_ref, sign_ref, zero_ref, asc_ref, wsc_ref, o_ref, acc_ref, *,
            k_steps: int, k_axis: int):
    """One (bn, bm, bk) tile step."""
    kstep = pl.program_id(k_axis)

    @pl.when(kstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vals = decode_tile(sign_ref[...], zero_ref[...])
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], vals,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(kstep == k_steps - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32)
            * asc_ref[...].astype(jnp.float32)          # (bn, 1) per-token
            * wsc_ref[...].astype(jnp.float32)          # (1, bm) per-channel
        )


@functools.partial(
    jax.jit,
    static_argnames=("bn", "bk", "bm", "dataflow", "interpret"),
)
def tsar_matmul_packed(
    a_q: jax.Array,        # int8 (N, K)
    a_scale: jax.Array,    # f32  (N, 1)
    sign_plane: jax.Array, # uint8 (K//8, M)
    zero_plane: jax.Array, # uint8 (K//8, M)
    w_scale: jax.Array,    # f32  (M,)
    *,
    bn: int = 128,
    bk: int = 512,
    bm: int = 256,
    dataflow: str = "AP",
    interpret: bool = False,
) -> jax.Array:
    """(N, K) int8 x packed ternary (K, M) -> (N, M) f32.

    Caller guarantees N % bn == K % bk == M % bm == 0 (ops.py pads).
    """
    n, k = a_q.shape
    m = sign_plane.shape[1]
    n_t, k_t, m_t = n // bn, k // bk, m // bm

    if dataflow == "AP":
        grid = (n_t, m_t, k_t)
        nm = lambda i, j, s: (i, j)          # grid ids -> (n_idx, m_idx)
    elif dataflow == "OP":
        grid = (m_t, n_t, k_t)
        nm = lambda i, j, s: (j, i)
    else:
        raise ValueError(f"dataflow must be AP or OP, got {dataflow!r}")
    k_axis = 2

    def a_map(i, j, s):
        ni, _ = nm(i, j, s)
        return (ni, s)

    def plane_map(i, j, s):
        _, mi = nm(i, j, s)
        return (s, mi)

    def asc_map(i, j, s):
        ni, _ = nm(i, j, s)
        return (ni, 0)

    def wsc_map(i, j, s):
        _, mi = nm(i, j, s)
        return (0, mi)

    def o_map(i, j, s):
        return nm(i, j, s)

    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_t, k_axis=k_axis),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), a_map),
            pl.BlockSpec((bk // PACK, bm), plane_map),
            pl.BlockSpec((bk // PACK, bm), plane_map),
            pl.BlockSpec((bn, 1), asc_map),
            pl.BlockSpec((1, bm), wsc_map),
        ],
        out_specs=pl.BlockSpec((bn, bm), o_map),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.int32)],
        interpret=interpret,
    )(deinterleave(a_q, bk, PACK), sign_plane, zero_plane, a_scale,
      w_scale.reshape(1, m))
    return out
