"""Public jitted wrappers for the T-SAR Pallas kernels.

Handles activation quantization, shape padding to tile multiples and
leading-dim flattening.  ``interpret=None`` compiles the kernels with Mosaic
on a TPU and runs them in the Pallas interpreter on any other backend: that
is how the CPU tests validate them.  A run that must prove the compiled
kernel passes ``interpret=False`` (``chip_smoke.py`` does).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ternary
from repro.kernels import tsar_lut as _lut_kernel
from repro.kernels import tsar_matmul as _mxu_kernel
from repro.kernels import tsar_sparse as _sparse_kernel
from repro.sparse import format as sparse_format


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _tile(n: int, pref: int, align: int) -> int:
    """Pick a tile size <= pref that keeps the padded dim a tile multiple."""
    if n >= pref:
        return pref
    return max(align, ((n + align - 1) // align) * align)


# ---------------------------------------------------------------------------
# Shared prologue/epilogue: every public wrapper flattens leading dims to one
# row axis, (maybe) quantizes + pads to tile multiples, and finally slices the
# padding off and restores the leading dims.
# ---------------------------------------------------------------------------

def _flatten_lead(x: jax.Array) -> tuple[jax.Array, tuple, int]:
    """(..., K) -> ((N, K) float32, lead_shape, N)."""
    lead = x.shape[:-1]
    n = 1
    for d in lead:
        n *= d
    return x.reshape((n, x.shape[-1])).astype(jnp.float32), lead, n


def _quantize_padded(x2: jax.Array, bn: int, k_mult: int) -> tuple[jax.Array, jax.Array]:
    """Per-token int8 quantization, rows padded to ``bn`` and the channel
    axis zero-padded to ``k_mult`` (zero rows/columns contribute nothing)."""
    a_q, a_scale = ternary.quantize_activations(x2)
    a_q = _pad_to(_pad_to(a_q, 0, bn), 1, k_mult)
    a_scale = _pad_to(a_scale, 0, bn)
    return a_q, a_scale


def _unflatten_lead(y: jax.Array, lead: tuple, n: int, m: int) -> jax.Array:
    """(N_padded, M_padded) -> (..., M): slice padding, restore lead dims."""
    return y[:n, :m].reshape(lead + (m,))


def tsar_matmul(
    x: jax.Array,
    tw: ternary.TernaryWeights,
    *,
    dataflow: str = "AP",
    bn: int = 128,
    bk: int = 512,
    bm: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """BitLinear matmul via the production packed-decode kernel.

    ``x`` (..., K) float -> (..., M) float32.  Full pipeline: per-token int8
    quant -> packed-ternary int8 matmul with VMEM decode -> fused dequant.
    """
    if interpret is None:
        interpret = _auto_interpret()
    k, m = tw.shape
    x2, lead, n = _flatten_lead(x)

    bn_ = _tile(n, bn, 8)
    bk_ = _tile(k, bk, 128)   # keeps plane tile rows (bk//8) a sublane multiple
    bm_ = _tile(m, bm, 128)

    a_q, a_scale = _quantize_padded(x2, bn_, bk_)
    # Padded K rows decode to sign=0,zero=0 => weight +1, but the matching
    # activation rows are zero-padded so they contribute nothing.  Padded M
    # columns are sliced off below.
    sign = _pad_to(_pad_to(tw.sign_plane, 0, bk_ // 8), 1, bm_)
    zero = _pad_to(_pad_to(tw.zero_plane, 0, bk_ // 8), 1, bm_)
    wsc = _pad_to(tw.scale, 0, bm_)

    y = _mxu_kernel.tsar_matmul_packed(
        a_q, a_scale, sign, zero, wsc,
        bn=bn_, bk=bk_, bm=bm_, dataflow=dataflow, interpret=interpret,
    )
    return _unflatten_lead(y, lead, n, m)


def tsar_sparse_matmul(
    x: jax.Array,
    bst: "sparse_format.BlockSparseTernary",
    *,
    bn: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """BitLinear matmul via the zero-block-skipping sparse kernel.

    ``x`` (..., K) float -> (..., M) float32.  Same pipeline as
    :func:`tsar_matmul` (per-token int8 quant -> int32 accumulate -> fused
    dequant) but weights come from a compacted :class:`BlockSparseTernary`
    pool and dead (bk, bm) blocks are skipped entirely — the inner grid runs
    over LIVE blocks per m-strip, so interpret-mode cost (and on TPU, HBM
    traffic + MXU issue) drops with block density.  Output is bit-identical
    to the dense path: skipped blocks contribute exactly 0 in int32.
    """
    if interpret is None:
        interpret = _auto_interpret()
    k, m = bst.shape
    bk, bm = bst.block_shape
    kb, mb = bst.grid
    x2, lead, n = _flatten_lead(x)

    bn_ = _tile(n, bn, 8)
    # Pad activations to the format's padded K (pad columns hit zero-padded
    # weight tails inside edge blocks — or dead blocks — so they are exact).
    a_q, a_scale = _quantize_padded(x2, bn_, kb * bk)
    wsc = _pad_to(bst.scale, 0, mb * bm)

    kids, slots, counts, s_max = sparse_format.strip_schedule(bst)
    y = _sparse_kernel.tsar_sparse_matmul_packed(
        a_q, a_scale, bst.sign_pool, bst.zero_pool, kids, slots, counts,
        wsc.reshape(1, mb * bm),
        bn=bn_, bk=bk, bm=bm, s_steps=max(s_max, 1), interpret=interpret,
    )
    return _unflatten_lead(y, lead, n, m)


def tsar_sparse_padded_matmul(
    x: jax.Array,
    pbst: "sparse_format.PaddedBlockSparseTernary",
    *,
    bn: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """BitLinear matmul via the padded-pool 2-D zero-skip kernel.

    ``x`` (..., K) float -> (..., M) float32.  Same pipeline as
    :func:`tsar_sparse_matmul`, but the weights are a static-shaped
    :class:`PaddedBlockSparseTernary` pool (so the call is vmappable over
    stacked scan layers) and the schedule is 2-D: dead weight blocks are
    skipped via ``counts`` AND all-zero (bn, bk) activation tiles via a
    per-(n-strip, k-block) liveness map computed here from the quantized
    activations.  Both skips drop exact int32 zeros — output is
    bit-identical to :func:`tsar_matmul`.
    """
    if interpret is None:
        interpret = _auto_interpret()
    k, m = pbst.shape
    bk, bm = pbst.block_shape
    kb, mb = pbst.grid
    x2, lead, n = _flatten_lead(x)

    bn_ = _tile(n, bn, 8)
    a_q, a_scale = _quantize_padded(x2, bn_, kb * bk)
    wsc = _pad_to(pbst.scale, 0, mb * bm)

    # Activation-side liveness: one flag per (n-strip, k-block) tile.  Padded
    # rows/channels are zero, so the map also encodes the shape padding.
    n_t = a_q.shape[0] // bn_
    act_live = jnp.any(
        a_q.reshape(n_t, bn_, kb, bk) != 0, axis=(1, 3)).astype(jnp.int32)

    y = _sparse_kernel.tsar_sparse_padded_matmul_packed(
        a_q, a_scale, pbst.sign_pool, pbst.zero_pool,
        pbst.kids, pbst.slots, pbst.counts, act_live,
        wsc.reshape(1, mb * bm),
        bn=bn_, bk=bk, bm=bm, s_steps=max(pbst.s_steps, 1),
        interpret=interpret,
    )
    return _unflatten_lead(y, lead, n, m)


def tsar_lut_gemv(
    x: jax.Array,
    idx_pos: jax.Array,
    idx_zero: jax.Array,
    w_scale: jax.Array,
    *,
    c: int = 4,
    bb: int = 128,
    bm: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """BitLinear GEMV via the paper-faithful in-VMEM LUT kernel.

    ``x`` (..., K) float -> (..., M) float32.
    """
    if interpret is None:
        interpret = _auto_interpret()
    blocks, m = idx_pos.shape
    x2, lead, n = _flatten_lead(x)  # true K; blocks*c >= k for ragged layers

    bb_ = _tile(blocks, bb, 8)
    bm_ = _tile(m, bm, 128)

    # Padded activation channels are zero, so padded-block LUT entries are all
    # zero and any index gathers 0 — padding is exact.  This also covers a
    # ragged tail block (pack_indices zero-padded K up to blocks*c).
    x2 = _pad_to(_pad_to(x2, 1, blocks * c), 1, bb_ * c)
    ip = _pad_to(_pad_to(idx_pos, 0, bb_), 1, bm_)
    iz = _pad_to(_pad_to(idx_zero, 0, bb_), 1, bm_)
    wsc = _pad_to(w_scale, 0, bm_)

    y = _lut_kernel.tsar_lut_gemv(
        x2, ip, iz, wsc, c=c, bb=bb_, bm=bm_, interpret=interpret
    )
    return _unflatten_lead(y, lead, n, m)
