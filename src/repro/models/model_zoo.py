"""Config -> model dispatch + input spec construction for every cell.

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every model
input — the dry-run lowers against these (no allocation ever happens for the
full-size configs).  Modality frontends are stubs per the assignment: audio
supplies frame embeddings, vision supplies patch embeddings.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, transformer
from repro.plan import runtime as plan_runtime


def _mod(cfg: ModelConfig):
    return encdec if cfg.family == "encdec" else transformer


def init_params(cfg: ModelConfig, key) -> dict:
    return _mod(cfg).init_params(cfg, key)


# Inference entry points accept an optional compiled ``repro.plan.ModelPlan``:
# the plan is activated around the model call, so every packed BitLinear
# inside dispatches through the plan's trace-time table lookup instead of any
# per-step kernel selection.  ``plan=None`` keeps whatever plan an enclosing
# context (e.g. the serving engine) already activated.

def forward(cfg, params, batch, train=True, remat=False, plan=None):
    with plan_runtime.activate(plan):
        return _mod(cfg).forward(cfg, params, batch, train, remat=remat)


def loss_fn(cfg, params, batch, train=True, remat=False):
    return _mod(cfg).loss_fn(cfg, params, batch, train, remat=remat)


def init_cache(cfg, batch_size, max_len, dtype=jnp.float32):
    return _mod(cfg).init_cache(cfg, batch_size, max_len, dtype)


def prefill(cfg, params, batch, cache, train=False, plan=None):
    with plan_runtime.activate(plan):
        return _mod(cfg).prefill(cfg, params, batch, cache, train)


def decode_step(cfg, params, tokens, cache, t, train=False, plan=None):
    with plan_runtime.activate(plan):
        return _mod(cfg).decode_step(cfg, params, tokens, cache, t, train)


def chunk_step(cfg, params, tokens, pos, cache, lengths, train=False, plan=None):
    """Per-slot chunked-append step (paged serving engine): tokens/pos (B, C),
    lengths (B,) per-slot write offsets.  A slot's first chunk may start at a
    nonzero ``lengths[i]`` against a pre-populated block table (prefix-cache
    fork).  See transformer.chunk_step."""
    with plan_runtime.activate(plan):
        return _mod(cfg).chunk_step(cfg, params, tokens, pos, cache, lengths,
                                    train)


def flat_step(cfg, params, tokens, slot, pos, pools, table, emit_row,
              train=False, plan=None):
    """Flat token-packed step (paged serving engine, ``flat`` policy):
    tokens/slot/pos (T,) per-token triples — multiple concurrent prefill
    chunks plus all decode tokens in one call — run against the block pools
    in place through the (B, VB) block ``table``, and emit_row (B,)
    selecting each slot's logit row before the head.  See
    transformer.flat_step."""
    with plan_runtime.activate(plan):
        return _mod(cfg).flat_step(cfg, params, tokens, slot, pos, pools,
                                   table, emit_row, train)


# ---------------------------------------------------------------------------
# Block-paged KV cache plumbing (serving engine)
#
# The attention K/V leaves ("k"/"v") are stored as a pool of fixed-size token
# blocks, lane-dense: (L, num_blocks, block_size, Hkv*Dh).  Heads and head
# dim share the last axis so the TPU tiles it whole (640 or 1024 lanes); a
# (..., Hkv, Dh) pool puts Hkv on the sublanes, and XLA then copies the
# whole pool into and out of any loop that writes it in place.  Per-slot
# block tables map a slot's logical token positions onto pool blocks.
# Everything else (SSM conv/state, enc-dec cross K/V) is O(1)-per-slot state
# and stays dense with a leading slot axis.  Block 0 is a reserved scratch
# block: table padding points at it, so reads and writes of unallocated
# table entries touch garbage that the causal mask guarantees is never
# attended.
# ---------------------------------------------------------------------------

PAGED_LEAVES = ("k", "v")


def init_paged_cache(cfg, slots: int, num_blocks: int, block_size: int,
                     dtype=jnp.float32) -> dict:
    """Pool-shaped decode caches: paged K/V pools + dense per-slot state."""
    proto = jax.eval_shape(lambda: init_cache(cfg, slots, block_size, dtype))
    pools = {}
    for name, leaf in proto.items():
        if name in PAGED_LEAVES:
            l, _, bs, hk, dh = leaf.shape
            pools[name] = jnp.zeros((l, num_blocks, bs, hk * dh), dtype)
        else:
            pools[name] = jnp.zeros(leaf.shape, leaf.dtype)
    return pools


@jax.named_scope("kv_gather")
def gather_cache_view(pools: dict, block_table, n_kv_heads: int) -> dict:
    """Materialize a contiguous per-slot cache view through block tables.

    block_table (B, VB) int32 — each slot's first VB blocks (0-padded).
    Paged leaves (L, NB, bs, Hkv*Dh) -> (L, B, VB*bs, Hkv, Dh); dense leaves
    pass through.  The result is shaped exactly like
    ``init_cache(cfg, B, VB*bs)`` so the model's prefill/decode/chunk entry
    points run on it unchanged.
    """
    view = {}
    for name, leaf in pools.items():
        if name in PAGED_LEAVES:
            l, _, bs, lanes = leaf.shape
            b, vb = block_table.shape
            g = leaf[:, block_table]                      # (L, B, VB, bs, HD)
            view[name] = g.reshape(
                (l, b, vb * bs, n_kv_heads, lanes // n_kv_heads))
        else:
            view[name] = leaf
    return view


@jax.named_scope("kv_scatter")
def scatter_cache_view(pools: dict, block_table, view: dict) -> dict:
    """Write an updated contiguous view back into the block pools.

    Table entries may repeat block 0 (scratch); duplicate scatters there are
    benign because scratch contents are never read as live data.
    """
    out = {}
    for name, leaf in pools.items():
        if name in PAGED_LEAVES:
            l, _, bs, lanes = leaf.shape
            b, vb = block_table.shape
            blk = view[name].reshape((l, b, vb, bs, lanes))
            out[name] = leaf.at[:, block_table].set(blk)
        else:
            out[name] = view[name]
    return out


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, dry-run contract)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, cache_dtype=jnp.bfloat16) -> dict:
    """Model inputs for one (arch x shape) cell.

    * train/prefill cells: full-sequence token batches (+ frontend stubs).
      For VLM the patch tokens occupy the first ``frontend_seq`` positions of
      the cell's seq_len budget, so total backbone length == shape.seq_len.
    * decode cells: one new token per sequence + the KV/SSM caches sized to
      shape.seq_len (``serve_step`` contract).
    """
    b, s = shape.global_batch, shape.seq_len
    kind = shape.kind
    specs: dict = {}

    if kind in ("train", "prefill"):
        s_text = s
        if cfg.family == "vlm":
            s_text = s - cfg.frontend_seq
            specs["patches"] = _sds((b, cfg.frontend_seq, cfg.frontend_dim), jnp.float32)
        if cfg.family == "encdec":
            specs["frames"] = _sds((b, cfg.enc_seq, cfg.d_model), jnp.float32)
        specs["tokens"] = _sds((b, s_text), jnp.int32)
        if kind == "train":
            specs["labels"] = _sds((b, s_text), jnp.int32)
        else:
            # prefill also takes the cache it fills
            specs["cache"] = jax.eval_shape(
                lambda: init_cache(cfg, b, s, cache_dtype))
    else:  # decode
        specs["tokens"] = _sds((b, 1), jnp.int32)
        specs["cache"] = jax.eval_shape(lambda: init_cache(cfg, b, s, cache_dtype))
        specs["t"] = _sds((), jnp.int32)
    return specs


def param_specs(cfg: ModelConfig, key=None) -> dict:
    """Parameter ShapeDtypeStructs via eval_shape (no allocation)."""
    key = jax.random.PRNGKey(0) if key is None else key
    return jax.eval_shape(lambda k: init_params(cfg, k), key)
