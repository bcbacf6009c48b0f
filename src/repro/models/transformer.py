"""Generic decoder-only LM covering the dense / moe / ssm / hybrid / vlm
families, with lax.scan over stacked layer parameters.

Heterogeneous local/global attention stacks (gemma2/gemma3/hymba) are scanned
homogeneously: a per-layer ``is_global`` flag array rides along the scan and
is blended into the attention mask (DESIGN.md §6), so HLO size stays O(1) in
depth — essential for compiling 62-layer configs 40 times in the dry-run.

Three lowered entry points per model:
* ``forward``      — full-sequence teacher-forced logits (train/eval).
* ``prefill``      — full-sequence forward that also fills the KV/SSM caches.
* ``decode_step``  — one-token autoregressive step against the caches.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import layers, moe as moe_lib, ssm as ssm_lib


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg) -> dict:
    ks = jax.random.split(key, 8)
    fam = cfg.family
    p: dict = {}
    if fam in ("dense", "moe", "vlm", "hybrid"):
        p["ln1"] = layers.init_rmsnorm(cfg.d_model)
        p["attn"] = layers.init_attention(ks[0], cfg)
        p["ln2"] = layers.init_rmsnorm(cfg.d_model)
        if cfg.is_moe:
            p["moe"] = moe_lib.init_moe(ks[1], cfg)
        else:
            p["mlp"] = layers.init_mlp(ks[1], cfg)
        if fam == "hybrid":
            p["ssm"] = ssm_lib.init_ssm(ks[2], cfg)
            p["ln_attn"] = layers.init_rmsnorm(cfg.d_model)
            p["ln_ssm"] = layers.init_rmsnorm(cfg.d_model)
    elif fam == "ssm":
        p["ln1"] = layers.init_rmsnorm(cfg.d_model)
        p["ssm"] = ssm_lib.init_ssm(ks[0], cfg)
    else:
        raise ValueError(fam)
    return p


def init_params(cfg, key) -> dict:
    kl, ke, kh, kf = jax.random.split(key, 4)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    # vmap draws the same values as one _init_block call per key, stacked,
    # without holding the per-layer copies beside the stack.
    stacked = jax.vmap(lambda k: _init_block(k, cfg))(layer_keys)
    params = {
        "blocks": stacked,
        "embed": jax.random.normal(ke, (cfg.padded_vocab, cfg.d_model), jnp.float32) * 0.02,
        "ln_f": layers.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "wd": jax.random.normal(kh, (cfg.d_model, cfg.padded_vocab), jnp.float32)
            * (1.0 / math.sqrt(cfg.d_model))
        }
    if cfg.frontend == "vision":
        params["frontend_proj"] = {
            "wd": jax.random.normal(kf, (cfg.frontend_dim, cfg.d_model), jnp.float32)
            * (1.0 / math.sqrt(cfg.frontend_dim))
        }
    return params


def global_flags(cfg) -> jnp.ndarray:
    return jnp.array([cfg.layer_is_global(i) for i in range(cfg.n_layers)], jnp.bool_)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _block(cfg, p, x, *, flag, pos, train, mode, cache=None, cache_len=None,
           slot=None):
    """One layer.  mode: 'fwd' | 'prefill' | 'decode'.

    Returns (x, aux_loss, new_cache_or_None).
    """
    fam = cfg.family
    aux = jnp.float32(0.0)
    new_cache: dict = {}

    if fam == "ssm":
        h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
        if mode == "decode":
            y, sc = ssm_lib.ssm_decode_step(cfg, p["ssm"], h, cache, train)
            new_cache.update(sc)
        else:
            y, final = ssm_lib.ssm_forward(cfg, p["ssm"], h, train)
            if mode == "prefill":
                new_cache.update(_ssm_prefill_cache(cfg, p["ssm"], h, final, train))
        return x + y, aux, (new_cache or None)

    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if mode == "decode":
        attn_out, ac = layers.attention(
            cfg, p["attn"], h, pos=pos, is_global=flag, cache=cache,
            cache_len=cache_len, slot=slot, train=train,
        )
        new_cache.update(ac)
    elif mode == "prefill":
        attn_out, (k, v) = layers.attention(
            cfg, p["attn"], h, pos=pos, is_global=flag, train=train, return_kv=True,
        )
        s_max = cache["k"].shape[1]
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        new_cache.update({"k": ck, "v": cv})
    else:
        attn_out, _ = layers.attention(
            cfg, p["attn"], h, pos=pos, is_global=flag, train=train,
        )

    if fam == "hybrid":
        if mode == "decode":
            ssm_out, sc = ssm_lib.ssm_decode_step(cfg, p["ssm"], h, cache, train)
            new_cache.update(sc)
        else:
            ssm_out, final = ssm_lib.ssm_forward(cfg, p["ssm"], h, train)
            if mode == "prefill":
                new_cache.update(_ssm_prefill_cache(cfg, p["ssm"], h, final, train))
        # Hymba: mean of per-branch normalized outputs.
        attn_out = 0.5 * (
            layers.rmsnorm(p["ln_attn"], attn_out, cfg.norm_eps)
            + layers.rmsnorm(p["ln_ssm"], ssm_out, cfg.norm_eps)
        )
    x = x + attn_out

    h2 = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.is_moe:
        y, aux = moe_lib.moe_forward(cfg, p["moe"], h2, train)
    else:
        y = layers.mlp(p["mlp"], h2, train)
    x = x + y
    return x, aux, (new_cache or None)


def _ssm_prefill_cache(cfg, p, h, final_state, train) -> dict:
    """Conv tail + final SSD state so decode can continue the recurrence."""
    # Recompute the pre-conv xBC tail (cheap: one projection on the last W-1
    # positions) to seed the rolling conv window.
    w = cfg.ssm_conv_width
    tail = h[:, -(w - 1):, :]
    z, xs, bs, cs, dt = ssm_lib._split_in(cfg, layers.linear(p["in_proj"], tail, train))
    conv = jnp.concatenate([xs, bs, cs], axis=-1)  # (B, W-1, conv_dim)
    return {"conv": conv, "state": final_state}


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, params, batch, train):
    """tokens (+ optional stub-frontend embeddings) -> x (B, S, D)."""
    tokens = batch["tokens"]
    x = params["embed"][tokens] * math.sqrt(cfg.d_model)
    if cfg.frontend == "vision" and "patches" in batch:
        proj = layers.linear(params["frontend_proj"], batch["patches"], train)
        x = jnp.concatenate([proj, x], axis=1)
    return x.astype(jnp.float32)


@jax.named_scope("head")
def _head(cfg, params, x):
    from repro.utils.act_sharding import constrain

    x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ constrain(params["embed"], "vocab_rows").T
    else:
        logits = x @ constrain(params["lm_head"]["wd"], "vocab_cols").astype(x.dtype)
    logits = constrain(logits, "logits")
    logits = layers.softcap(logits, cfg.logit_softcap)
    if cfg.padded_vocab != cfg.vocab_size:  # mask the padding columns
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, jnp.finfo(logits.dtype).min)
    return logits


def forward(cfg, params, batch, train: bool = True, remat: bool = False):
    """Teacher-forced logits (B, S_total, V); aux is the MoE balance loss.

    ``remat=True`` checkpoints each scanned block (activation rematerialization
    — the standard memory/compute trade for long-sequence training).
    """
    x = _embed_inputs(cfg, params, batch, train)
    s = x.shape[1]
    pos = jnp.arange(s)
    flags = global_flags(cfg)

    def body(carry, xs):
        xv, aux = carry
        p, flag = xs
        xv, a, _ = _block(cfg, p, xv, flag=flag, pos=pos, train=train, mode="fwd")
        return (xv, aux + a), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), (params["blocks"], flags))
    return _head(cfg, params, x), aux


def loss_fn(cfg, params, batch, train: bool = True, remat: bool = False):
    logits, aux = forward(cfg, params, batch, train, remat=remat)
    labels = batch["labels"]
    # VLM prepends patch positions; only score the token tail.
    logits = logits[:, -labels.shape[1]:, :]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return nll + 0.01 * aux, {"nll": nll, "aux": aux}


# ---- caches ----------------------------------------------------------------

def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.float32) -> dict:
    """Stacked per-layer decode caches (leading axis = layer)."""
    l = cfg.n_layers
    c: dict = {}
    if cfg.family in ("dense", "moe", "vlm", "hybrid", "encdec"):
        hk, dh = cfg.n_kv_heads, cfg.head_dim
        c["k"] = jnp.zeros((l, batch_size, max_len, hk, dh), dtype)
        c["v"] = jnp.zeros((l, batch_size, max_len, hk, dh), dtype)
    if cfg.family in ("ssm", "hybrid"):
        di, n, nh, conv_dim = ssm_lib._dims(cfg)
        c["conv"] = jnp.zeros((l, batch_size, cfg.ssm_conv_width - 1, conv_dim), dtype)
        c["state"] = jnp.zeros((l, batch_size, nh, cfg.ssm_head_dim, n), dtype)
    return c


def prefill(cfg, params, batch, cache: dict, train: bool = False):
    """Run the prompt, fill caches.  Returns (last-position logits, caches)."""
    x = _embed_inputs(cfg, params, batch, train)
    s = x.shape[1]
    pos = jnp.arange(s)
    flags = global_flags(cfg)

    def body(carry, xs):
        xv = carry
        p, flag, cache_l = xs
        xv, _, nc = _block(cfg, p, xv, flag=flag, pos=pos, train=train,
                           mode="prefill", cache=cache_l)
        return xv, nc

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], flags, cache))
    logits = _head(cfg, params, x[:, -1:, :])
    return logits, new_cache


def decode_step(cfg, params, tokens, cache: dict, t, train: bool = False):
    """One decode step.  tokens (B, 1) int32; t = current length (scalar).

    Returns (logits (B, 1, V), updated caches).
    """
    x = params["embed"][tokens] * math.sqrt(cfg.d_model)
    x = x.astype(jnp.float32)
    pos = jnp.asarray(t)[None]
    flags = global_flags(cfg)

    def body(carry, xs):
        xv = carry
        p, flag, cache_l = xs
        xv, _, nc = _block(cfg, p, xv, flag=flag, pos=pos, train=train,
                           mode="decode", cache=cache_l, cache_len=t)
        return xv, nc

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], flags, cache))
    return _head(cfg, params, x), new_cache


def flat_step(cfg, params, tokens, slot, pos, pools: dict, table, emit_row,
              train: bool = False):
    """Flat token-packed step for the paged serving engine (``flat`` policy),
    run against the block pools in place.

    tokens (T,) int32 — ONE ragged batch of real tokens from many slots
    packed along the sequence axis: several concurrent prefill chunks plus
    every decode token, budgeted purely in tokens (no per-slot padding
    rows);
    slot (T,) int32 — per-token cache slot; padding rows carry the sentinel
    ``B`` (== table rows) and are fully masked, their K/V written to
    scratch block 0;
    pos (T,) int32 — per-token absolute position (== its KV write offset);
    pools — ``model_zoo.init_paged_cache`` pools, K/V (L, NB, bs, Hkv*Dh);
    table (B, VB) int32 — each slot's first VB pool blocks (0-padded);
    emit_row (B,) int32 — for each slot, the flat row whose logits it
    samples (its last real token this step; engine masks non-emitting
    slots).

    Returns (logits (B, V) gathered at ``emit_row``, updated pools).  The
    head runs on B rows, not T — emit-row selection happens before the
    vocab matmul, so a wide prefill step never pays a (T, V) head.

    The pools ride the layer scan as carry: each layer writes its T new K/V
    rows into its pool rows in place, then reads its (B, VB*bs) view through
    the table (``layers.attention``).  No view leaves a layer and nothing is
    written back, so a step moves T rows plus one read per layer, not the
    whole view.

    Like ``chunk_step``, a slot's rows may start at a nonzero position
    against pre-populated blocks (prefix-cache fork); attention masks by
    absolute position within the slot's segment.
    """
    assert cfg.family not in ("ssm", "hybrid"), \
        "SSM recurrence: flat layout needs KV-cache attention"
    x = params["embed"][tokens][None, :, :] * math.sqrt(cfg.d_model)
    x = x.astype(jnp.float32)
    flags = global_flags(cfg)
    # Each token's pool row, once for every layer: block table[slot, pos//bs]
    # at offset pos % bs.  Padding rows go explicitly to scratch block 0:
    # scatter modes clamp or drop out-of-range indices depending on the mode,
    # so the destination is never left to them.
    bs = pools["k"].shape[2]
    real = slot < table.shape[0]
    blk = jnp.where(real, table[jnp.where(real, slot, 0),
                                jnp.where(real, pos // bs, 0)], 0)
    off = jnp.where(real, pos % bs, 0)

    def body(carry, xs):
        xv, pk, pv = carry
        p, flag, layer = xs
        cache_l = {"k": pk, "v": pv, "layer": layer, "table": table,
                   "blk": blk, "off": off}
        xv, _, nc = _block(cfg, p, xv, flag=flag, pos=pos, train=train,
                           mode="decode", cache=cache_l, slot=slot)
        return (xv, nc["k"], nc["v"]), None

    (x, pk, pv), _ = jax.lax.scan(
        body, (x, pools["k"], pools["v"]),
        (params["blocks"], flags, jnp.arange(cfg.n_layers)))
    sel = x[0, emit_row]                       # (B, D) emitting rows only
    logits = _head(cfg, params, sel[None])     # (1, B, V)
    return logits[0], {**pools, "k": pk, "v": pv}


def chunk_step(cfg, params, tokens, pos, cache: dict, lengths, train: bool = False):
    """Chunked-append step for the paged serving engine.

    tokens (B, C) int32 — per-slot token rows: a prefill chunk, a single
    decode token, or padding (slots advance independently);
    pos (B, C) int32 — absolute positions of each token (padding clamped);
    lengths (B,) int32 — per-slot KV write offsets (current live length).

    Returns (logits (B, C, V), updated caches).  C == 1 reduces to a decode
    step with per-slot positions; C > 1 interleaves up to C prompt tokens of
    a prefilling slot with the other slots' single decode tokens.

    A slot's FIRST chunk may start at a nonzero offset (``lengths[i] > 0``
    with ``pos`` continuing from there) against a pre-populated cache — the
    prefix-cache hit path, where the leading positions were forked from
    another request's blocks: attention masks by absolute position
    (``kpos <= pos``), so the chunk attends over the pre-populated prefix
    exactly as if this slot had prefilled it (asserted in
    ``tests/test_prefix_cache.py::test_chunk_step_accepts_nonzero_start``).

    SSM/hybrid recurrences only support C == 1 (their prefill goes through
    ``prefill``; ``ssm_lib.ssm_forward`` now takes ``initial_state`` /
    ``initial_conv``, the building block for lifting this — engine wiring is
    an open ROADMAP item).
    """
    if cfg.family in ("ssm", "hybrid"):
        assert tokens.shape[1] == 1, "SSM recurrence: chunked path is C == 1 only"
    x = params["embed"][tokens] * math.sqrt(cfg.d_model)
    x = x.astype(jnp.float32)
    flags = global_flags(cfg)

    def body(carry, xs):
        xv = carry
        p, flag, cache_l = xs
        xv, _, nc = _block(cfg, p, xv, flag=flag, pos=pos, train=train,
                           mode="decode", cache=cache_l, cache_len=lengths)
        return xv, nc

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], flags, cache))
    return _head(cfg, params, x), new_cache
