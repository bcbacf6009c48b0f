"""Shared transformer building blocks (pure functional JAX).

Conventions:
* Params are plain dict pytrees; ``init_*`` builds them, ``*_forward`` applies.
* Every projection goes through :func:`linear`, which dispatches on the param
  dict: ``{'w'}`` = ternary BitLinear latent weights (QAT fake-quant forward),
  ``{'sign','zero','scale'}`` = frozen packed T-SAR weights (2-bit HBM
  residency — the inference path), ``{'wd'}`` = plain dense fp (embeddings,
  router, frontends, and all weights when cfg.ternary=False).
* Attention supports GQA, RoPE, sliding-window vs global masking (blended by
  a per-layer flag so heterogeneous stacks can be lax.scan'ed), qk-norm,
  attention/logit softcaps, cross-attention, and single-token decode against
  a KV cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitlinear, lut, ternary
from repro.utils.act_sharding import constrain


# ---------------------------------------------------------------------------
# Linear dispatch
# ---------------------------------------------------------------------------

def init_linear(key, k: int, m: int, ternary_layer: bool = True, dtype=jnp.float32) -> dict:
    if ternary_layer:
        return bitlinear.init(key, k, m, dtype)
    w = jax.random.normal(key, (k, m), dtype) * (1.0 / jnp.sqrt(k))
    return {"wd": w}


def linear(p: dict, x: jax.Array, train: bool = True) -> jax.Array:
    if "wd" in p:
        return x @ p["wd"].astype(x.dtype)
    if "w" in p:  # BitLinear latent weights
        if train:
            return bitlinear.apply_train(p, x)
        t, scale = ternary.absmean_ternarize(p["w"])
        return (lut.bitlinear_matmul_exact_int(x, t, scale)).astype(x.dtype)
    if "sign" in p:  # frozen packed planes: decode-in-fast-memory path
        return _packed_linear(p, x).astype(x.dtype)
    raise ValueError(f"unrecognized linear params: {list(p)}")


@jax.named_scope("bitlinear")
def _packed_linear(p: dict, x: jax.Array) -> jax.Array:
    """Inference forward from 2-bit planes, dispatched through the active
    execution plan (``repro.plan.runtime``).

    When a ``ModelPlan`` is active (the serving engine activates its plan
    around every jitted step) the planned kernel for this layer's (k, m) at
    the step's token count decides the realization — a trace-time constant
    table lookup, never a ``select_kernel`` call.

    On every backend, TPU included, a planned ``tsar_mxu`` or ``tsar_lut``
    runs the jnp spelling below: unpack the planes to int8, then one
    int8 x int8 -> int32 dot.  No Pallas kernel is called (their
    ``serve_via_registry`` is False), so with planes-only params the
    served step's HLO holds no ``tpu_custom_call``; the Pallas kernels (``repro.kernels``) compute the
    same integers and run when called directly.  Whether the step should
    call them is for a chip trace to decide.  A planned
    ``tsar_sparse_padded`` runs the registry lowering over the layer's
    ``sp_*`` padded-pool leaves: the 2-D zero-skip Pallas kernel on a TPU,
    elsewhere a decode from the pool, bit-identical to the planes.  The
    baselines genuinely switch: planned ``dense`` runs the
    dequantized fp matmul and planned ``memory_lut`` the DRAM-LUT gather,
    so A/B plans measure what their label says.  A planned ``tsar_sparse``
    (compacted — unserveable from a params tree, its pool size is
    data-dependent) degrades to the padded lowering when the leaves are
    present, else to the planes spelling — same math either way.

    The only weight bytes stored are the two uint8 bitplanes (+
    per-channel scales), 8x fewer than bf16; whether XLA keeps the decoded
    int8 weights out of HBM is not measured yet.  The spelling is
    SPMD-shardable.
    """
    from repro.plan import runtime as plan_runtime

    k = x.shape[-1]
    m = p["scale"].shape[-1]
    n = 1
    for d in x.shape[:-1]:   # static at trace time
        n *= d
    lp = plan_runtime.planned(k, m, n)
    if lp is not None:
        from repro.plan import registry

        kern = lp.kernel
        if kern in registry.SPARSE_KERNELS:
            # Compacted pools can't ride a params tree (data-dependent
            # size): remap within the sparse family to whatever format the
            # leaves actually carry, else fall through to the planes
            # spelling (same math).
            kern = next((kn for kn in registry.SPARSE_KERNELS
                         if registry.get(kn).supports(p)), kern)
        impl = registry.get(kern)
        # serve_via_registry is each impl's own declaration that its
        # lowering differs from the planes spelling below (see the
        # KernelImpl protocol) — the registry stays the source of truth.
        if getattr(impl, "serve_via_registry", False) and impl.supports(p):
            return impl.lower(p, x, lp=lp)
    sign = _unpack_plane_nd(p["sign"], k)   # int8 {0,1}
    zero = _unpack_plane_nd(p["zero"], k)
    t = ((1 - 2 * sign) * (1 - zero)).astype(jnp.int8)
    a_q, a_scale = ternary.quantize_activations(x.astype(jnp.float32))
    acc = jax.lax.dot_general(
        a_q, t,
        dimension_numbers=(((a_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * a_scale * p["scale"]


def _unpack_plane_nd(plane: jax.Array, k: int) -> jax.Array:
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape((1, 8) + (1,) * (plane.ndim - 1))
    bits = (plane[:, None] >> shifts) & jnp.uint8(1)
    kp = plane.shape[0] * 8   # ragged K: planes carry zero-padded tail bits
    return bits.reshape((kp,) + plane.shape[1:])[:k].astype(jnp.int8)


def pack_linear(p: dict, lp=None, *, name: str | None = None,
                sparse: bool = False, block_shape: tuple | None = None,
                max_live: int | None = None,
                s_steps: int | None = None) -> dict:
    """Freeze one linear layer's latent weights to 2-bit planes (+ scale).

    Also stamps the measured nonzero-weight ``density`` — a scalar leaf that
    rides the params tree (vmap-stacked for scan layers / experts) so the
    density profiler (``sparse.stats.profile_params``, surfaced as the
    serving engine's init telemetry) reads the freeze-time measurement
    instead of re-deriving it from the planes.  The forward path
    (:func:`_packed_linear`) ignores it.

    ``lp`` directs the packing: a ``repro.plan.LayerPlan`` / kernel name, or
    a whole ``repro.plan.ModelPlan`` (resolved through ``name``).  A layer
    the plan pins to ``dense`` at every bucket keeps fp weights (``{'wd'}``)
    instead of 2-bit planes, so the dense escape hatch costs no decode at
    serve time.  All T-SAR kernels share the plane packing, so any other
    plan packs identically.

    ``sparse=True`` additionally emits the PADDED block-sparse pool
    (``repro.sparse.format.pad_from_ternary``) as ``sp_*`` leaves plus a
    measured ``block_density`` leaf.  The construction is pure ``jnp`` and
    the leaf shapes are static (``max_live``/``s_steps`` bound the pool, the
    full block grid by default), so this works under ``vmap`` — which is how
    ``serving.freeze_params`` stacks per-scan-layer pools that ride a
    ``lax.scan`` through the jitted serving step.  The serve-path dispatch
    (:func:`_packed_linear`) runs the ``tsar_sparse_padded`` lowering from
    these leaves when the active plan says so.
    """
    if "w" not in p:
        return p
    if hasattr(lp, "layers"):        # ModelPlan: dense only if EVERY bucket is
        by_bucket = lp.layers.get(name, {}) if name else {}
        kerns = {e.kernel for e in by_bucket.values()}
        kern = "dense" if kerns == {"dense"} else None
    else:
        kern = getattr(lp, "kernel", lp)
    t, scale = ternary.absmean_ternarize(p["w"])
    if kern == "dense":
        return {"wd": (t * scale[..., None, :]).astype(p["w"].dtype)}
    tw = ternary.pack(t, scale)
    out = {"sign": tw.sign_plane, "zero": tw.zero_plane, "scale": tw.scale,
           "density": ternary.ternary_density(t)}
    if sparse:
        from repro.sparse import format as sparse_format

        bk, bm = block_shape or sparse_format.DEFAULT_BLOCK_SHAPE
        pbst = sparse_format.pad_from_ternary(
            t.astype(jnp.int8), scale, bk=bk, bm=bm,
            max_live=max_live, s_steps=s_steps)
        out.update({
            "sp_sign": pbst.sign_pool, "sp_zero": pbst.zero_pool,
            "sp_map": pbst.block_map, "sp_kids": pbst.kids,
            "sp_slots": pbst.slots, "sp_counts": pbst.counts,
            "block_density": jnp.mean((pbst.occupancy > 0.0)
                                      .astype(jnp.float32)),
        })
    return out


# ---------------------------------------------------------------------------
# Norms / activations / RoPE
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int) -> dict:
    return {"g": jnp.zeros((d,), jnp.float32)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["g"])).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x (..., S, H, Dh), pos (..., S) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = pos[..., None].astype(jnp.float32) * freqs           # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                           # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

# Query-block size for the scanned long-sequence attention path; bounds the
# transient (Sq, T) score tile at B*H*Q_CHUNK*T elements per layer.
Q_CHUNK = 1024


def init_attention(key, cfg, cross: bool = False) -> dict:
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    tern = cfg.ternary
    p = {
        "wq": init_linear(ks[0], d, h * dh, tern),
        "wk": init_linear(ks[1], d, hk * dh, tern),
        "wv": init_linear(ks[2], d, hk * dh, tern),
        "wo": init_linear(ks[3], h * dh, d, tern),
    }
    if cfg.qk_norm and not cross:
        p["qn"] = init_rmsnorm(dh)
        p["kn"] = init_rmsnorm(dh)
    return p


def _split_heads(x, n_heads, dh):
    return x.reshape(x.shape[:-1] + (n_heads, dh))


@jax.named_scope("attention")
def attention(
    cfg,
    p: dict,
    x: jax.Array,                    # (B, S, D) queries' residual stream
    *,
    pos: jax.Array,                  # (S,) absolute positions of the queries
    is_global,                       # bool / 0-1 scalar; blends window mask
    kv_x: jax.Array | None = None,   # cross-attention source (B, T, D)
    causal: bool = True,
    cache: dict | None = None,       # {'k','v'} (B, S_max, Hkv, Dh) decode
                                     # cache; flat: the pools (see below)
    cache_len: jax.Array | None = None,  # valid prefix length (== pos of new tok)
    slot: jax.Array | None = None,   # (T,) per-token slot index (flat layout)
    train: bool = True,
    return_kv: bool = False,
) -> tuple[jax.Array, dict | None]:
    """Returns (out (B,S,D), updated cache / (k, v) / None)."""
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hk
    b, s, _ = x.shape

    q = _split_heads(linear(p["wq"], x, train), h, dh)       # (B,S,H,Dh)
    if kv_x is None:
        k = _split_heads(linear(p["wk"], x, train), hk, dh)  # (B,S,Hk,Dh)
        v = _split_heads(linear(p["wv"], x, train), hk, dh)
    else:
        k = _split_heads(linear(p["wk"], kv_x, train), hk, dh)
        v = _split_heads(linear(p["wv"], kv_x, train), hk, dh)

    if "qn" in p:
        q = rmsnorm(p["qn"], q, cfg.norm_eps)
        k = rmsnorm(p["kn"], k, cfg.norm_eps)

    use_rope = kv_x is None  # no RoPE on cross-attention
    if use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)  # new token(s) at absolute pos in decode
    # Pin head-sharded layouts: without this XLA's propagation is free to
    # replicate batch / split heads unevenly (observed 50 GB score temps).
    q = constrain(q, "attn_q")
    k = constrain(k, "attn_kv")
    v = constrain(v, "attn_kv")

    new_cache = None
    if cache is not None and slot is not None:
        # Flat token-packed step over the block pools (paged serving engine,
        # ``flat`` policy): x is (1, T, D) — a ragged batch of T tokens from
        # many slots packed along the sequence axis; ``slot``/``pos`` are
        # (T,) per-token coordinates, padding rows carry the slot sentinel
        # B.  ``cache`` holds the lane-dense pools (L, NB, bs, Hkv*Dh), this
        # layer's index, the (B, VB) block table and each token's pool row
        # (``blk``, ``off``).  The T new K/V rows are written in place
        # first, so a token sees itself and its chunk's earlier rows; then
        # this layer's (B, VB*bs) view is read through the table.
        layer, table = cache["layer"], cache["table"]
        nb, vb = table.shape
        bs = cache["k"].shape[2]
        with jax.named_scope("kv_scatter"):
            at = (layer, cache["blk"], cache["off"])
            pk = cache["k"].at[at].set(
                k[0].reshape(s, hk * dh).astype(cache["k"].dtype))
            pv = cache["v"].at[at].set(
                v[0].reshape(s, hk * dh).astype(cache["v"].dtype))
        new_cache = {"k": pk, "v": pv}
        # Keys/values: every slot's view flattened to one (B*VB*bs,) key
        # axis, slot-major; the segment mask keeps cross-slot rows
        # invisible.
        vtok = vb * bs
        t = nb * vtok
        with jax.named_scope("kv_gather"):
            k = pk[layer, table].reshape((1, t, hk, dh))
            v = pv[layer, table].reshape((1, t, hk, dh))
        kidx = jnp.arange(t)
        kslot = kidx // vtok
        kpos = kidx % vtok
        valid = (kslot[None, :] == slot[:, None]) \
            & (kpos[None, :] <= pos[:, None])               # (T, B*Vtok)
        if cfg.window_pattern:
            in_win = kpos[None, :] > (pos[:, None] - cfg.window_size)
            valid = valid & (jnp.asarray(is_global, bool) | in_win)
        # Padding queries (slot == B) match no key: their softmax row is a
        # uniform distribution over masked scores — finite garbage, never
        # emitted (same contract as rectangular padding rows).
        mask = valid[None, None, None, :, :]                # (1,1,1,T,B*Vtok)
    elif cache is not None and jnp.ndim(cache_len) == 0:
        # Legacy synchronous decode: write new K/V at position cache_len
        # (shared by the whole batch), attend over the prefix.
        start = cache_len
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, start, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, start, 0, 0))
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        t = k.shape[1]
        kpos = jnp.arange(t)
        valid = kpos <= cache_len                           # causal over prefix+new
        if cfg.window_pattern:
            in_win = kpos > (cache_len - cfg.window_size)
            valid = valid & (jnp.asarray(is_global, bool) | in_win)
        mask = valid[None, None, None, None, :]             # (1,1,1,S=1,T)
    elif cache is not None:
        # Chunked-append decode (paged serving engine): ``cache_len`` is a
        # (B,) vector of per-slot write offsets and ``pos`` carries per-slot
        # absolute query positions (B, S).  Each slot's S new K/V rows are
        # written contiguously at its own offset; the mask is causal in
        # absolute position, so cache rows beyond a slot's live length
        # (scratch garbage / this chunk's padding tail) are never attended.
        upd = jax.vmap(
            lambda c, u, s0: jax.lax.dynamic_update_slice(c, u, (s0, 0, 0)))
        ck = upd(cache["k"], k.astype(cache["k"].dtype), cache_len)
        cv = upd(cache["v"], v.astype(cache["v"].dtype), cache_len)
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        t = k.shape[1]
        kpos = jnp.arange(t)
        qabs = pos if pos.ndim == 2 else jnp.broadcast_to(pos[None, :], (b, s))
        valid = kpos[None, None, :] <= qabs[:, :, None]     # (B, S, T)
        if cfg.window_pattern:
            in_win = kpos[None, None, :] > (qabs[:, :, None] - cfg.window_size)
            valid = valid & (jnp.asarray(is_global, bool) | in_win)
        mask = valid[:, None, None, :, :]                   # (B,1,1,S,T)
    else:
        t = k.shape[1]
        if causal and kv_x is None:
            qpos = pos[:, None]
            kpos = pos[None, :]
            m = kpos <= qpos
            if cfg.window_pattern:
                in_win = kpos > (qpos - cfg.window_size)
                m = m & (jnp.asarray(is_global, bool) | in_win)
            mask = m[None, None, None, :, :]
        else:
            mask = None

    qg = q.reshape(b, s, hk, g, dh)

    def attend(qc, maskc):
        """One query block against the full K/V.  qc (B,Sq,Hk,G,Dh).

        The query block is re-constrained INSIDE the scan body: the scanned
        chunk axis cannot be sharded (scan iterates it), so without this the
        whole attention replicates across 'model' whenever heads < |model|
        (measured 16x wasted compute on whisper/gemma prefill — §Perf iter 2).
        """
        sq = qc.shape[1]
        qc = constrain(qc.reshape(b, sq, hk * g, dh), "attn_q").reshape(qc.shape)
        scores = jnp.einsum("bshgd,bthd->bhgst", qc, k.astype(qc.dtype)) / jnp.sqrt(
            jnp.float32(dh)).astype(x.dtype)
        scores = softcap(scores, cfg.attn_softcap)
        if maskc is not None:
            scores = jnp.where(maskc, scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
        ctxc = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(probs.dtype))
        return constrain(ctxc.reshape(b, sq, hk * g, dh), "attn_q").reshape(ctxc.shape)

    # Long sequences: scan over query blocks so the (Sq, T) score tile is
    # bounded (flash-attention-style working set; exact math since each query
    # block sees its full key row).  Peak scores memory: B*H*Q_CHUNK*T.
    # The mask's leading dim is 1 (shared causal mask) or B (per-slot chunked
    # decode mask); both chunk along the query axis the same way.
    if s > Q_CHUNK and s % Q_CHUNK == 0 and mask is not None:
        nq = s // Q_CHUNK
        qb = qg.reshape(b, nq, Q_CHUNK, hk, g, dh)
        mb = mask.reshape(mask.shape[0], 1, 1, nq, Q_CHUNK, t)

        # Per-chunk remat: without it the scan saves every chunk's (QC, T)
        # score tile for backward, reconstituting the full S x T matrix.
        @jax.checkpoint
        def body(_, inp):
            qc, mc = inp
            return None, attend(qc, mc)

        # mask chunk (B|1,1,1,Q_CHUNK,T): moveaxis the nq dim to scan over.
        qb_s = jnp.moveaxis(qb, 1, 0)                    # (nq, B, QC, Hk, G, Dh)
        mb_s = jnp.moveaxis(mb, 3, 0)                    # (nq, B|1, 1, 1, QC, T)
        _, ctxs = jax.lax.scan(body, None, (qb_s, mb_s))
        ctx = jnp.moveaxis(ctxs, 0, 1).reshape(b, s, hk, g, dh)
    else:
        ctx = attend(qg, mask)
    ctx = constrain(ctx.reshape(b, s, hk * g, dh), "attn_q").reshape(b, s, hk, g, dh)
    out = linear(p["wo"], ctx.reshape(b, s, h * dh), train)
    if return_kv:
        return out, (k, v)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    tern = cfg.ternary
    if cfg.mlp_gated:
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w_gate": init_linear(k1, d, f, tern),
            "w_up": init_linear(k2, d, f, tern),
            "w_down": init_linear(k3, f, d, tern),
        }
    k1, k2 = jax.random.split(key, 2)
    return {"w_up": init_linear(k1, d, f, tern), "w_down": init_linear(k2, f, d, tern)}


def mlp(p: dict, x: jax.Array, train: bool = True) -> jax.Array:
    if "w_gate" in p:
        return linear(p["w_down"], silu(linear(p["w_gate"], x, train)) * linear(p["w_up"], x, train), train)
    return linear(p["w_down"], jax.nn.gelu(linear(p["w_up"], x, train)), train)
