"""The dense decoder this repository serves: BitNet b1.58 and, through
``llama.py``, llama-type models such as deepseek-coder.

An architecture file holds everything the harness needs to know of one
``model_type`` (``spec.arch``): the program's ``ModelConfig`` for a
configuration file, the plain reference and its weight rule, and the
operations and bytes of a serving step, whole and by part.

The plain reference computes, in float32 (or in a lower precision, for the
control), the layer equations of this repository's dense model: pre-norm
blocks, RoPE, grouped-query causal attention, a gated-SiLU MLP, every
projection but the embedding and the head a BitLinear (absmean-ternarised
weights, per-token absmax int8 activations).  It imports nothing of the
program.  Where these depart from a published model, the configuration
file's ``departures`` say so.

Weight rule, from ``PRNGKey(seed & 0xffffffff)`` folded with ``seed >> 32``:
split into (layers, embed, head, -); layer i's key is the i-th of
``split(layers, n_layers)``; it splits 8 ways: the first key splits 4 ways
into q, k, v, o, the second 3 ways into gate, up, down.  A BitLinear's latent
weight is ``normal(key, (k, m)) / sqrt(k)``; the embedding is
``normal(key, (Vp, d)) * 0.02`` and an untied head ``normal(key, (d, Vp)) /
sqrt(d)``, with Vp the vocabulary rounded up to a multiple of 2048 (rows
past the vocabulary are drawn but never read).  Norm gains start at zero.

It runs layer by layer, drawing each layer's weights inside the jitted
layer, over a batch of whole sequences, with attention in query blocks, so
that it fits beside nothing else on one chip.

Precision: ``low=None`` is the reference, float32 arrays and every matrix
product at the highest precision.  The controls put it in the program's
place one precision lower: ``"bfloat16"`` keeps every array and product in
bfloat16; ``"float8"`` keeps float32 arrays but rounds the inputs of every
float matrix product (attention and the head) to float8 (e4m3), as the
step below the TPU's default one-pass bfloat16 products.  BitLinear
products stay exact in both (int8 activations times ternary weights).

Counts (``counts.py`` gives the conventions), per step:

* ``bitlinear``: every BitLinear's 2 K M operations per real token; its
  2-bit planes and 2-byte channel scales;
* ``attention``: for a token at position p, 2 H Dh (p + 1) for QK^T and as
  much for PV, in each layer; each slot's keys and values read and the new
  ones written, at 2 bytes;
* ``head``: 2 D V operations per emitted row; the head's weight at 2 bytes.

The parts' operations sum to ``step_counts``' exactly; the step's bytes
add the norms and the embedding rows.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import counts

# The device scopes the step is read under: each part that ``step_parts``
# counts, with the peak its operations are held to ...
PARTS = {"bitlinear": "int8_ops", "attention": "bf16_flops",
         "head": "bf16_flops"}
# ... and the KV pool's per-layer read and write, timed but not counted
# apart (their bytes are attention's).
POOL = ("kv_gather", "kv_scatter")
Q_BLOCK = 512

# Published config.json keys -> the program's ModelConfig fields.
_ARCH_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    if config["hidden_act"] != "silu":
        raise ValueError(f"{config['name']}: the program's dense MLP is "
                         f"gated SiLU, not {config['hidden_act']!r}")
    kw = {dst: config[src] for src, dst in _ARCH_KEYS.items()}
    return ModelConfig(name=config["name"], family="dense", mlp_gated=True,
                       ternary=True, **kw)


def weight_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def padded_vocab(v: int) -> int:
    mult = 2048 if v > 2048 else 16
    return -(-v // mult) * mult


def _shapes(c: dict) -> dict:
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    return {"d": d, "h": h, "kv": kv, "dh": c.get("head_dim") or d // h,
            "ff": c["intermediate_size"], "layers": c["num_hidden_layers"],
            "vocab": c["vocab_size"], "vp": padded_vocab(c["vocab_size"]),
            "eps": c["rms_norm_eps"], "theta": c["rope_theta"],
            "tied": c["tie_word_embeddings"]}


def _mm_in(x, low):
    """A float matrix product's input as the control rounds it."""
    if low == "float8":
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    return x


def _latent(key, k: int, m: int):
    return jax.random.normal(key, (k, m), jnp.float32) * (1.0 / jnp.sqrt(k))


def _ternarize(w):
    gamma = jnp.mean(jnp.abs(w)) + 1e-6
    t = jnp.clip(jnp.round(w / gamma), -1, 1)
    scale = jnp.sum(w * t, axis=0) / (jnp.sum(t * t, axis=0) + 1e-6)
    return t, scale


def _layer_weights(s: dict, key) -> dict:
    ks = jax.random.split(key, 8)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 3)
    d, h, kv, dh, ff = s["d"], s["h"], s["kv"], s["dh"], s["ff"]
    shapes = {"q": (ka[0], d, h * dh), "k": (ka[1], d, kv * dh),
              "v": (ka[2], d, kv * dh), "o": (ka[3], h * dh, d),
              "gate": (km[0], d, ff), "up": (km[1], d, ff),
              "down": (km[2], ff, d)}
    return {name: _ternarize(_latent(k_, a, b))
            for name, (k_, a, b) in shapes.items()}


def _rmsnorm(x, eps, dt):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt)


def _bitlinear(x, tw, dt):
    t, scale = tw
    a_scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-6
    q = jnp.clip(jnp.round(x / a_scale), -127, 127)
    acc = jnp.matmul(q.astype(dt), t.astype(dt), preferred_element_type=dt)
    return acc * a_scale.astype(dt) * scale.astype(dt)


def _rope(x, pos, theta, dt):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos = jnp.cos(ang)[None, :, None, :].astype(dt)
    sin = jnp.sin(ang)[None, :, None, :].astype(dt)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(s, q, k, v, dt, low):
    """Causal grouped-query attention, one block of queries at a time."""
    b, n, _, dh = q.shape
    g = s["h"] // s["kv"]
    qg = q.reshape(b, n, s["kv"], g, dh)
    kpos = jnp.arange(n)
    k, v = _mm_in(k, low), _mm_in(v, low)
    out = []
    for q0 in range(0, n, Q_BLOCK):
        qb = _mm_in(qg[:, q0:q0 + Q_BLOCK], low)
        sc = jnp.einsum("bshgd,bthd->bhgst", qb, k,
                        preferred_element_type=dt) / jnp.sqrt(
                            jnp.float32(dh)).astype(dt)
        qpos = q0 + jnp.arange(qb.shape[1])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc,
                       jnp.finfo(dt).min)
        p = _mm_in(jax.nn.softmax(sc, axis=-1), low)
        out.append(jnp.einsum("bhgst,bthd->bshgd", p, v,
                              preferred_element_type=dt))
    return jnp.concatenate(out, axis=1).reshape(b, n, s["h"] * dh)


@partial(jax.jit, static_argnums=(0, 3, 4))
def _layer(s_items, key, x, dt, low):
    s = dict(s_items)
    w = _layer_weights(s, key)
    b, n, _ = x.shape
    pos = jnp.arange(n)
    h = _rmsnorm(x, s["eps"], dt)
    q = _bitlinear(h, w["q"], dt).reshape(b, n, s["h"], s["dh"])
    k = _bitlinear(h, w["k"], dt).reshape(b, n, s["kv"], s["dh"])
    v = _bitlinear(h, w["v"], dt).reshape(b, n, s["kv"], s["dh"])
    q = _rope(q, pos, s["theta"], dt)
    k = _rope(k, pos, s["theta"], dt)
    x = x + _bitlinear(_attention(s, q, k, v, dt, low), w["o"], dt)
    h = _rmsnorm(x, s["eps"], dt)
    gate = _bitlinear(h, w["gate"], dt)
    y = _bitlinear((gate * jax.nn.sigmoid(gate)) * _bitlinear(h, w["up"], dt),
                   w["down"], dt)
    return x + y


@partial(jax.jit, static_argnums=(0, 3))
def _embed(s_items, key, tokens, dt):
    s = dict(s_items)
    table = jax.random.normal(key, (s["vp"], s["d"]), jnp.float32) * 0.02
    return (table[tokens] * math.sqrt(s["d"])).astype(dt)


@partial(jax.jit, static_argnums=(0, 4, 5))
def _head(s_items, kemb, khead, x, dt, low):
    """Logits over the vocabulary, float32, for rows ``x`` (N, d)."""
    s = dict(s_items)
    if s["tied"]:
        w = (jax.random.normal(kemb, (s["vp"], s["d"]), jnp.float32)
             * 0.02).T
    else:
        w = jax.random.normal(khead, (s["d"], s["vp"]), jnp.float32) * (
            1.0 / jnp.sqrt(s["d"]))
    h = _mm_in(_rmsnorm(x.astype(dt), s["eps"], dt), low)
    logits = jnp.matmul(h, _mm_in(w[:, :s["vocab"]].astype(dt), low),
                        preferred_element_type=dt)
    return logits.astype(jnp.float32)


def logits_at(config: dict, seed: int, seqs: list[np.ndarray],
              rows: list[np.ndarray], pad_to: int | None = None,
              low: str | None = None) -> jnp.ndarray:
    """The model's logits for each sequence in ``seqs`` at the positions in
    the matching entry of ``rows``, stacked in that order: (sum of rows, V).

    ``low`` names a control's precision (module docstring); None is the
    reference.  ``pad_to`` fixes the padded sequence length, so that every
    run of a cell reuses one compiled layer.
    """
    if low not in (None, "bfloat16", "float8"):
        raise ValueError(f"unknown control precision {low!r}")
    dtype = jnp.bfloat16 if low == "bfloat16" else jnp.float32
    s = _shapes(config)
    items = tuple(sorted(s.items()))
    n = pad_to or max(len(q) for q in seqs)
    tokens = np.zeros((len(seqs), n), np.int32)
    for i, q in enumerate(seqs):
        tokens[i, :len(q)] = q
    kl, ke, kh, _ = jax.random.split(weight_key(seed), 4)
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        x = _embed(items, ke, jnp.asarray(tokens), dtype)
        for lk in jax.random.split(kl, s["layers"]):
            x = _layer(items, lk, x, dtype, low)
        sel = jnp.concatenate([x[i, jnp.asarray(r)] for i, r in
                               enumerate(rows)], axis=0)
        return _head(items, ke, kh, sel, dtype, low)


# -- operations and bytes ---------------------------------------------------

def step_parts(config: dict, slots, emit: int) -> dict:
    """{part: (operations, bytes)} of one step; ``slots`` and ``emit`` as
    ``counts.py`` describes them."""
    s = _shapes(config)
    d, h, kv, dh, ff = s["d"], s["h"], s["kv"], s["dh"], s["ff"]
    linears = [(d, h * dh), (d, kv * dh), (d, kv * dh), (h * dh, d),
               (d, ff), (d, ff), (ff, d)]
    tokens = sum(n for n, _ in slots)
    weights = s["layers"] * sum(k * m for k, m in linears)
    planes = s["layers"] * sum(k * m / 4 + counts.BF16 * m
                               for k, m in linears)
    context = sum(counts.context_sum(n, e) for n, e in slots)
    kv_token = s["layers"] * 2 * kv * dh * counts.BF16
    return {
        "bitlinear": (float(2 * tokens * weights), float(planes)),
        "attention": (float(4 * s["layers"] * h * dh * context),
                      float(kv_token * sum(e for _, e in slots))),
        "head": (float(2 * emit * d * s["vocab"]),
                 float(d * s["vocab"] * counts.BF16)),
    }


def step_counts(config: dict, slots, emit: int) -> tuple[float, float]:
    """(operations, bytes) of one step: its parts, and the bytes of the
    block norms, the final norm and the embedding rows."""
    s = _shapes(config)
    parts = step_parts(config, slots, emit).values()
    norms = (2 * s["layers"] + 1) * s["d"] * counts.BF16
    embed = sum(n for n, _ in slots) * s["d"] * counts.BF16
    return (sum(o for o, _ in parts),
            sum(b for _, b in parts) + norms + embed)
