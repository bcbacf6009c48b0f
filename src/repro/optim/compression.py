"""Int8 error-feedback gradient compression for the data-parallel all-reduce.

Distributed-optimization trick for the 1000+-node regime: the DP gradient
all-reduce (which crosses the slow 'pod' DCN axis in multi-pod meshes) is the
dominant cross-pod collective.  A psum of int32 would not save wire bytes, so
the all-reduce is decomposed explicitly:

    reduce-scatter phase:  all_to_all of int8 chunks   (1 byte/elem on wire)
    local reduction:       dequant + f32 sum
    all-gather phase:      bf16 re-broadcast           (2 bytes/elem on wire)

Total wire traffic ~= 3 bytes/elem vs 8 for a f32 ring all-reduce (2.7x), or
vs 4 for bf16 (1.3x) — with the int8 quantization error carried in a
per-shard error-feedback buffer (EF-SGD) so convergence is preserved.  The
buffer lives in the optimizer state, sharded like params.

Used inside a ``shard_map`` train step over the DP axis; see
repro.train.train_loop.make_compressed_dp_train_step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def compress_leaf(g: jax.Array, err: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(g + err) -> (int8 q, scale, new_err)."""
    target = g.astype(jnp.float32) + err
    scale = jnp.max(jnp.abs(target)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(target / scale), -127, 127).astype(jnp.int8)
    new_err = target - q.astype(jnp.float32) * scale
    return q, scale, new_err


def _allreduce_int8(q: jax.Array, scale: jax.Array, axis: str) -> jax.Array:
    """Mean over the axis via int8 reduce-scatter + bf16 all-gather.

    Returns the dequantized mean (f32), same shape as q.
    """
    n = jax.lax.axis_size(axis)   # a Python int: the reshapes stay static
    flat = q.reshape(-1)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)                                  # (N, C)
    # reduce-scatter phase: all_to_all moves int8 on the wire; afterwards this
    # device holds everyone's copy of its chunk: (N, C).
    recv = jax.lax.all_to_all(chunks, axis, split_axis=0, concat_axis=0, tiled=True)
    recv = recv.reshape(n, -1)
    scales = jax.lax.all_gather(scale, axis)                      # (N,) f32 scalars
    summed = jnp.sum(recv.astype(jnp.float32) * scales[:, None], axis=0) / n
    # all-gather phase in bf16.
    gathered = jax.lax.all_gather(summed.astype(jnp.bfloat16), axis, tiled=True)
    out = gathered.astype(jnp.float32)[: q.size]
    return out.reshape(q.shape)


def psum_compressed(grads, err_buf, axis: str) -> tuple[dict, dict]:
    """Compressed mean-all-reduce over the named DP axis (inside shard_map)."""
    flat_g, tree = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err_buf)
    out_g, out_e = [], []
    for g, e in zip(flat_g, flat_e):
        if g.size < 1024:  # tiny leaves: plain f32 psum, not worth compressing
            out_g.append(jax.lax.pmean(g, axis))
            out_e.append(e)
            continue
        q, scale, new_err = compress_leaf(g, e)
        g_hat = _allreduce_int8(q, scale, axis)
        out_g.append(g_hat.astype(g.dtype))
        out_e.append(new_err)
    return jax.tree.unflatten(tree, out_g), jax.tree.unflatten(tree, out_e)


def init_error_buffer(params):
    return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
