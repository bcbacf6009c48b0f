"""Training step construction: pjit'd 2-D/3-D-sharded steps, gradient
accumulation, remat, and the compressed-DP shard_map variant.

``TrainState`` is a plain pytree (params, opt state, step) so checkpointing
and resharding treat it uniformly.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import model_zoo
from repro.optim import OptConfig, adamw_init, adamw_update, compression
from repro.train import sharding


class TrainState(NamedTuple):
    params: Any
    opt: Any
    step: jax.Array
    err_buf: Any = None      # int8-compression error feedback (optional)


def init_state(cfg, key, opt_cfg: OptConfig, compressed: bool = False) -> TrainState:
    params = model_zoo.init_params(cfg, key)
    return TrainState(
        params=params,
        opt=adamw_init(params, jnp.dtype(opt_cfg.moment_dtype)),
        step=jnp.zeros((), jnp.int32),
        err_buf=compression.init_error_buffer(params) if compressed else None,
    )


def make_train_step(cfg, opt_cfg: OptConfig, *, remat: bool = False,
                    accum_steps: int = 1):
    """Plain SPMD train step (pjit handles all collectives).

    With ``accum_steps > 1`` the batch's leading dim is split into
    microbatches scanned sequentially with gradient accumulation — the
    standard trick to hit large global batches within HBM limits.
    """

    def loss(params, batch):
        l, metrics = model_zoo.loss_fn(cfg, params, batch, train=True, remat=remat)
        return l, metrics

    def train_step(state: TrainState, batch):
        if accum_steps == 1:
            (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(state.params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:]),
                batch,
            )

            def micro_step(acc, mb):
                (l, metrics), g = jax.value_and_grad(loss, has_aux=True)(state.params, mb)
                acc = jax.tree.map(jnp.add, acc, g)
                return acc, (l, metrics)

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            grads, (ls, ms) = jax.lax.scan(micro_step, zeros, micro)
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            l, metrics = jnp.mean(ls), jax.tree.map(jnp.mean, ms)

        new_params, new_opt, opt_metrics = adamw_update(opt_cfg, grads, state.opt, state.params)
        metrics = dict(metrics, **opt_metrics, loss=l)
        return TrainState(new_params, new_opt, state.step + 1, state.err_buf), metrics

    return train_step


def jit_train_step(cfg, opt_cfg, mesh, state, batch_example, *, fsdp: bool = False, **kw):
    """Build + jit the step with explicit in/out shardings on ``mesh``."""
    step_fn = make_train_step(cfg, opt_cfg, **kw)
    pspecs = sharding.param_specs(state.params, mesh, fsdp=fsdp)
    state_specs = TrainState(
        params=pspecs,
        opt=type(state.opt)(mu=pspecs, nu=pspecs, count=P()),
        step=P(),
        err_buf=pspecs if state.err_buf is not None else None,
    )
    bspecs = sharding.batch_specs(mesh, batch_example)
    return jax.jit(
        step_fn,
        in_shardings=(sharding.to_named(mesh, state_specs),
                      sharding.to_named(mesh, bspecs)),
        out_shardings=(sharding.to_named(mesh, state_specs), None),
        donate_argnums=(0,),
    )


# ---------------------------------------------------------------------------
# Compressed-DP variant (shard_map over the data axis)
# ---------------------------------------------------------------------------

def make_compressed_dp_train_step(cfg, opt_cfg: OptConfig, mesh, *, remat: bool = False):
    """Pure-DP train step with the int8 error-feedback gradient all-reduce.

    Params are replicated across 'data'; the gradient exchange — the
    cross-pod-dominant collective at 1000+ nodes — moves int8/bf16 on the
    wire (see repro.optim.compression).  Used by tests + the train driver's
    ``--compress-grads`` flag; composable with TP by nesting meshes.
    """
    axis = "data"

    def local_loss(params, batch):
        l, metrics = model_zoo.loss_fn(cfg, params, batch, train=True, remat=remat)
        return l, metrics

    def step(state: TrainState, batch):
        (l, metrics), grads = jax.value_and_grad(local_loss, has_aux=True)(
            state.params, batch)
        grads, new_err = compression.psum_compressed(grads, state.err_buf, axis)
        l = jax.lax.pmean(l, axis)
        metrics = jax.lax.pmean(metrics, axis)
        new_params, new_opt, opt_metrics = adamw_update(opt_cfg, grads, state.opt, state.params)
        metrics = dict(metrics, **opt_metrics, loss=l)
        return TrainState(new_params, new_opt, state.step + 1, new_err), metrics

    replicated = P()

    def wrapped(state, batch):
        state_spec = jax.tree.map(lambda _: replicated, state)
        # batch leaves are (B, ...): shard B over the DP axis.
        batch_spec = jax.tree.map(lambda x: P(axis, *([None] * (x.ndim - 1))), batch)
        fn = jax.shard_map(step, mesh=mesh,
                           in_specs=(state_spec, batch_spec),
                           out_specs=(state_spec, replicated),
                           check_vma=False)
        return fn(state, batch)

    return jax.jit(wrapped)
