"""Faults planted under the timed path.

Each takes the engine before its warm-up and breaks one thing a serving
cell can get wrong; a run with any of them has to come out not correct.
The CPU tests plant them at a tiny size, ``calibrate.py faults`` at a
cell's own size on the chip.
"""
from __future__ import annotations

import numpy as np


def state_unchanged(engine):
    """The step computes its logits from the pool as it finds it and hands
    that pool back unchanged: no token's keys and values are kept.  The
    logits come from the step's own program compiled without the pool's
    donation and without the new pool among its outputs, so the pool is
    not copied (a copy does not fit beside bitnet2b.chat's step on one
    v5e)."""
    import jax

    from repro.plan import runtime as plan_runtime
    from repro.serving.engine import _flat_call

    cfg = engine.cfg
    logits_only = jax.jit(
        lambda p, pools, *rest: _flat_call(cfg, p, pools, *rest)[0])

    def call(params, pools, *rest):
        with plan_runtime.activate(engine.plan):
            return logits_only(params, pools, *rest), pools
    engine._flat_fn = call


def half_batch(engine):
    """The odd slots' tokens are left out of the step (made padding rows),
    and those slots are handed the logits of slot 0."""
    import jax.numpy as jnp

    step = engine._flat_fn
    b = engine.slots

    def call(params, pools, table, tokens, slot, pos, emit_row):
        sel, pools = step(params, pools, table, tokens,
                          jnp.where(slot % 2 == 1, b, slot), pos, emit_row)
        keep = jnp.arange(b) % 2 == 0
        return jnp.where(keep[:, None], sel, sel[:1]), pools
    engine._flat_fn = call


def token_altered(engine):
    """Every sampled token is moved to its neighbour in the vocabulary."""
    sample = engine._sample
    v = engine.cfg.vocab_size

    def call(logits, temps):
        return (np.asarray(sample(logits, temps)) + 1) % v
    engine._sample = call


ALL = {"state_unchanged": state_unchanged, "half_batch": half_batch,
       "token_altered": token_altered}
