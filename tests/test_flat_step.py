"""Flat token-packed engine step (``policy="flat"``): one step in place on
the block pools against the gather -> step-over-view -> scatter reference,
the pool cells a step may touch, token identity to the rectangular chunked
and whole-prompt paths across dense/MoE and prefix-cache on/off, behavior
under a preemption storm, planner budget/ordering properties, and the
rejection accounting satellite."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import layers, transformer
from repro.models import model_zoo as zoo
from repro.serving import PagedKVCache, Request, ServingEngine
from repro.serving.engine import _flat_call
from repro.serving.scheduler import ChunkedScheduler, FlatStepPlan, SlotState

CHUNK = 8


@pytest.fixture(scope="module")
def dense_model():
    cfg = configs.get("bitnet-2b-4t").reduced()
    return cfg, zoo.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def moe_model():
    # Dropless capacity: with ample capacity nothing overflows and every
    # layout routes identically (the overflow regime is a true cross-policy
    # divergence, documented in tests/test_moe_serving.py).
    cfg = dataclasses.replace(configs.get("deepseek-moe-16b").reduced(),
                              capacity_factor=8.0)
    return cfg, zoo.init_params(cfg, jax.random.PRNGKey(0))


def _mixed_reqs(maxnew=6, seed=7):
    rng = np.random.default_rng(seed)
    lens = [3, CHUNK, 21, 40]
    return [Request(uid=i, prompt=rng.integers(0, 100, size=s).astype(np.int32),
                    max_new_tokens=maxnew)
            for i, s in enumerate(lens)]


# ---------------------------------------------------------------------------
# One step in place on the pools
# ---------------------------------------------------------------------------

def _view_step(cfg, params, tokens, slot, pos, view, emit_row):
    """The flat step as it ran over a gathered (L, B, Vtok, Hkv, Dh) view:
    each layer writes its rows into the view (padding rows to a dump row
    past the live cells), attends over the whole view flattened slot-major
    under the segment mask, and the scan stacks the layer views back.
    Dense family without qk-norm, windows or softcaps."""
    assert not (cfg.qk_norm or cfg.window_pattern or cfg.attn_softcap)
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, vtok = view["k"].shape[1:3]
    n = tokens.shape[0]
    widx = jnp.where(slot < b, slot * vtok + pos, b * vtok)
    kidx = jnp.arange(b * vtok)
    valid = (kidx[None] // vtok == slot[:, None]) \
        & (kidx[None] % vtok <= pos[:, None])

    def write(c, u):
        flat = c.reshape((b * vtok, hk, dh))
        flat = jnp.concatenate([flat, jnp.zeros_like(flat[:1])])
        return flat.at[widx].set(u)[:b * vtok].reshape(c.shape)

    def body(x, xs):
        p, ck, cv = xs
        a = p["attn"]
        hx = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
        q = layers.linear(a["wq"], hx, False).reshape(1, n, h, dh)
        k = layers.linear(a["wk"], hx, False).reshape(1, n, hk, dh)
        v = layers.linear(a["wv"], hx, False).reshape(1, n, hk, dh)
        q = layers.rope(q, pos, cfg.rope_theta)
        k = layers.rope(k, pos, cfg.rope_theta)
        ck, cv = write(ck, k[0]), write(cv, v[0])
        qg = q.reshape(1, n, hk, h // hk, dh)
        scores = jnp.einsum("bshgd,bthd->bhgst", qg,
                            ck.reshape(1, b * vtok, hk, dh)) / jnp.sqrt(
            jnp.float32(dh))
        scores = jnp.where(valid, scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhgst,bthd->bshgd", probs,
                         cv.reshape(1, b * vtok, hk, dh))
        x = x + layers.linear(a["wo"], ctx.reshape(1, n, h * dh), False)
        x = x + layers.mlp(p["mlp"], layers.rmsnorm(p["ln2"], x,
                                                    cfg.norm_eps), False)
        return x, (ck, cv)

    x = params["embed"][tokens][None] * math.sqrt(cfg.d_model)
    x, (ks, vs) = jax.lax.scan(body, x.astype(jnp.float32),
                               (params["blocks"], view["k"], view["v"]))
    logits = transformer._head(cfg, params, x[0, emit_row][None])[0]
    return logits, {"k": ks, "v": vs}


@pytest.fixture(scope="module")
def one_step(dense_model):
    """Three slots over garbage-filled pools (block size 8, 4-block view):
    slot 0 decodes at position 21; slot 1 forks slot 0's first two blocks
    as a cached prefix and runs a mid-prompt chunk of 8 rows at 16..23;
    slot 2 has a one-block table and decodes at 5; 3 padding rows.  Both
    the in-place step and the view reference run from the same pools."""
    cfg, params = dense_model
    kv = PagedKVCache(cfg, slots=3, max_len=32, block_size=8, num_blocks=16)
    assert kv.ensure(0, 22)
    kv.fork_blocks(1, [int(b) for b in kv.table[0, :2]])
    assert kv.ensure(1, 24)
    assert kv.ensure(2, 6)
    key_k, key_v = jax.random.split(jax.random.PRNGKey(5))
    pools = {"k": jax.random.normal(key_k, kv.pools["k"].shape),
             "v": jax.random.normal(key_v, kv.pools["v"].shape)}
    table = kv.table_view(4)
    slot = np.array([0] + [1] * 8 + [2] + [3] * 3, np.int32)
    pos = np.array([21] + list(range(16, 24)) + [5] + [0] * 3, np.int32)
    tokens = np.random.default_rng(2).integers(
        0, cfg.vocab_size, size=slot.size).astype(np.int32)
    emit_row = np.array([0, 8, 9], np.int32)
    args = (table, jnp.asarray(tokens), jnp.asarray(slot), jnp.asarray(pos),
            jnp.asarray(emit_row))
    got = jax.jit(lambda p, pl, *a: _flat_call(cfg, p, pl, *a))(
        params, pools, *args)

    def reference(p, pl, tbl, tk, sl, ps, er):
        view = zoo.gather_cache_view(pl, tbl, cfg.n_kv_heads)
        logits, view = _view_step(cfg, p, tk, sl, ps, view, er)
        return logits, zoo.scatter_cache_view(pl, tbl, view)

    want = jax.jit(reference)(params, pools, *args)
    written = {(int(table[s, p // 8]), int(p % 8))
               for s, p in zip(slot, pos) if s < 3}
    return {"pools": pools, "got": got, "want": want, "written": written,
            "shared": [int(b) for b in kv.table[0, :2]]}


def test_in_place_step_matches_view_reference(one_step):
    """Logits equal to float32 rounding; every pool cell outside scratch
    block 0 equal to the reference's."""
    (logits, pools), (ref_logits, ref_pools) = one_step["got"], \
        one_step["want"]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)
    for name in zoo.PAGED_LEAVES:
        assert pools[name].shape == ref_pools[name].shape
        np.testing.assert_allclose(np.asarray(pools[name])[:, 1:],
                                   np.asarray(ref_pools[name])[:, 1:],
                                   rtol=1e-5, atol=1e-5)


def test_step_writes_only_its_rows(one_step):
    """The only pool rows a step changes are its tokens' rows
    ``(table[slot, pos // bs], pos % bs)``, in every layer, and scratch
    block 0; the blocks slot 1 forked from slot 0 stay bitwise as they
    were."""
    before = one_step["pools"]
    _, after = one_step["got"]
    for name in zoo.PAGED_LEAVES:
        old, new = np.asarray(before[name]), np.asarray(after[name])
        changed = (old != new).any(axis=-1)                 # (L, NB, bs)
        rows = {(int(b), int(o)) for _, b, o in np.argwhere(changed)}
        assert rows - {r for r in rows if r[0] == 0} == one_step["written"]
        for b, o in one_step["written"]:
            assert changed[:, b, o].all()
        for b in one_step["shared"]:
            np.testing.assert_array_equal(new[:, b], old[:, b])


# ---------------------------------------------------------------------------
# Token-identity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["dense", "moe"])
def test_flat_matches_chunked_and_whole(family, dense_model, moe_model):
    """Greedy outputs are identical across flat / chunked / whole for both
    chunkable families — the flat repack changes the layout, not the math."""
    cfg, params = dense_model if family == "dense" else moe_model
    outs = {}
    for policy in ("flat", "chunked", "whole"):
        reqs = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                             prefill_chunk=CHUNK, policy=policy
                             ).run(_mixed_reqs())
        outs[policy] = [r.out_tokens for r in reqs]
    assert outs["flat"] == outs["chunked"]
    assert outs["flat"] == outs["whole"]


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_flat_prefix_cache_token_identical_and_cheaper(family, dense_model,
                                                       moe_model):
    """Flat + prefix cache: warm outputs identical to cache-off, with a
    nonzero hit rate and strictly fewer prefill tokens scheduled."""
    cfg, params = dense_model if family == "dense" else moe_model
    sys_prompt = (np.arange(32, dtype=np.int32) * 5 + 1) % 90
    rng = np.random.default_rng(3)
    tails = [rng.integers(0, 90, size=12).astype(np.int32) for _ in range(4)]
    mk = lambda: [Request(uid=i, prompt=np.concatenate([sys_prompt, tails[i]]),
                          max_new_tokens=5) for i in range(4)]
    off = ServingEngine(cfg, params, max_len=128, batch_slots=2,
                        prefill_chunk=CHUNK, policy="flat")
    r_off = off.run(mk())
    on = ServingEngine(cfg, params, max_len=128, batch_slots=2,
                       prefill_chunk=CHUNK, policy="flat", prefix_cache=True)
    r_on = on.run(mk())
    for a, b in zip(r_off, r_on):
        assert a.out_tokens == b.out_tokens, (a.uid, a.out_tokens, b.out_tokens)
    assert on.stats["prefix_hit_rate"] > 0
    assert on.sched.cached_tokens_skipped > 0
    assert on.sched.prefill_tokens_planned < off.sched.prefill_tokens_planned
    on.prefix.check()


def test_flat_preemption_storm_token_identical(dense_model):
    """A pool tight enough to preempt under the flat policy still finishes
    every request with outputs identical to a roomy flat engine."""
    cfg, params = dense_model
    rng = np.random.default_rng(11)
    mk = lambda: [Request(uid=i, prompt=rng.integers(0, 90, size=30 + i),
                          max_new_tokens=6) for i in range(3)]
    rng2 = np.random.default_rng(11)
    mk2 = lambda: [Request(uid=i, prompt=rng2.integers(0, 90, size=30 + i),
                           max_new_tokens=6) for i in range(3)]
    roomy = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                          prefill_chunk=CHUNK, policy="flat").run(mk())
    tight_eng = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                              prefill_chunk=CHUNK, policy="flat",
                              block_size=4, kv_blocks=16)
    tight = tight_eng.run(mk2())
    assert tight_eng.stats["preemptions"] > 0, "pool not tight enough"
    assert all(r.done for r in tight)
    for a, b in zip(roomy, tight):
        assert a.out_tokens == b.out_tokens, (a.uid, a.out_tokens, b.out_tokens)


def test_flat_is_default_policy_and_budget_bound(dense_model):
    """Flat is the auto policy for chunkable families; real work per step is
    bounded by the token budget (default prefill_chunk + slots)."""
    cfg, params = dense_model
    eng = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                        prefill_chunk=CHUNK)
    assert eng.policy == "flat"
    assert eng.token_budget == CHUNK + eng.slots
    eng.run(_mixed_reqs())
    assert eng.stats["whole_prefills"] == 0
    assert eng.max_step_tokens() <= eng.token_budget


def test_flat_policy_refused_for_recurrent_families():
    cfg = configs.get("mamba2-780m").reduced()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="flat"):
        ServingEngine(cfg, params, max_len=32, batch_slots=1, policy="flat")


def test_token_budget_validated(dense_model):
    cfg, params = dense_model
    with pytest.raises(ValueError, match="token_budget"):
        ServingEngine(cfg, params, max_len=64, batch_slots=4, token_budget=4)


def test_multi_prefill_concurrency(dense_model):
    """Two prompts admitted together both advance in the SAME step — the
    one-prefill-per-step restriction is gone (TTFT under concurrency)."""
    cfg, params = dense_model
    eng = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                        prefill_chunk=CHUNK, policy="flat")
    reqs = [Request(uid=i, prompt=np.arange(20, dtype=np.int32) + i,
                    max_new_tokens=2) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng._admit()
    plan = eng.sched.plan_flat(eng._slots, eng.kv, eng.token_budget)
    assert plan.prefill_mask.all(), "both prefills must advance concurrently"
    assert plan.n_real[0] == plan.n_real[1] == eng.token_budget // 2
    eng.run([])  # drain


# ---------------------------------------------------------------------------
# Planner properties (no model, stub allocator)
# ---------------------------------------------------------------------------

class _KVStub:
    """Minimal allocator facade for pure planner tests."""

    def __init__(self, slots):
        self.lengths = np.zeros(slots, np.int64)

    def ensure(self, i, n):
        return True

    def view_blocks(self, n_tokens):
        vb = 1
        while vb * 16 < max(1, n_tokens):
            vb *= 2
        return vb


def _random_slots(rng, b):
    """Random mix of empty / prefilling / decoding slots + the stub kv."""
    kv = _KVStub(b)
    slots = []
    for i in range(b):
        r = rng.random()
        if r < 0.25:
            slots.append(None)
            continue
        plen = int(rng.integers(1, 30))
        st = SlotState(req=None, prompt=np.arange(plen, dtype=np.int32),
                       admitted_at=int(rng.integers(0, 100)), last_tok=1)
        if r < 0.6:                      # prefilling, possibly mid-prompt
            st.cursor = int(rng.integers(0, plen))
            kv.lengths[i] = st.cursor
        else:                            # decoding
            st.cursor = plen
            kv.lengths[i] = plen + int(rng.integers(0, 4))
        slots.append(st)
    return slots, kv


def test_plan_flat_budget_and_ordering_properties():
    """For random slot mixes: ``sum(n_real) == min(budget, available)``, each
    slot's rows carry contiguous ascending positions starting at its live
    length (never interleaved out of position order), padding rows carry the
    slot sentinel, and emit rows point at each slot's last real token."""
    rng = np.random.default_rng(0)
    for _ in range(200):
        b = int(rng.integers(1, 6))
        slots, kv = _random_slots(rng, b)
        budget = int(rng.integers(b + 1, 40))
        sched = ChunkedScheduler(prefill_chunk=CHUNK)
        plan = sched.plan_flat(slots, kv, budget)
        active = [i for i in range(b) if slots[i] is not None]
        if not active:
            assert plan is None
            continue
        assert isinstance(plan, FlatStepPlan)
        available = sum(
            (len(slots[i].prompt) - slots[i].cursor)
            if slots[i].prefilling else 1
            for i in active)
        assert plan.real_tokens == min(budget, available)
        assert plan.width == (budget if plan.prefill_tokens else b)
        for i in range(b):
            rows = np.flatnonzero(plan.slot == i)
            assert len(rows) == plan.n_real[i]
            if not len(rows):
                continue
            # Contiguous ascending positions from the slot's live length —
            # in row order, so no slot's tokens interleave out of order.
            want = kv.lengths[i] + np.arange(len(rows))
            np.testing.assert_array_equal(plan.pos[rows], want)
            if plan.emit[i]:
                assert plan.emit_row[i] == rows[-1]
            if slots[i].prefilling:
                np.testing.assert_array_equal(
                    plan.tokens[rows],
                    slots[i].prompt[slots[i].cursor:
                                    slots[i].cursor + len(rows)])
        # Padding rows: sentinel slot index b, exactly the unused width.
        assert (plan.slot == b).sum() == plan.width - plan.real_tokens
        assert plan.real_tokens == plan.prefill_tokens + plan.decode_tokens


def test_plan_flat_decode_never_starved():
    """Every decoding slot gets its token even when prefill demand alone
    exceeds the budget."""
    b = 4
    kv = _KVStub(b)
    slots = []
    for i in range(b):
        plen = 100
        st = SlotState(req=None, prompt=np.arange(plen, dtype=np.int32),
                       admitted_at=i, last_tok=1)
        if i < 2:                        # two huge prefills
            st.cursor = 0
        else:                            # two decoders
            st.cursor = plen
            kv.lengths[i] = plen
        slots.append(st)
    plan = ChunkedScheduler(prefill_chunk=CHUNK).plan_flat(slots, kv, 12)
    assert plan.n_real[2] == plan.n_real[3] == 1
    assert plan.emit[2] and plan.emit[3]
    # Remaining 10 tokens fair-shared across the two concurrent prefills.
    assert plan.n_real[0] == plan.n_real[1] == 5
    assert plan.decode_tokens == 2 and plan.prefill_tokens == 10


# ---------------------------------------------------------------------------
# Rejection accounting (satellite)
# ---------------------------------------------------------------------------

def test_prompt_too_long_rejection_is_metric_visible(dense_model):
    """A prompt that can never fit is finished-ignored AND accounted: the
    ``rejections`` counter increments, ``t_done`` is stamped, and the
    workload counter block surfaces the count."""
    from benchmarks.workloads.metrics import engine_counters

    cfg, params = dense_model
    eng = ServingEngine(cfg, params, max_len=32, batch_slots=2,
                        prefill_chunk=CHUNK)
    good = Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                   max_new_tokens=3)
    bad = Request(uid=1, prompt=np.arange(64, dtype=np.int32),
                  max_new_tokens=3)
    eng.run([bad, good])
    assert bad.done and not bad.out_tokens
    assert bad.t_done is not None, "rejection must stamp t_done"
    assert eng.stats["rejections"] == 1
    assert eng.metrics.get("rejections").value == 1
    assert engine_counters(eng)["rejections"] == 1
    assert good.out_tokens and len(good.out_tokens) == 3
    # reset_run_stats clears it like every other run counter.
    eng.reset_run_stats()
    assert eng.stats["rejections"] == 0 and eng.sched.rejections == 0
