"""Prefix-caching KV reuse subsystem: radix-cache properties (insert/lookup/
evict round-trips, refcount safety, LRU order), hit-path token identity with
a cold engine, scheduler token-budget accounting, preemption under sharing,
and the chunk_step nonzero-start-offset contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.configs as configs
from repro.models import model_zoo as zoo
from repro.serving import PagedKVCache, PrefixCache, Request, ServingEngine

BS = 4  # block size for the data-structure tests


@pytest.fixture(scope="module")
def cfg():
    return configs.get("bitnet-2b-4t").reduced()


@pytest.fixture(scope="module")
def model(cfg):
    return cfg, zoo.init_params(cfg, jax.random.PRNGKey(0))


def _fill_and_register(kv, cache, slot, tokens):
    """Simulate a finished prefill: allocate blocks for ``tokens`` in
    ``slot`` and register the full blocks with the cache."""
    assert kv.ensure(slot, len(tokens))
    kv.lengths[slot] = len(tokens)
    cache.insert(tokens, kv.table[slot])


class TestRadixCache:
    """Pure data-structure properties over the real allocator."""

    def _mk(self, cfg, num_blocks=64, capacity=None):
        kv = PagedKVCache(cfg, slots=4, max_len=16 * BS, block_size=BS,
                          num_blocks=num_blocks)
        return kv, PrefixCache(kv, capacity_blocks=capacity)

    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(min_value=1, max_value=40),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_insert_lookup_roundtrip(self, cfg, n, seed):
        """A registered sequence matches back exactly its full blocks capped
        below the sequence length, with the registering slot's block ids."""
        kv, pc = self._mk(cfg)
        rng = np.random.default_rng(seed)
        toks = rng.integers(0, 50, size=n).astype(np.int32)
        _fill_and_register(kv, pc, 0, toks)
        want_blocks = min(len(toks) // BS, max(0, (len(toks) - 1) // BS))
        cached, blocks = pc.match(toks)
        assert cached == want_blocks * BS
        assert blocks == [int(kv.table[0, j]) for j in range(want_blocks)]
        # A diverging suffix only matches the shared full blocks.
        div = toks.copy()
        if len(div) > BS:
            div[-1] = (div[-1] + 1) % 50
            c2, _ = pc.match(div)
            assert c2 <= cached
        pc.check()
        # Freeing the slot keeps cached blocks alive (cache holds a ref).
        kv.free_slot(0)
        c3, _ = pc.match(toks)
        assert c3 == cached
        pc.check()

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_refcount_never_negative_random_ops(self, cfg, seed):
        """Random interleavings of fill/register/fork/free/evict keep every
        allocator + tree invariant (refcount >= 0, free list consistent,
        no cached block freed while referenced)."""
        kv, pc = self._mk(cfg, num_blocks=40)
        rng = np.random.default_rng(seed)
        seqs = [rng.integers(0, 20, size=rng.integers(1, 3 * BS + 2))
                .astype(np.int32) for _ in range(4)]
        busy = set()
        for _ in range(30):
            op = rng.integers(0, 4)
            slot = int(rng.integers(0, 4))
            toks = seqs[int(rng.integers(0, len(seqs)))]
            if op == 0 and slot not in busy:
                cached = pc.fork(slot, toks)
                if kv.ensure(slot, len(toks)):
                    kv.lengths[slot] = len(toks)
                    pc.insert(toks, kv.table[slot])
                    busy.add(slot)
                else:
                    kv.free_slot(slot)
                assert cached % BS == 0 and cached < max(len(toks), 1)
            elif op == 1 and slot in busy:
                kv.free_slot(slot)
                busy.discard(slot)
            elif op == 2:
                pc.evict(int(rng.integers(1, 4)))
            else:
                pc.match(toks)
            pc.check()
        for slot in list(busy):
            kv.free_slot(slot)
        pc.check()
        # Draining the cache returns every block to the free list.
        pc.evict(pc.cached_blocks)
        pc.check()
        assert kv.blocks_in_use == 0

    def test_eviction_order_is_lru(self, cfg):
        kv, pc = self._mk(cfg)
        a = np.arange(BS, dtype=np.int32) + 1          # distinct single blocks
        b = np.arange(BS, dtype=np.int32) + 100
        c = np.arange(BS, dtype=np.int32) + 200
        for slot, toks in enumerate((a, b, c)):
            # +1 so the full block is insertable AND matchable (the matcher
            # always leaves >= 1 token to recompute).
            _fill_and_register(kv, pc, slot, np.append(toks, 7))
            kv.free_slot(slot)
        assert pc.cached_blocks == 3
        assert pc.fork(0, np.append(a, 7)) == BS       # touch A
        kv.free_slot(0)
        pc.evict(1)
        assert pc.match(np.append(b, 7))[0] == 0       # B was LRU -> gone
        assert pc.match(np.append(a, 7))[0] == BS
        assert pc.match(np.append(c, 7))[0] == BS
        pc.evict(2)
        assert pc.match(np.append(c, 7))[0] == 0       # C before touched A
        assert pc.cached_blocks == 0
        assert kv.blocks_in_use == 0
        pc.check()

    def test_eviction_never_touches_live_slots(self, cfg):
        kv, pc = self._mk(cfg)
        toks = np.arange(3 * BS + 1, dtype=np.int32)
        _fill_and_register(kv, pc, 0, toks)
        kv.free_slot(0)
        # Slot 1 forks the prefix — its blocks are now live.
        cached = pc.fork(1, toks)
        assert cached == 3 * BS
        freed = pc.evict(10)
        assert freed == 0                              # all cached blocks live
        assert pc.evictable() == 0
        kv.free_slot(1)
        assert pc.evictable() == 3
        assert pc.evict(10) == 3
        pc.check()

    def test_capacity_bound_evicts_lru(self, cfg):
        kv, pc = self._mk(cfg, capacity=2)
        for base in (0, 100, 200):
            toks = np.arange(BS, dtype=np.int32) + base
            slot = 0
            _fill_and_register(kv, pc, slot, np.append(toks, 7))
            kv.free_slot(slot)
        assert pc.cached_blocks <= 2
        assert pc.match(np.append(np.arange(BS, dtype=np.int32), 7))[0] == 0
        pc.check()

    def test_partial_last_block_never_cached(self, cfg):
        """Block-aligned cap: a sequence shorter than one block caches
        nothing; an exact-multiple sequence keeps its last block out of the
        MATCH (>= 1 token always recomputed) though it may be registered."""
        kv, pc = self._mk(cfg)
        short = np.arange(BS - 1, dtype=np.int32)
        _fill_and_register(kv, pc, 0, short)
        assert pc.cached_blocks == 0
        exact = np.arange(2 * BS, dtype=np.int32) + 50
        _fill_and_register(kv, pc, 1, exact)
        cached, _ = pc.match(exact)
        assert cached == BS                            # not 2*BS: last stays hot
        pc.check()


class TestEnginePrefixReuse:
    def _shared_reqs(self, sys_prompt, n=4, tail=16, maxnew=5):
        rng = np.random.default_rng(3)
        tails = [rng.integers(0, 90, size=tail).astype(np.int32)
                 for _ in range(n)]
        return [Request(uid=i,
                        prompt=np.concatenate([sys_prompt, tails[i]]),
                        max_new_tokens=maxnew)
                for i in range(n)]

    def test_shared_prefix_token_identical_and_cheaper(self, model):
        """Acceptance: 75%-shared prompts under the prefix cache produce
        token-identical outputs to the cache-off engine, schedule strictly
        fewer prefill chunk-tokens, and report a nonzero hit rate; the
        cache-off engine's stats carry no prefix keys (PR 4 unchanged)."""
        cfg, params = model
        sys_prompt = (np.arange(48, dtype=np.int32) * 5 + 1) % 90
        mk = lambda: self._shared_reqs(sys_prompt)     # 48 shared / 64 total
        off = ServingEngine(cfg, params, max_len=128, batch_slots=2,
                            prefill_chunk=8)
        r_off = off.run(mk())
        on = ServingEngine(cfg, params, max_len=128, batch_slots=2,
                           prefill_chunk=8, prefix_cache=True)
        r_on = on.run(mk())
        for a, b in zip(r_off, r_on):
            assert a.out_tokens == b.out_tokens, (a.uid, a.out_tokens, b.out_tokens)
        assert on.sched.prefill_tokens_planned < off.sched.prefill_tokens_planned
        assert on.stats["prefill_tokens"] < off.stats["prefill_tokens"]
        assert on.sched.cached_tokens_skipped > 0
        assert on.stats["prefix_hit_rate"] > 0
        assert on.stats["prefix_hit_tokens"] >= 48     # later reqs hit 48 each
        assert "prefix_hit_rate" not in off.stats
        assert "cached_blocks" not in off.stats
        on.prefix.check()

    def test_prefix_cache_off_is_default(self, model):
        cfg, params = model
        eng = ServingEngine(cfg, params, max_len=64, batch_slots=2)
        assert eng.prefix is None
        eng.run([Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                         max_new_tokens=3)])
        assert "prefix_hit_rate" not in eng.stats

    def test_multi_turn_reuse_via_generated_tokens(self, model):
        """A follow-up prompt quoting prompt+answer of a finished request
        hits the registered generated blocks too."""
        cfg, params = model
        eng = ServingEngine(cfg, params, max_len=128, batch_slots=2,
                            prefill_chunk=8, block_size=4, prefix_cache=True)
        first = Request(uid=0, prompt=np.arange(24, dtype=np.int32) % 70,
                        max_new_tokens=8)
        eng.run([first])
        turn2_prompt = np.concatenate(
            [first.prompt, np.asarray(first.out_tokens, np.int32),
             np.arange(5, dtype=np.int32) + 7])
        hit0 = eng.stats["prefix_hit_tokens"]
        follow = Request(uid=1, prompt=turn2_prompt, max_new_tokens=4)
        eng.run([follow])
        # prompt (24) + all but the last generated token (7) are cached;
        # the fork reuses at least the prompt's six 4-token blocks.
        assert eng.stats["prefix_hit_tokens"] - hit0 >= 24
        eng.prefix.check()

    def test_preemption_with_shared_prefix_recovers(self, model):
        """Satellite regression: recompute-preemption of a request whose
        blocks are shared (prefix cache + a sibling fork) must release
        references, not free-list them — outputs stay identical to a roomy
        engine and to cache-off, and the pool drains clean."""
        cfg, params = model
        sys_prompt = (np.arange(16, dtype=np.int32) * 3 + 2) % 80
        mk = lambda: self._shared_reqs(sys_prompt, n=3, tail=8, maxnew=8)
        roomy = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                              prefill_chunk=8, prefix_cache=True).run(mk())
        off = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                            prefill_chunk=8).run(mk())
        # Tight pool: two growing requests + cached blocks must collide.
        tight_eng = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                                  prefill_chunk=8, block_size=4, kv_blocks=16,
                                  prefix_cache=True)
        tight = tight_eng.run(mk())
        assert all(r.done for r in tight)
        for a, b, c in zip(roomy, tight, off):
            assert a.out_tokens == b.out_tokens
            assert a.out_tokens == c.out_tokens
        tight_eng.prefix.check()
        # Every non-cached block is back on the free list.
        assert tight_eng.kv.blocks_in_use == tight_eng.prefix.cached_blocks

    def test_preempted_partial_prefill_reused_at_readmission(self, model):
        """Satellite regression (PR 6): at preemption time the victim's
        partial prefill is registered into the prefix cache, so its
        recompute re-admission forks the already-computed blocks instead of
        re-prefilling from token zero.

        Prompts are pairwise-distinct here, so ``cached_tokens_skipped`` can
        ONLY come from a preempted request re-matching its own registered
        blocks — with registration absent it is provably zero.  The cache-on
        engine must also schedule strictly fewer prefill chunk-tokens than
        the cache-off engine preempting over the same pool."""
        cfg, params = model
        rng = np.random.default_rng(11)
        mk = lambda: [Request(uid=i, prompt=rng.integers(0, 90, size=30 + i),
                              max_new_tokens=6) for i in range(3)]
        rng2 = np.random.default_rng(11)
        mk2 = lambda: [Request(uid=i, prompt=rng2.integers(0, 90, size=30 + i),
                               max_new_tokens=6) for i in range(3)]
        tight = dict(max_len=64, batch_slots=2, prefill_chunk=8,
                     block_size=4, kv_blocks=16)
        roomy = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                              prefill_chunk=8).run(mk())
        on = ServingEngine(cfg, params, prefix_cache=True, **tight)
        r_on = on.run(mk2())
        assert on.stats["preemptions"] > 0, "pool not tight enough to preempt"
        assert on.sched.readmissions > 0
        # The tentpole assertion: re-admissions reused registered partials.
        assert on.sched.cached_tokens_skipped > 0
        assert on.stats["prefix_hit_tokens"] > 0
        for a, b in zip(roomy, r_on):
            assert a.out_tokens == b.out_tokens, (a.uid, a.out_tokens,
                                                  b.out_tokens)
        rng2 = np.random.default_rng(11)
        off = ServingEngine(cfg, params, **tight)
        r_off = off.run(mk2())
        assert off.stats["preemptions"] > 0
        assert off.sched.cached_tokens_skipped == 0
        assert (on.sched.prefill_tokens_planned
                < off.sched.prefill_tokens_planned), \
            "preemption-time registration did not reduce re-prefill work"
        for a, b in zip(r_on, r_off):
            assert a.out_tokens == b.out_tokens
        on.prefix.check()

    def test_pool_pressure_evicts_cache_before_preempting(self, model):
        """A pool mostly consumed by stale cached prefixes must be reclaimed
        by the allocator's evictor hook, not strand admissions."""
        cfg, params = model
        eng = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                            prefill_chunk=8, block_size=4, kv_blocks=20,
                            prefix_cache=True)
        rng = np.random.default_rng(0)
        # Distinct prompts fill the cache with ~unreusable prefixes.
        warm = [Request(uid=i, prompt=rng.integers(0, 90, size=20),
                        max_new_tokens=4) for i in range(3)]
        eng.run(warm)
        assert eng.stats["cached_blocks"] > 0
        more = [Request(uid=9 + i, prompt=rng.integers(0, 90, size=24),
                        max_new_tokens=4) for i in range(2)]
        eng.run(more)
        assert all(r.done and len(r.out_tokens) == 4 for r in more)
        assert eng.stats["prefix_evictions"] > 0
        eng.prefix.check()

    @pytest.mark.parametrize("policy", ["flat", "chunked"])
    def test_finish_at_prefill_end_registers_once(self, model, policy):
        """Satellite regression: a request whose final prefill chunk also
        emits its last token (max_new_tokens=1) used to be registered with
        the prefix cache TWICE in one step — once at prefill end, once at
        finish.  ``PrefixCache.inserts`` counts insert() calls, pinning
        single registration per lifecycle event."""
        cfg, params = model
        eng = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                            prefill_chunk=8, policy=policy,
                            prefix_cache=True)
        eng.run([Request(uid=0, prompt=np.arange(24, dtype=np.int32),
                         max_new_tokens=1)])
        assert eng.prefix.inserts == 1
        # A request that keeps decoding registers once at prefill end and
        # once at finish — two lifecycle events, two inserts.
        eng2 = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                             prefill_chunk=8, policy=policy,
                             prefix_cache=True)
        eng2.run([Request(uid=0, prompt=np.arange(24, dtype=np.int32),
                          max_new_tokens=4)])
        assert eng2.prefix.inserts == 2

    def test_ssm_family_degrades_to_cold(self):
        """Satellite: state-carrying families accept prefix_cache=True but
        degrade gracefully — whole-prefill policy, zero hit rate, identical
        outputs to a cache-off engine."""
        cfg = configs.get("mamba2-780m").reduced()
        params = zoo.init_params(cfg, jax.random.PRNGKey(0))
        mk = lambda: [Request(uid=i, prompt=np.arange(6 + i) % 50,
                              max_new_tokens=4) for i in range(2)]
        eng = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                            prefix_cache=True)
        assert eng.policy == "whole" and eng.prefix is None
        out = eng.run(mk())
        assert eng.stats["prefix_hit_rate"] == 0.0
        assert eng.stats["cached_blocks"] == 0
        ref = ServingEngine(cfg, params, max_len=48, batch_slots=2).run(mk())
        for a, b in zip(out, ref):
            assert a.out_tokens == b.out_tokens


def test_chunk_step_accepts_nonzero_start(model):
    """Model-zoo contract: a chunk starting at lengths[i] > 0 over a
    pre-populated cache matches the same positions computed in one shot."""
    cfg, params = model
    S, split = 24, 16
    toks = (np.arange(S, dtype=np.int32) * 11 + 3) % 80
    cache = zoo.init_cache(cfg, 1, 32)
    logits_a, cache_a = zoo.chunk_step(
        cfg, params, jnp.asarray(toks[None]),
        jnp.arange(S, dtype=jnp.int32)[None], cache,
        jnp.zeros((1,), jnp.int32), train=False)
    cache = zoo.init_cache(cfg, 1, 32)
    _, cache_b = zoo.chunk_step(
        cfg, params, jnp.asarray(toks[None, :split]),
        jnp.arange(split, dtype=jnp.int32)[None], cache,
        jnp.zeros((1,), jnp.int32), train=False)
    logits_b, cache_b = zoo.chunk_step(
        cfg, params, jnp.asarray(toks[None, split:]),
        jnp.arange(split, S, dtype=jnp.int32)[None], cache_b,
        jnp.full((1,), split, jnp.int32), train=False)
    np.testing.assert_allclose(np.asarray(logits_a[:, -1]),
                               np.asarray(logits_b[:, -1]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache_a["k"][:, :, :S]),
                               np.asarray(cache_b["k"][:, :, :S]),
                               rtol=2e-5, atol=2e-5)
