"""Block-paged KV cache: allocator bookkeeping, gather/scatter through block
tables, live-token accounting, and slot-recycling isolation."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import model_zoo as zoo
from repro.serving import PagedKVCache, Request, ServingEngine


@pytest.fixture(scope="module")
def cfg():
    return configs.get("bitnet-2b-4t").reduced()


class TestAllocator:
    def test_alloc_free_roundtrip(self, cfg):
        kv = PagedKVCache(cfg, slots=2, max_len=32, block_size=4)
        free0 = kv.free_blocks
        assert kv.ensure(0, 10)          # 3 blocks
        assert kv.n_blocks[0] == 3
        assert kv.ensure(0, 12)          # still 3 (12 = 3*4 exactly)
        assert kv.n_blocks[0] == 3
        assert kv.ensure(0, 13)          # grows to 4
        assert kv.n_blocks[0] == 4
        assert kv.free_blocks == free0 - 4
        handed = set(kv.table[0, :4].tolist())
        assert len(handed) == 4 and 0 not in handed  # unique, scratch reserved
        kv.free_slot(0)
        assert kv.free_blocks == free0
        assert kv.n_blocks[0] == 0 and kv.lengths[0] == 0
        assert (kv.table[0] == 0).all()

    def test_oom_reports_without_allocating(self, cfg):
        kv = PagedKVCache(cfg, slots=2, max_len=32, block_size=4, num_blocks=4)
        assert kv.ensure(0, 12)          # takes all 3 real blocks
        before = kv.n_blocks.copy()
        assert not kv.can_allocate(1)
        assert not kv.ensure(1, 4)       # refused, nothing half-allocated
        assert (kv.n_blocks == before).all()
        kv.free_slot(0)
        assert kv.ensure(1, 4)

    def test_fork_release_refcounts(self, cfg):
        """Shared blocks survive any one holder's free: fork takes a
        reference per block, release returns a block to the free list only
        when the LAST holder lets go."""
        kv = PagedKVCache(cfg, slots=3, max_len=32, block_size=4)
        assert kv.ensure(0, 8)                   # two exclusive blocks
        blocks = [int(kv.table[0, j]) for j in range(2)]
        assert all(kv.refcount[b] == 1 for b in blocks)
        kv.fork_blocks(1, blocks)
        kv.fork_blocks(2, blocks)
        assert all(kv.refcount[b] == 3 for b in blocks)
        free0 = kv.free_blocks
        kv.free_slot(0)
        kv.free_slot(2)
        assert kv.free_blocks == free0           # slot 1 still holds them
        assert all(kv.refcount[b] == 1 for b in blocks)
        kv.check()
        kv.free_slot(1)
        assert kv.free_blocks == free0 + 2
        kv.check()

    def test_fork_into_occupied_slot_rejected(self, cfg):
        kv = PagedKVCache(cfg, slots=2, max_len=32, block_size=4)
        kv.ensure(0, 4)
        kv.ensure(1, 4)
        with pytest.raises(ValueError, match="non-empty"):
            kv.fork_blocks(1, [int(kv.table[0, 0])])
        kv.free_slot(1)
        with pytest.raises(ValueError, match="unowned"):
            kv.fork_blocks(1, [kv._free[-1]])    # free block: not forkable
        with pytest.raises(ValueError, match="scratch"):
            kv.release(0)

    def test_view_covers_chunk_past_max_len(self, cfg):
        kv = PagedKVCache(cfg, slots=2, max_len=32, block_size=4)
        vb = kv.view_blocks(32 + 16)     # near-full slot + chunk-wide write
        assert vb * kv.block_size >= 32 + 16
        assert kv.table_view(vb).shape == (2, vb)


class TestGatherScatter:
    def test_roundtrip_through_block_tables(self, cfg):
        kv = PagedKVCache(cfg, slots=2, max_len=16, block_size=4)
        kv.ensure(0, 8)
        kv.ensure(1, 8)
        key = jax.random.PRNGKey(0)
        kv.pools["k"] = jax.random.normal(key, kv.pools["k"].shape)
        table = kv.table_view(2)
        hk, dh = cfg.n_kv_heads, cfg.head_dim
        # A pool block is lane-dense (bs, Hkv*Dh); the view's rows are
        # (Hkv, Dh).
        block = lambda pool, b: np.asarray(pool)[:, b].reshape(  # noqa: E731
            -1, kv.block_size, hk, dh)
        view = zoo.gather_cache_view(kv.pools, table, hk)
        s0, s1 = int(table[0, 0]), int(table[1, 1])
        np.testing.assert_array_equal(
            np.asarray(view["k"])[:, 0, :4], block(kv.pools["k"], s0))
        np.testing.assert_array_equal(
            np.asarray(view["k"])[:, 1, 4:8], block(kv.pools["k"], s1))
        # scatter writes modified blocks back to their pool homes
        view["k"] = view["k"] + 1.0
        pools2 = zoo.scatter_cache_view(kv.pools, table, view)
        np.testing.assert_array_equal(
            block(pools2["k"], s0), np.asarray(view["k"])[:, 0, :4])
        # untouched pool blocks stay untouched
        owned = set(np.asarray(table).ravel().tolist())
        for blk in range(kv.num_blocks):
            if blk not in owned:
                np.testing.assert_array_equal(
                    np.asarray(pools2["k"])[:, blk],
                    np.asarray(kv.pools["k"])[:, blk])


class TestEngineAccounting:
    @pytest.fixture(scope="class")
    def model(self, cfg):
        return cfg, zoo.init_params(cfg, jax.random.PRNGKey(0))

    def test_blocks_in_use_tracks_live_tokens(self, model):
        """Paged memory claim: blocks in use never exceed
        live_tokens / block_size + one partial block per active slot."""
        cfg, params = model
        eng = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                            prefill_chunk=8, block_size=8)
        rng = np.random.default_rng(0)
        for i, s in enumerate([5, 30, 12, 44]):
            eng.submit(Request(uid=i, prompt=rng.integers(0, 90, size=s),
                               max_new_tokens=5))
        while eng.step():
            live = eng.kv.live_tokens()
            bound = math.ceil(live / eng.kv.block_size) + eng.slots
            assert eng.kv.blocks_in_use <= bound, (eng.kv.blocks_in_use, bound)
        assert eng.kv.blocks_in_use == 0  # all freed at completion

    def test_no_cross_slot_leakage_after_recycle(self, model):
        """A slot recycled to a new request must produce exactly the tokens a
        fresh engine produces — stale cache blocks are never attended."""
        cfg, params = model
        mk = lambda uid, s: Request(
            uid=uid, prompt=(np.arange(s, dtype=np.int32) * 7 + uid) % 83,
            max_new_tokens=6)
        # Third request reuses a recycled slot (2 slots, 3 requests).
        shared = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                               prefill_chunk=8)
        r_shared = shared.run([mk(0, 6), mk(1, 9), mk(2, 13)])
        solo = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                             prefill_chunk=8)
        r_solo = solo.run([mk(2, 13)])
        assert r_shared[2].out_tokens == r_solo[0].out_tokens

    def test_dense_state_families_still_serve(self, model):
        """SSM caches have no paged leaves; the paged engine must still serve
        them (whole-prefill policy, dense per-slot state)."""
        cfg = configs.get("mamba2-780m").reduced()
        params = zoo.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, max_len=48, batch_slots=2)
        assert eng.policy == "whole"
        reqs = [Request(uid=i, prompt=np.arange(4 + i) % 50, max_new_tokens=4)
                for i in range(2)]
        eng.run(reqs)
        assert all(r.done and len(r.out_tokens) == 4 for r in reqs)
