"""Training-substrate integration: loss decreases, grad accumulation
equivalence, data-pipeline determinism, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.data import DataConfig, PrefetchIterator, SyntheticLMStream
from repro.optim import OptConfig, compression
from repro.train import init_state, make_train_step


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.get("bitnet-2b-4t").reduced()
    opt = OptConfig(lr=2e-3, warmup_steps=5, total_steps=100)
    return cfg, opt


def test_loss_decreases(tiny):
    cfg, opt = tiny
    state = init_state(cfg, jax.random.PRNGKey(0), opt)
    step = jax.jit(make_train_step(cfg, opt))
    stream = SyntheticLMStream(DataConfig(cfg.vocab_size, 64, 8, seed=1))
    losses = []
    for i in range(30):
        state, m = step(state, stream.batch(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05


def test_grad_accum_matches_full_batch(tiny):
    cfg, opt = tiny
    state = init_state(cfg, jax.random.PRNGKey(0), opt)
    stream = SyntheticLMStream(DataConfig(cfg.vocab_size, 32, 8, seed=2))
    batch = stream.batch(0)
    s1, m1 = jax.jit(make_train_step(cfg, opt, accum_steps=1))(state, batch)
    s2, m2 = jax.jit(make_train_step(cfg, opt, accum_steps=4))(state, batch)
    # same gradient mean => same update (tolerances: accumulation reorders sums)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5)


def test_data_pipeline_deterministic_and_resumable():
    dc = DataConfig(vocab_size=512, seq_len=32, global_batch=8, seed=7)
    s1, s2 = SyntheticLMStream(dc), SyntheticLMStream(dc)
    for step in (0, 5, 1000):
        np.testing.assert_array_equal(np.asarray(s1.batch(step)["tokens"]),
                                      np.asarray(s2.batch(step)["tokens"]))
    # host sharding: different hosts see different data
    d2 = DataConfig(vocab_size=512, seq_len=32, global_batch=8, n_hosts=2, host_id=1, seed=7)
    assert not np.array_equal(np.asarray(SyntheticLMStream(d2).batch(0)["tokens"]),
                              np.asarray(s1.batch(0)["tokens"]))


def test_prefetch_iterator_order():
    dc = DataConfig(vocab_size=128, seq_len=8, global_batch=4, seed=3)
    stream = SyntheticLMStream(dc)
    it = PrefetchIterator(stream, start_step=10)
    try:
        for expect in (10, 11, 12):
            step, batch = next(it)
            assert step == expect
            np.testing.assert_array_equal(np.asarray(batch["tokens"]),
                                          np.asarray(stream.batch(expect)["tokens"]))
    finally:
        it.close()


class TestGradCompression:
    def test_compress_leaf_error_feedback(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (1000,))
        err = jnp.zeros_like(g)
        q, scale, new_err = compression.compress_leaf(g, err)
        assert q.dtype == jnp.int8
        # dequantized + error == original exactly (EF invariant)
        np.testing.assert_allclose(
            np.asarray(q, np.float32) * float(scale) + np.asarray(new_err),
            np.asarray(g), rtol=1e-5, atol=1e-6)

    def test_error_feedback_reduces_bias(self):
        """Accumulated EF error stays bounded; naive quantization drifts."""
        g = jax.random.normal(jax.random.PRNGKey(1), (512,)) * 0.01
        err = jnp.zeros_like(g)
        total_sent = jnp.zeros_like(g)
        for _ in range(50):
            q, scale, err = compression.compress_leaf(g, err)
            total_sent = total_sent + q.astype(jnp.float32) * scale
        # mean transmitted ~= g (error feedback recovers the small signal)
        np.testing.assert_allclose(np.asarray(total_sent / 50), np.asarray(g),
                                   rtol=0.02, atol=5e-5)

    def test_compressed_psum_single_device(self):
        """shard_map over a 1-device mesh: compression must be ~lossless-mean."""
        from jax.sharding import PartitionSpec as P

        mesh = jax.make_mesh((1,), ("data",))
        grads = {"w": jax.random.normal(jax.random.PRNGKey(2), (64, 64))}
        err = compression.init_error_buffer(grads)

        def f(g, e):
            return compression.psum_compressed(g, e, "data")

        out, new_err = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False,
        ))(grads, err)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(grads["w"]), rtol=2e-2, atol=2e-2)
