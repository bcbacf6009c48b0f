"""Serve bitnet-2b-4t at its published widths on one TPU and check the answers.

    python chip_smoke.py

The quickest proof that the system still runs on the chip.  It drives the
main path through the entry points a user calls, with random weights from
seed 0, and fails (non-zero exit, no result line) if any phase fails:

1. kernels: each Pallas kernel compiled for the chip (``interpret=False``) at
   the 2560 x 6912 BitLinear shape, for decode (N=1) and prefill (N=128),
   ``tsar_matmul`` in both dataflows.  The compiled HLO must hold the Mosaic
   call (``tpu_custom_call``) and the output must match ``kernels/ref.py``.
2. init: ``zoo.init_params`` frozen to 2-bit planes inside one jitted program
   (``serving.init_packed_params``), all 30 layers at full width.
3. serve: ``ServingEngine(packed=True)`` with the default ``flat`` policy
   serves 4 greedy requests (prompts of 16-64 tokens, 16 new tokens each)
   through steps that mix prefill chunks with decode tokens.
4. reference: ``model_zoo.prefill`` plus ``decode_step`` on an unpaged cache,
   from the same params and fed the served tokens.  The logits each served
   token was sampled from, the first ones included, must agree with the
   reference's within ``LOGITS_RTOL``, and every served token must be the
   reference's greedy choice or a near-tie that the measured logits
   difference explains.

Times are from this one cold run, compilation included unless named
otherwise.  The last line of standard output is the JSON result.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.configs as configs  # noqa: E402
from repro.core import ternary  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import layers, model_zoo as zoo  # noqa: E402
from repro.obs.trace import EventTracer, MemorySink  # noqa: E402
from repro.plan import runtime as plan_runtime  # noqa: E402
from repro.serving import Request, ServingEngine, init_packed_params  # noqa: E402
from repro.serving.engine import _flat_call  # noqa: E402
from repro.sparse import format as sparse_format  # noqa: E402

SEED = 0
ARCH = "bitnet-2b-4t"
K, M = 2560, 6912                 # the model's d_model x d_ff BitLinear
KERNEL_NS = (1, 128)              # decode GEMV, prefill GEMM rows
KERNEL_RTOL = 1e-3                # max |kernel - ref| over max |ref|
PROMPT_LENS = (16, 32, 48, 64)
MAX_NEW = 16
SLOTS = 4
PREFILL_CHUNK = 32
MAX_LEN = 128
# Engine and reference run the same math on differently shaped arrays
# (paged vs unpaged cache, one flat row vector vs per-request batches), so
# float sums differ in order in the last bits.  Per-token int8 activation
# quantization makes the model discontinuous: such a difference can flip a
# rounding, and the flip spreads through later layers.  So the check bounds
# the RMS of the logits difference relative to the RMS of the logits; a
# wrong position, cache block or mask gives logits that are unrelated, a
# relative RMS near 1.4.
LOGITS_RTOL = 0.2
TIMING_REPS = 5


def log(msg: str) -> None:
    print(msg, flush=True)


def _compile(fn, *args):
    """Compile ``fn`` for the device; require a Mosaic kernel in its HLO."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("compiled HLO holds no tpu_custom_call")
    return compiled, secs


def _median_ms(fn, x) -> float:
    """Median wall time of ``TIMING_REPS`` warm calls, each waited for."""
    jax.block_until_ready(fn(x))
    times = []
    for _ in range(TIMING_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        times.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(times))


def smoke_kernels() -> None:
    kw, ks, kx = jax.random.split(jax.random.PRNGKey(SEED), 3)
    bk, bm = sparse_format.DEFAULT_BK, sparse_format.DEFAULT_BM
    # Block-structured zeros, so the sparse kernels really skip blocks.
    t = sparse_format.random_block_sparse_ternary(kw, (K, M), bk, bm,
                                                  p_zero_block=0.3)
    scale = jax.random.uniform(ks, (M,), minval=0.25, maxval=2.0)
    tw = ternary.pack(t.astype(jnp.float32), scale)
    ip, iz = ternary.pack_indices(t, 4)
    bst = sparse_format.from_ternary(t, scale, bk=bk, bm=bm)
    pbst = sparse_format.pad_from_ternary(t, scale, bk=bk, bm=bm)
    planes = {"sign": tw.sign_plane, "zero": tw.zero_plane,
              "scale": tw.scale}
    served = jax.jit(lambda x: layers.linear(planes, x, train=False))
    log(f"kernels: K={K} M={M}, {bst.n_live}/{bst.grid[0] * bst.grid[1]} "
        f"live {bk}x{bm} blocks; times are the median of {TIMING_REPS} "
        f"warm calls in this one run")
    for n in KERNEL_NS:
        x = jax.random.normal(jax.random.fold_in(kx, n), (n, K), jnp.float32)
        with jax.default_matmul_precision("highest"):
            want_lut = ref.ternary_matmul_ref(x, t, scale)
        want_q = ref.quantized_matmul_ref(x, tw)
        want_sp = ref.block_sparse_matmul_ref(x, bst)
        cases = [
            (f"tsar_matmul[{df}]",
             lambda x, df=df: ops.tsar_matmul(x, tw, dataflow=df,
                                              interpret=False), want_q)
            for df in ("AP", "OP")
        ] + [
            ("tsar_lut_gemv",
             lambda x: ops.tsar_lut_gemv(x, ip, iz, scale, c=4,
                                         interpret=False), want_lut),
            ("tsar_sparse_matmul",
             lambda x: ops.tsar_sparse_matmul(x, bst, interpret=False),
             want_sp),
            ("tsar_sparse_padded_matmul",
             lambda x: ops.tsar_sparse_padded_matmul(x, pbst,
                                                     interpret=False),
             want_sp),
        ]
        for name, fn, want in cases:
            compiled, secs = _compile(fn, x)
            got = np.asarray(compiled(x))
            want = np.asarray(want)
            err = float(np.max(np.abs(got - want)))
            top = float(np.max(np.abs(want)))
            log(f"  {name} n={n}: compile {secs:.2f} s, tpu_custom_call in "
                f"HLO, max|err| {err:.3g} of max|ref| {top:.4g}, "
                f"{_median_ms(compiled, x):.3f} ms")
            if not (got.shape == want.shape and np.isfinite(got).all()
                    and err <= KERNEL_RTOL * top):
                raise AssertionError(f"{name} n={n} disagrees with ref.py")
        err = float(np.max(np.abs(np.asarray(served(x)) - np.asarray(want_q))))
        log(f"  served jnp planes spelling n={n} (no Pallas): max|err| "
            f"{err:.3g}, {_median_ms(served, x):.3f} ms")


class _RecordingEngine(ServingEngine):
    """Keeps, per request, the logits row each token was sampled from."""

    logits: dict

    def _sample(self, logits, temps):
        self._last_logits = logits
        return super()._sample(logits, temps)

    def _emit_token(self, i, st, tok):
        self.logits.setdefault(st.req.uid, []).append(
            np.asarray(self._last_logits[i]))
        super()._emit_token(i, st, tok)


def _served_step_has_pallas(engine) -> bool:
    """Whether the engine's flat step, as jitted, calls a Pallas kernel."""
    t, b = engine.token_budget, engine.slots
    z = lambda n: jnp.zeros((n,), jnp.int32)  # noqa: E731
    step = jax.jit(lambda p, pools, tbl, tk, sl, ps, er: _flat_call(
        engine.cfg, p, pools, tbl, tk, sl, ps, er))
    with plan_runtime.activate(engine.plan):
        text = step.lower(engine.params, engine.kv.pools,
                          engine.kv.table_view(1), z(t), z(t), z(t),
                          z(b)).as_text()
    return "tpu_custom_call" in text


def _memory_line(dev) -> str:
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "device memory: peak not reported"
    return (f"device memory: peak {stats['peak_bytes_in_use'] / 1e9:.3f} GB, "
            f"in use {stats.get('bytes_in_use', 0) / 1e9:.3f} GB, "
            f"limit {stats.get('bytes_limit', 0) / 1e9:.3f} GB")


def smoke_serving(cfg, dev) -> None:
    t0 = time.perf_counter()
    params = init_packed_params(cfg, jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"init: {cfg.name} L={cfg.n_layers} d={cfg.d_model} ff={cfg.d_ff} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab_size}; "
        f"frozen params {nbytes / 1e9:.3f} GB; "
        f"{time.perf_counter() - t0:.1f} s with compile")
    log(_memory_line(dev))

    tracer = EventTracer(sink=MemorySink())
    engine = _RecordingEngine(cfg, params, packed=True, batch_slots=SLOTS,
                              max_len=MAX_LEN, prefill_chunk=PREFILL_CHUNK,
                              profile_density=False, tracer=tracer)
    engine.logits = {}
    log(f"engine: policy={engine.policy} slots={SLOTS} "
        f"token_budget={engine.token_budget} max_len={MAX_LEN} "
        f"kv_blocks={engine.kv.num_blocks}; plan "
        f"{engine.plan.summary()['decode_kernel']} (decode) / "
        f"{engine.plan.summary()['prefill_kernel']} (prefill)")
    if engine.policy != "flat":
        raise AssertionError(f"expected the flat policy, got {engine.policy}")
    log(f"served step calls a Pallas kernel: {_served_step_has_pallas(engine)}")

    rng = np.random.default_rng(SEED)

    def requests(uid0):
        return [Request(uid=uid0 + i, max_new_tokens=MAX_NEW,
                        prompt=rng.integers(0, cfg.vocab_size, size=n,
                                            dtype=np.int32))
                for i, n in enumerate(PROMPT_LENS)]

    # Warm every step shape with the same request lengths (engine.warmup's
    # single request skips the small view buckets four requests reach).
    t0 = time.perf_counter()
    engine.run(requests(-len(PROMPT_LENS)))
    engine.reset_run_stats()
    log(f"warm run (compiles every step shape; set-up time): "
        f"{time.perf_counter() - t0:.1f} s")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    reqs = requests(0)
    t0 = time.perf_counter()
    engine.run(reqs)
    wall = time.perf_counter() - t0
    n_compiles = len(compiles)
    steps = [e["args"] for e in tracer.events
             if e.get("ph") == "X" and e.get("name") == "step"]
    mixed = sum(1 for a in steps
                if a["prefill_tokens"] > 0 and a["decode_tokens"] > 0)
    ok = [r for r in reqs if r.done and len(r.out_tokens) == MAX_NEW]
    step_s = engine.stats["prefill_s"] + engine.stats["decode_s"]
    log(f"serve (one run after the warm run): {wall:.2f} s wall, "
        f"{n_compiles} compiles, "
        f"{engine.stats['steps']} steps ({mixed} mixed prefill+decode), "
        f"{1e3 * step_s / max(engine.stats['steps'], 1):.1f} ms per jitted "
        f"step call on average, "
        f"{engine.stats['prefill_tokens']} prompt + "
        f"{engine.stats['total_tokens']} output tokens, "
        f"{len(reqs)} requests: {len(ok)} succeeded, "
        f"{len(reqs) - len(ok)} failed, "
        f"{engine.stats['rejections']} rejected")
    log(_memory_line(dev))
    if len(ok) != len(reqs) or mixed == 0:
        raise AssertionError("not every request was served, or no step "
                             "mixed prefill with decode")
    check_reference(cfg, params, engine, reqs)


def check_reference(cfg, params, engine, reqs) -> None:
    plan = engine.plan
    prefill = jax.jit(lambda p, tok, cache: zoo.prefill(
        cfg, p, {"tokens": tok}, cache, plan=plan))
    decode = jax.jit(lambda p, tok, cache, t: zoo.decode_step(
        cfg, p, tok, cache, t, plan=plan))
    v = cfg.vocab_size                    # padded columns are masked in both
    t0 = time.perf_counter()
    first, worst = [], 0.0
    exact = near = 0
    for r in reqs:
        cache = zoo.init_cache(cfg, 1, MAX_LEN)
        logits, cache = prefill(params, jnp.asarray(r.prompt)[None], cache)
        for i, tok in enumerate(r.out_tokens):
            ref_row = np.asarray(logits[0, -1])[:v]
            got_row = engine.logits[r.uid][i][:v]
            diff = np.abs(got_row - ref_row)
            rel = float(np.sqrt(np.mean(diff ** 2) / np.mean(ref_row ** 2)))
            worst = max(worst, rel)
            if i == 0:
                first.append((rel, float(diff.max()), float(ref_row.std())))
            if not np.isfinite(got_row).all() or rel > LOGITS_RTOL:
                raise AssertionError(
                    f"request {r.uid} token {i}: logits differ by relative "
                    f"RMS {rel:.3g} > {LOGITS_RTOL}")
            best = int(np.argmax(ref_row))
            if tok == best:
                exact += 1
            elif ref_row[best] - ref_row[tok] <= 2 * float(diff.max()):
                near += 1              # the engine's own argmax: a near-tie
            else:
                raise AssertionError(
                    f"request {r.uid} token {i}: served {tok}, reference "
                    f"greedy {best} (logit gap "
                    f"{ref_row[best] - ref_row[tok]:.3g})")
            if i + 1 < len(r.out_tokens):
                logits, cache = decode(params, jnp.asarray([[tok]]), cache,
                                       jnp.int32(len(r.prompt) + i))
    log("reference: first logits per request (relative RMS diff, max|diff|, "
        "logits std): " + ", ".join(f"({a:.3g}, {b:.3g}, {c:.3g})"
                                     for a, b, c in first))
    log(f"reference: worst relative RMS diff over all {exact + near} served "
        f"tokens {worst:.3g} (limit {LOGITS_RTOL}); {exact} tokens the "
        f"reference's greedy choice, {near} near-ties within twice the "
        f"logits difference; {time.perf_counter() - t0:.1f} s with compile")


def main() -> int:
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this check runs only on the chip",
              file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind!r} x {len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    smoke_kernels()
    smoke_serving(configs.get(ARCH), dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
