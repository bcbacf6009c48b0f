"""Serving engine: chunked-prefill continuous batching over a block-paged KV
cache.

The engine mirrors the paper's inference protocol (Sec. IV-A) — a GEMM-heavy
prefill filling the KV cache and a GEMV-heavy steady-state decode — but
serves them Sarathi-style: instead of blocking the decode loop on whole-
prompt prefills, every engine step is ONE jitted static-shape model call that
mixes up to ``prefill_chunk`` prompt tokens from the admitted request with
one decode token per running request (see ``scheduler.ChunkedScheduler``).
KV memory is a pool of fixed-size blocks reached through per-slot block
tables (``kv_cache.PagedKVCache``), so resident cache bytes track live
tokens, not ``slots * max_len``.

The module splits four ways:

* ``kv_cache.py``     — block pool, ref-counted free-list allocator, per-slot
  block tables;
* ``scheduler.py``    — admission + chunked-prefill step planning + preemption;
* ``prefix_cache.py`` — block-granular radix tree over token-ID prefixes:
  admitted prompts fork the cached leading blocks of an earlier request
  instead of recomputing them (``ServingEngine(prefix_cache=True)``);
* this file           — the ``ServingEngine``/``Request`` API, the jitted
  steps (the flat step works on the block pools in place), sampling, prefix
  registration, and latency stats (per-request TTFT/TPOT).

Policies: ``flat`` (default for dense/MoE attention families) packs every
step into one flat ``(T,)`` token vector — multiple concurrent prefill
chunks plus all decode tokens, budgeted purely in tokens
(``token_budget``), so the jitted matmuls multiply almost no padding;
``chunked`` is the rectangular ``(B, C)`` predecessor (one prefill chunk
per step, kept as the equivalence reference); ``whole`` prefills each
admitted prompt in a single per-slot call (required for SSM/hybrid
recurrences, enc-dec and VLM frontends).  All three run the same
per-slot-position decode math, so their greedy outputs are identical.

Weight modes:
* ``qat``    — latent fp weights, exact-int8 eval math.
* ``packed`` — weights frozen to 2-bit T-SAR planes; every BitLinear matmul
  streams 8x fewer weight bytes (the paper's core claim).
"""
from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers, model_zoo
from repro.obs import NULL_TRACER, MetricsRegistry, StatsView
from repro.obs import trace as obs_trace
from repro.plan import BatchProfile, ModelPlan, compile_plan
from repro.plan import runtime as plan_runtime
from repro.serving.kv_cache import PagedKVCache
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import ChunkedScheduler, Preempt, SlotState


@dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    out_tokens: list = field(default_factory=list)
    done: bool = False
    # -- latency stats (stamped by the engine) --
    t_submit: float | None = None
    t_admit: float | None = None  # first admission into a slot
    t_first: float | None = None
    t_done: float | None = None
    n_preempted: int = 0          # recompute-preemptions suffered

    @property
    def ttft(self) -> float | None:
        """Time to first token (s)."""
        if self.t_submit is None or self.t_first is None:
            return None
        return self.t_first - self.t_submit

    @property
    def queue_s(self) -> float | None:
        """Submit -> first admission into a slot (s)."""
        if self.t_submit is None or self.t_admit is None:
            return None
        return self.t_admit - self.t_submit

    @property
    def tpot(self) -> float | None:
        """Mean time per output token after the first (s/token)."""
        if self.t_first is None or self.t_done is None or len(self.out_tokens) < 2:
            return None
        return (self.t_done - self.t_first) / (len(self.out_tokens) - 1)


def _measure_stack(w, block_shape: tuple) -> tuple[int, int, float]:
    """Host-side occupancy measurement of one (possibly stacked) latent
    weight: (stack-wide max live blocks, stack-wide max live per strip,
    mean live-block fraction over slices).

    This re-ternarizes (pack_linear ternarizes again inside the vmap — an
    accepted freeze-time-only double cost; the vmapped construction cannot
    see across the stack, so the bounds must be measured out here).
    """
    import numpy as np

    from repro.core import ternary
    from repro.sparse import stats as sparse_stats

    bk, bm = block_shape
    t, _ = ternary.absmean_ternarize(w)
    tn = np.asarray(t, np.int8).reshape((-1,) + t.shape[-2:])
    max_live = s_steps = 0
    bds = []
    for i in range(tn.shape[0]):
        occ = sparse_stats.block_occupancy(tn[i], bk, bm)
        live = occ > 0
        max_live = max(max_live, int(live.sum()))
        s_steps = max(s_steps, int(live.sum(axis=0).max()))
        bds.append(float(live.mean()))
    return max_live, s_steps, float(np.mean(bds)) if bds else 1.0


def _sparse_prepass(w, block_shape: tuple, max_live: int | None = None,
                    s_steps: int | None = None) -> dict | None:
    """Sizing pass for ``sparse="auto"``: when the MEAN live-block fraction
    over the stack sits below the freeze threshold, returns the pack_linear
    kwargs that emit a padded pool sized to the STACK-WIDE maxima
    (``max_live``/``s_steps`` must be uniform across the stack or the pools
    can't ride a vmap/scan).  The mean is the same signal ``compile_plan``
    costs with (the stamped ``block_density`` leaves, averaged) — a single
    sparse outlier slice in an otherwise-dense stack must not stamp
    near-full-grid pools the planner will never pick.  The gate is
    ``SPARSE_SIDE_CAR_THRESHOLD`` (0.95), deliberately a notch ABOVE the
    ~0.9 dispatch break-even — same rationale as the compacted sidecar at
    freeze time: borderline layers keep the option (a plan recompiled with
    a calibrated tax, or a different n-bucket profile, may cross the line),
    while clearly-dense stacks don't carry dead pool bytes.  Caller-supplied
    ``max_live``/``s_steps`` act as FLOORS on the measured values (to keep
    ALL ``sp_*`` leaf shapes — pools and kids/slots schedules alike —
    uniform across re-freezes for a saved plan).  Returns None when the
    checkpoint is too dense to bother (pad slots would dominate).
    """
    from repro.core import bitlinear

    measured_live, measured_steps, mean_bd = _measure_stack(w, block_shape)
    if mean_bd >= bitlinear.SPARSE_SIDE_CAR_THRESHOLD:
        return None
    return {"sparse": True, "block_shape": block_shape,
            "max_live": max(measured_live, max_live or 0, 1),
            "s_steps": max(measured_steps, s_steps or 0, 1)}


def freeze_params(params, *, sparse: str | bool = "auto",
                  block_shape: tuple | None = None,
                  max_live: int | None = None,
                  s_steps: int | None = None) -> dict:
    """Pack every BitLinear latent weight to 2-bit planes (tree-wide).

    Stacked (scan-layer / expert) weights are packed with vmap over leading
    dims; dense fp leaves pass through untouched.

    ``sparse`` controls the padded-pool sidecars (the serveable sparse
    format — see ``repro.sparse.format.PaddedBlockSparseTernary``):

    * ``"auto"`` (default) — on concrete weights, a host-side pre-pass
      measures each layer's block occupancy and emits pools only for layers
      below the freeze threshold, sized to the measured stack-wide
      ``max_live``/``s_steps`` (tight pools, real memory savings);
      caller-supplied ``max_live``/``s_steps`` act as floors (uniform
      ``sp_*`` leaf shapes — pools AND schedules — across re-freezes).
      Under tracing nothing is measurable, so no pools are emitted.
    * ``True`` — always emit pools.  The pool pads to ``max_live`` and the
      schedule to ``s_steps`` (full block grid / K-per-block when None) —
      fully traceable, so ``freeze_params`` itself can run under
      ``jit``/``eval_shape`` and the vmapped per-layer construction works
      on stacked scan weights either way (this is "freeze emits padded
      pools under tracing").  On concrete weights undersized bounds raise
      (checked host-side — the vmap would otherwise silently drop live
      blocks); under tracing the bounds are the caller's promise.
    * ``False`` — planes only (PR 3 behavior).
    """
    from repro.sparse import format as sparse_format

    if sparse not in (True, False, "auto"):
        # A typo ('Auto', 'true') silently freezing planes-only would leave
        # the operator believing the sparse path is active — reject loudly,
        # like hw.set_calibration does for unknown keys.
        raise ValueError(
            f"freeze_params: sparse={sparse!r} must be True, False, or "
            "'auto'")
    bshape = block_shape or sparse_format.DEFAULT_BLOCK_SHAPE

    def freeze_leafdict(node):
        if isinstance(node, dict) and set(node) == {"w"}:
            w = node["w"]
            kw = {}
            if sparse is True:
                kw = {"sparse": True, "block_shape": bshape,
                      "max_live": max_live, "s_steps": s_steps}
                bounded = max_live is not None or s_steps is not None
                if bounded and not isinstance(w, jax.core.Tracer):
                    # The vmapped construction below traces even concrete
                    # stacks, which silences format.py's undersized-bound
                    # checks — enforce the caller's bounds host-side here so
                    # an overflowing layer raises instead of silently
                    # dropping live blocks.
                    m_live, m_steps, _ = _measure_stack(w, bshape)
                    if max_live is not None and m_live > max_live:
                        raise ValueError(
                            f"freeze_params: max_live={max_live} < {m_live}"
                            f" live blocks in a {tuple(w.shape)} layer stack;"
                            " pass a larger bound (or None for the full"
                            " grid)")
                    if s_steps is not None and m_steps > s_steps:
                        raise ValueError(
                            f"freeze_params: s_steps={s_steps} < {m_steps} "
                            f"live blocks in the fullest strip of a "
                            f"{tuple(w.shape)} layer stack; pass a larger "
                            "bound (or None for K/bk)")
            elif sparse == "auto" and not isinstance(w, jax.core.Tracer):
                kw = _sparse_prepass(w, bshape, max_live=max_live,
                                     s_steps=s_steps) or {}
            fn = layers.pack_linear
            if kw:
                fn = functools.partial(fn, **kw)
            for _ in range(w.ndim - 2):
                fn = jax.vmap(fn)
            return fn({"w": w})
        return node

    def walk(node):
        if isinstance(node, dict):
            out = freeze_leafdict(node)
            if out is not node:
                return out
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(params)


def init_packed_params(cfg, key) -> dict:
    """``freeze_params(model_zoo.init_params(cfg, key), sparse=False)`` as
    one jitted program, so the latent float32 tree never lives on the
    device beside the frozen one.

    At bitnet-2b-4t's widths the latent tree alone takes 11 GB, and freezing
    it eagerly needs more than one 16 GB v5e holds; compiled for v5e, this
    program needs 3.2 GB of output and 4.2 GB of temporaries.  It draws the
    same random values: the 2-bit planes equal the eager ones bit for bit,
    while float leaves (embeddings, head, scales) may differ in the last
    bits, where XLA fuses their arithmetic differently.  Traced weights are
    not measured, so no padded sparse pools are emitted (``sparse="auto"``
    emits none for random weights either).
    """
    return jax.jit(lambda k: freeze_params(model_zoo.init_params(cfg, k),
                                           sparse=False))(key)


def density_telemetry(params) -> dict | None:
    """Per-layer weight-density profile of a packed params tree (host-side).

    Returns ``sparse.stats.summarize`` output plus the full per-layer
    profile, or None when the tree has no packed/latent BitLinear leaves or
    is abstract (``jax.eval_shape``).  This is the serving-side surface of
    the density signal: operators see, per deployment, how far the
    checkpoint sits from the ``tsar_sparse`` break-even.
    """
    from repro.sparse import stats as sparse_stats

    try:
        profile = sparse_stats.profile_params(params)
    except (jax.errors.TracerArrayConversionError, TypeError):
        return None
    if not profile:
        return None
    out = sparse_stats.summarize(profile)
    out["profile"] = profile
    return out


def packed_fraction(params) -> float:
    """Diagnostic: fraction of param bytes in 2-bit packed form."""
    packed, total = 0, 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = [getattr(k, "key", "") for k in path]
        nb = leaf.size * leaf.dtype.itemsize
        total += nb
        if any(n in ("sign", "zero") for n in names):
            packed += nb * 8  # each packed byte stands for 8 weights
    return packed / max(total, 1)


# ---------------------------------------------------------------------------
# Jitted step bodies.  The flat step reads and writes the block pools in
# place; the chunked and whole-prompt steps run gather -> model -> scatter
# over a contiguous view, fused in one XLA program.
# ---------------------------------------------------------------------------

def _chunk_call(cfg, params, pools, table, tokens, pos, lengths, emit_idx):
    view = model_zoo.gather_cache_view(pools, table, cfg.n_kv_heads)
    logits, view = model_zoo.chunk_step(cfg, params, tokens, pos, view,
                                        lengths, train=False)
    pools = model_zoo.scatter_cache_view(pools, table, view)
    sel = jnp.take_along_axis(logits, emit_idx[:, None, None], axis=1)[:, 0]
    return sel, pools


def _flat_call(cfg, params, pools, table, tokens, slot, pos, emit_row):
    return model_zoo.flat_step(cfg, params, tokens, slot, pos, pools, table,
                               emit_row, train=False)


def _whole_prefill_call(cfg, params, pools, table, batch, slot):
    view = model_zoo.gather_cache_view(pools, table, cfg.n_kv_heads)
    slot_view = jax.tree.map(
        lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, 1), view)
    logits, slot_view = model_zoo.prefill(cfg, params, batch, slot_view,
                                          train=False)
    view = jax.tree.map(
        lambda full, sl: jax.lax.dynamic_update_index_in_dim(full, sl[:, 0], slot, 1),
        view, slot_view)
    pools = model_zoo.scatter_cache_view(pools, table, view)
    return logits[:, -1, :], pools


_CHUNKABLE_FAMILIES = ("dense", "moe")


class ServingEngine:
    def __init__(self, cfg, params, *, max_len: int = 512, batch_slots: int = 4,
                 packed: bool = False, cache_dtype=jnp.float32, seed: int = 0,
                 prefill_chunk: int = 16, block_size: int = 16,
                 kv_blocks: int | None = None, policy: str | None = None,
                 token_budget: int | None = None,
                 profile_density: bool = True,
                 plan: ModelPlan | None = None,
                 sparse: str | bool = "auto",
                 sparse_block: tuple | None = None,
                 prefix_cache: bool | int = False,
                 tracer=None, profiler_annotations: bool = False,
                 incidents=None, flight_recorder: bool | int = False):
        self.cfg = cfg
        self.params = (freeze_params(params, sparse=sparse,
                                     block_shape=sparse_block)
                       if packed else params)
        self.max_len = max_len
        self.slots = batch_slots
        self.key = jax.random.PRNGKey(seed)
        self.prefill_chunk = prefill_chunk
        if policy is None:
            policy = "flat" if cfg.family in _CHUNKABLE_FAMILIES else "whole"
        elif (policy in ("flat", "chunked")
              and cfg.family not in _CHUNKABLE_FAMILIES):
            # SSM recurrences / frontend prefills need the whole-prompt path;
            # refusing (rather than silently downgrading) keeps benchmark
            # labels honest.
            raise ValueError(
                f"policy={policy!r} is unsupported for family {cfg.family!r}; "
                "pass policy=None (auto) or 'whole'")
        self.policy = policy
        # TTFT-vs-TPOT knob for the flat policy: the static per-step token
        # budget T.  The default matches the rectangular bound
        # (prefill_chunk + slots), so flat serves the same worst-case real
        # work per step with almost none of the padding.
        if token_budget is None:
            token_budget = prefill_chunk + batch_slots
        if token_budget < batch_slots + 1:
            raise ValueError(
                f"token_budget={token_budget} < batch_slots + 1 "
                f"({batch_slots + 1}): every decode slot needs a row plus "
                "at least one prefill token")
        self.token_budget = token_budget
        self._extra = cfg.frontend_seq if cfg.family == "vlm" else 0

        self.kv = PagedKVCache(cfg, batch_slots, max_len, block_size=block_size,
                               num_blocks=kv_blocks, dtype=cache_dtype)
        self.sched = ChunkedScheduler(prefill_chunk=prefill_chunk)
        # Prefix-caching KV reuse (``serving.prefix_cache``): ``True`` turns
        # it on, an int additionally caps the cached-block footprint (LRU
        # evicted above it).  Reuse requires a chunk-capable path (a prefill
        # must be able to START at the fork boundary); for whole-prefill families
        # — SSM/hybrid recurrences carry non-block state, enc-dec/VLM
        # frontends carry non-token positions — hits cannot apply, so the
        # config degrades gracefully to a disabled cache whose telemetry
        # reports a 0.0 hit rate instead of refusing to serve.
        self.prefix: PrefixCache | None = None
        if prefix_cache and self.policy in ("flat", "chunked"):
            cap = (prefix_cache
                   if isinstance(prefix_cache, int)
                   and not isinstance(prefix_cache, bool) else None)
            self.prefix = PrefixCache(self.kv, capacity_blocks=cap)
        self._queue: list[Request] = []
        self._slots: list[SlotState | None] = [None] * batch_slots

        # -- observability (repro.obs) ----------------------------------------
        # The typed registry OWNS all run telemetry; ``stats`` below is a
        # write-through view over it under the legacy key names, so every
        # pre-existing key keeps its name, meaning, and mutability.  The
        # tracer defaults to the no-op recorder: every emit site guards on
        # ``tracer.enabled``, so an untraced engine pays one attribute read
        # per potential event and its counters stay bit-identical.
        if tracer is None and flight_recorder:
            # Always-on flight recorder: a ring-buffered tracer cheap enough
            # to leave enabled, so incident snapshots can dump the last N
            # events post-hoc.  An int picks the ring capacity.
            cap = (flight_recorder
                   if isinstance(flight_recorder, int)
                   and not isinstance(flight_recorder, bool)
                   else obs_trace.DEFAULT_RING_CAPACITY)
            tracer = obs_trace.EventTracer(sink=obs_trace.RingSink(cap))
        self.tracer = NULL_TRACER if tracer is None else tracer
        self._profile_steps = bool(profiler_annotations)
        self._phase: dict[int, str] = {}  # uid -> open lifecycle span (traced)
        self.sched.tracer = self.tracer
        self.kv.tracer = self.tracer
        if self.prefix is not None:
            self.prefix.tracer = self.tracer
        reg = self.metrics = MetricsRegistry()
        # Incident snapshots (repro.obs.incident): the monitor hooks sit
        # OUTSIDE the tracer.enabled guards and own no registry metrics, so
        # attaching one perturbs neither traced-vs-untraced bit-identity nor
        # the exact-gated benchmark counters.
        self.incidents = incidents
        self._evictions_seen = 0
        if incidents is not None:
            incidents.bind(registry=reg, tracer=self.tracer)
        self.kv.incidents = incidents
        t_step = reg.counter("step_time_s",
                             "wall seconds in jitted step calls, by phase",
                             labels=("phase",))
        self._t_prefill = t_step.labels(phase="prefill")
        self._t_decode = t_step.labels(phase="decode")
        self._c_steps = reg.counter("steps", "mixed chunk/decode engine steps")
        self._c_decode_tokens = reg.counter(
            "decode_tokens", "tokens emitted by pure-decode steps")
        self._c_total_tokens = reg.counter("total_tokens",
                                           "all emitted tokens")
        self._c_prefill_tokens = reg.counter(
            "prefill_tokens", "prompt tokens scheduled into chunks")
        self._c_whole_prefills = reg.counter(
            "whole_prefills", "single-call whole-prompt prefills")
        self._c_preemptions = reg.counter(
            "preemptions", "recompute-style slot preemptions")
        self._c_admissions = reg.counter(
            "admissions", "slot admissions (including re-admissions)")
        self._c_rejections = reg.counter(
            "rejections",
            "requests rejected at admission (prompt can never fit)")
        self._c_planned = reg.counter(
            "planned_tokens",
            "step-width rows the jitted call multiplies (flat: T; "
            "rectangular: padded B*C)")
        self._c_realized = reg.counter(
            "realized_tokens", "real (non-padding) tokens across steps")
        self._c_prefill_steps = reg.counter(
            "prefill_steps", "steps carrying a prefill chunk")
        self._c_decode_steps = reg.counter("decode_steps", "pure-decode steps")
        self._g_kv = reg.gauge(
            "kv_blocks", "pool blocks in use (peak -> peak_kv_blocks)")
        self._g_step_tokens = reg.gauge(
            "step_tokens", "real tokens of the last step "
                           "(peak -> max_step_tokens)")
        self._h_ttft = reg.histogram("ttft_s", "time to first token (s)")
        self._h_tpot = reg.histogram(
            "tpot_s", "mean time per output token after the first (s)")
        self._h_queue = reg.histogram(
            "queue_s", "submit -> first slot admission (s)")

        def _cv(m):
            # counter/gauge value with the legacy dict's write-through
            return (lambda: m.value, m.set)

        def _peak(g):
            # Legacy peak keys read the gauge's tracked peak; an external
            # write (the old reset idiom) rebases both value and peak.
            def setter(v):
                g.value = v
                g.peak = v
            return (lambda: g.peak, setter)

        self.stats = StatsView({
            "prefill_s": _cv(self._t_prefill),
            "decode_s": _cv(self._t_decode),
            "decode_tokens": _cv(self._c_decode_tokens),
            "total_tokens": _cv(self._c_total_tokens),
            "prefill_tokens": _cv(self._c_prefill_tokens),
            "steps": _cv(self._c_steps),
            "whole_prefills": _cv(self._c_whole_prefills),
            "preemptions": _cv(self._c_preemptions),
            "peak_kv_blocks": _peak(self._g_kv),
            "max_step_tokens": _peak(self._g_step_tokens),
        })
        # Bound AFTER the base view: the first ten legacy keys keep their
        # pinned order (tests assert it) while rejections still write
        # through to the registry like every other stat.
        self.stats.bind("rejections", *_cv(self._c_rejections))
        if prefix_cache:
            # Keys (and their registry metrics) exist whenever the cache was
            # REQUESTED (including the whole-policy degrade, where they stay
            # at zero) and never when it wasn't — a cache-off engine's stats
            # are unchanged.
            for key, m in (
                ("prefix_hit_rate", reg.gauge(
                    "prefix_hit_rate",
                    "fraction of admitted prompt tokens served from cache")),
                ("cached_blocks", reg.gauge(
                    "cached_blocks", "blocks held by the prefix-cache tree")),
                ("prefix_hit_tokens", reg.counter(
                    "prefix_hit_tokens", "prompt tokens served from cache")),
                ("prefix_lookups", reg.counter(
                    "prefix_lookups", "prefix-cache forks attempted")),
                ("prefix_evictions", reg.counter(
                    "prefix_evictions", "cached blocks evicted")),
            ):
                self.stats.bind(key, *_cv(m))
        # Density telemetry: measured once at init from the packed planes so
        # the sparse-dispatch signal is visible per deployment.  The profile
        # decodes one stacked layer slice at a time (bounded host transient)
        # but still walks every plane — pass profile_density=False to skip it
        # for latency-critical starts on very large models.
        self.density = (density_telemetry(self.params)
                        if packed and profile_density else None)
        if self.density is not None:
            self.stats["weight_density_mean"] = self.density["density_mean"]
            self.stats["block_density_mean"] = self.density["block_density_mean"]

        # Execution plan (paper Fig. 5 offline phase): compiled — or loaded,
        # when the caller passes a ``ModelPlan`` saved next to the checkpoint
        # — exactly once at init.  Every jitted step below runs inside
        # ``plan_runtime.activate(self.plan)``, so the packed BitLinear
        # dispatch is a trace-time plan lookup and ZERO ``select_kernel``
        # calls happen after this constructor returns.
        supplied = plan is not None
        if plan is None and packed:
            plan = compile_plan(self.params, BatchProfile(
                decode_ns=(1, batch_slots),
                prefill_ns=(prefill_chunk,
                            batch_slots * (prefill_chunk + 1),
                            token_budget)))
        self.plan = plan
        if self.plan is not None:
            self.stats["plan_layers"] = len(self.plan.layers)
            # Shapes shared by layers with conflicting plans fall back to the
            # default realization (the shape-keyed serve lookup can't tell
            # them apart) — surface the count so operators notice.
            self.stats["plan_shape_conflicts"] = len(self.plan.shape_conflicts())
        if supplied:
            # A loaded plan is only as good as its match to THIS model: a
            # plan saved for another config resolves nothing and would
            # silently serve every layer un-planned while telemetry claims
            # otherwise.
            if not packed:
                warnings.warn(
                    "repro.serving.ServingEngine: a ModelPlan was supplied "
                    "but packed=False — qat serving never consults the plan",
                    UserWarning, stacklevel=2)
            else:
                matched, total = self.plan.coverage(self.params)
                self.stats["plan_matched_layers"] = matched
                if matched < total:
                    warnings.warn(
                        f"repro.serving.ServingEngine: supplied plan resolves "
                        f"only {matched}/{total} BitLinear layers of this "
                        f"model; unmatched layers run the default realization "
                        f"(was the plan compiled for a different config?)",
                        UserWarning, stacklevel=2)

        # Donating the pools lets XLA update the block pools in place instead
        # of holding input + output copies alive across the step (on backends
        # without aliasing support jax falls back to a copy with a warning).
        chunk_jit = jax.jit(
            lambda p, pools, tbl, tk, ps, ln, ei:
            _chunk_call(cfg, p, pools, tbl, tk, ps, ln, ei),
            donate_argnums=(1,))
        flat_jit = jax.jit(
            lambda p, pools, tbl, tk, sl, ps, er:
            _flat_call(cfg, p, pools, tbl, tk, sl, ps, er),
            donate_argnums=(1,))
        prefill_jit = jax.jit(
            lambda p, pools, tbl, b, i:
            _whole_prefill_call(cfg, p, pools, tbl, b, i),
            donate_argnums=(1,))

        def _planned(fn):
            def call(*args):
                with plan_runtime.activate(self.plan):
                    return fn(*args)
            return call

        self._chunk_fn = _planned(chunk_jit)
        self._flat_fn = _planned(flat_jit)
        self._prefill_fn = _planned(prefill_jit)

    # -- request management --------------------------------------------------

    def submit(self, req: Request):
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        tr = self.tracer
        if tr.enabled:
            tr.begin(req.uid, "req", prompt_len=len(req.prompt),
                     max_new_tokens=req.max_new_tokens)
            tr.begin(req.uid, "queued")
            self._phase[req.uid] = "queued"
        self._queue.append(req)

    def _admit(self):
        rej0 = self.sched.rejections
        admitted = self.sched.admit(self._slots, self._queue, self.kv,
                                    extra_positions=self._extra,
                                    reserve_full=self.policy == "whole",
                                    prefix_cache=self.prefix)
        if self.sched.rejections > rej0:
            # Mirror scheduler rejections (prompt-too-long, finished-ignored
            # at admission) into the registry so goodput denominators and
            # ``stats["rejections"]`` stay honest.
            n_rej = self.sched.rejections - rej0
            self._c_rejections.inc(n_rej)
            if self.incidents is not None:
                self.incidents.observe("rejection", n=n_rej,
                                       queue_len=len(self._queue))
        tr = self.tracer
        for i, st in admitted:
            self._c_admissions.inc()
            if st.req.t_admit is None:
                # First admission only: queueing latency measures the wait
                # for a slot, not re-admission churn after preemption.
                st.req.t_admit = time.perf_counter()
                self._h_queue.observe(st.req.queue_s)
            if tr.enabled:
                # The scheduler already closed the queued span and marked
                # the admission; the prefill phase starts here.
                tr.begin(st.req.uid, "prefill", slot=i,
                         cached_len=st.cached_len)
                self._phase[st.req.uid] = "prefill"
            if self.policy == "whole":
                self._prefill_slot(i, st)
            # chunked: the scheduler interleaves this prompt's chunks with
            # running decodes from the next step() on; a prefix-cache hit
            # already forked the cached leading blocks and advanced the
            # slot's cursor to the fork boundary.

    # -- prefix-cache registration -------------------------------------------

    def _register_prefix(self, i: int, st: SlotState):
        """Register slot ``i``'s current cache content with the prefix
        cache.  The content is exactly ``req.prompt + out_tokens[:-1]``
        truncated to the live length (the final sampled token is emitted but
        its KV row is never written); only FULL blocks are registered, so a
        later writer of the slot's partial tail block never mutates a cached
        block."""
        if self.prefix is None:
            return
        req = st.req
        content = np.concatenate([np.asarray(req.prompt, np.int32),
                                  np.asarray(req.out_tokens, np.int32)])
        content = content[:int(self.kv.lengths[i])]
        self.prefix.insert(content, self.kv.table[i])

    def _sync_prefix_stats(self):
        if self.prefix is not None:
            self.stats.update(self.prefix.stats())

    def _prefill_slot(self, i: int, st: SlotState):
        """Whole-prompt prefill of one slot through the paged cache."""
        cfg = self.cfg
        batch = {"tokens": jnp.asarray(st.prompt, jnp.int32)[None, :]}
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (1, cfg.frontend_seq, cfg.frontend_dim), jnp.float32)
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((1, cfg.enc_seq, cfg.d_model), jnp.float32)
        table = self.kv.table_view(self.kv.max_blocks)
        t0 = time.perf_counter()
        sel, self.kv.pools = self._prefill_fn(
            self.params, self.kv.pools, table, batch, jnp.int32(i))
        sel.block_until_ready()
        dt = time.perf_counter() - t0
        self._t_prefill.inc(dt)
        self._c_whole_prefills.inc()
        self._g_step_tokens.set(len(st.prompt) + st.extra)
        if self.tracer.enabled:
            self.tracer.mark(st.req.uid, "prefill_chunk",
                             n=len(st.prompt), start=0, whole=True)
        self.kv.lengths[i] = len(st.prompt) + st.extra
        st.cursor = len(st.prompt)
        tok = int(self._sample(sel, np.array([st.req.temperature]))[0])
        self._emit_token(i, st, tok)

    # -- sampling -------------------------------------------------------------

    def _sample(self, logits, temps: np.ndarray) -> np.ndarray:
        """Per-slot sampling: greedy rows stay deterministic argmax, rows with
        ``temperature > 0`` draw from the tempered categorical (this fixes the
        seed engine's decode path, which ignored request temperatures)."""
        greedy = jnp.argmax(logits, axis=-1)
        if not (temps > 0).any():
            return np.asarray(greedy)
        self.key, sub = jax.random.split(self.key)
        t = jnp.asarray(np.where(temps > 0, temps, 1.0), jnp.float32)
        samp = jax.random.categorical(sub, logits / t[:, None], axis=-1)
        return np.asarray(jnp.where(jnp.asarray(temps > 0), samp, greedy))

    def _emit_token(self, i: int, st: SlotState, tok: int):
        req = st.req
        req.out_tokens.append(tok)
        tr = self.tracer
        first = req.t_first is None
        if first:
            req.t_first = time.perf_counter()
            self._h_ttft.observe(req.ttft)
            if self.incidents is not None:
                self.incidents.request_first_token(req)
        if tr.enabled:
            # A token emission always means the prompt is fully in cache —
            # close the prefill phase (also after a re-prefill following
            # preemption, where it isn't the request's first token).
            if self._phase.get(req.uid) == "prefill":
                tr.end(req.uid, "prefill")
                tr.begin(req.uid, "decode")
                self._phase[req.uid] = "decode"
            if first:
                tr.mark(req.uid, "first_token")
        self._c_total_tokens.inc()
        if (len(req.out_tokens) >= req.max_new_tokens
                or self.kv.lengths[i] >= self.max_len - 1):
            req.done = True
            req.t_done = time.perf_counter()
            self._h_tpot.observe(req.tpot)
            if self.incidents is not None:
                self.incidents.request_finished(req)
            if tr.enabled:
                tr.end(req.uid, "decode")
                tr.mark(req.uid, "finished", n_out=len(req.out_tokens),
                        preemptions=req.n_preempted)
                tr.end(req.uid, "req")
                self._phase.pop(req.uid, None)
            # Register prompt + generated tokens (multi-turn reuse: a
            # follow-up request quoting this conversation hits them) while
            # the slot still holds its block references.
            self._register_prefix(i, st)
            self.kv.free_slot(i)
            self._slots[i] = None
        else:
            st.last_tok = tok

    # -- main loop ------------------------------------------------------------

    def step(self) -> bool:
        """One engine step: admit, then one mixed prefill-chunk/decode call.
        Returns False when there was nothing to do.

        With ``profiler_annotations`` each host phase is a span on the
        profiler's clock (``obs.trace.ENGINE_SPANS``): admit, plan, the
        dispatch and the wait inside ``tsar_engine_step``, sample, emit."""
        span, off = obs_trace.profiler_span, obs_trace.NULL_SPAN
        prof = self._profile_steps
        step_no = self._c_steps.value
        with span("engine.admit", step_no) if prof else off:
            self._admit()
        flat = self.policy == "flat"

        def _plan():
            if flat:
                return self.sched.plan_flat(self._slots, self.kv,
                                            self.token_budget)
            return self.sched.plan(self._slots, self.kv)

        with span("engine.plan", step_no) if prof else off:
            plan = _plan()
            while isinstance(plan, Preempt):
                self._preempt(plan.slot)
                plan = _plan()
            if plan is None:
                return False
            table = self.kv.table_view(plan.view_blocks)
        # planned = the static step width: the rows the jitted matmuls
        # actually multiply (flat: T; rectangular: the padded B*C).
        # realized/planned is the step-budget utilization the timeline CLI
        # reports; 1 - it is exactly the padding waste the flat layout
        # removes.
        planned = plan.width if flat else self.slots * plan.chunk
        t0 = time.perf_counter()
        with span(obs_trace.STEP_SPAN, step_no) if prof else off:
            with span("engine.dispatch", step_no) if prof else off:
                if flat:
                    sel, self.kv.pools = self._flat_fn(
                        self.params, self.kv.pools, table,
                        jnp.asarray(plan.tokens), jnp.asarray(plan.slot),
                        jnp.asarray(plan.pos), jnp.asarray(plan.emit_row))
                else:
                    sel, self.kv.pools = self._chunk_fn(
                        self.params, self.kv.pools, table,
                        jnp.asarray(plan.tokens), jnp.asarray(plan.pos),
                        jnp.asarray(plan.lengths), jnp.asarray(plan.emit_idx))
            with span("engine.wait", step_no) if prof else off:
                sel.block_until_ready()
        dt = time.perf_counter() - t0

        self._c_steps.inc()
        self._c_planned.inc(planned)
        self._c_realized.inc(plan.real_tokens)
        self._g_step_tokens.set(plan.real_tokens)
        self._g_kv.set(int(self.kv.blocks_in_use))
        self._c_prefill_tokens.inc(plan.prefill_tokens)
        if plan.prefill_tokens > 0:
            self._t_prefill.inc(dt)
            self._c_prefill_steps.inc()
        else:
            self._t_decode.inc(dt)
            self._c_decode_steps.inc()
            self._c_decode_tokens.inc(plan.decode_tokens)

        tr = self.tracer
        if tr.enabled:
            tr.step(dt, step=step_no, planned=planned,
                    realized=plan.real_tokens,
                    prefill_tokens=plan.prefill_tokens,
                    decode_tokens=plan.decode_tokens,
                    kv_blocks=int(self.kv.blocks_in_use),
                    active_slots=sum(1 for s in self._slots if s is not None),
                    kernel=(self.plan.dominant_kernel(planned)
                            if self.plan is not None else None))

        toks = None
        if plan.emit.any():
            with span("engine.sample", step_no) if prof else off:
                temps = np.array([
                    self._slots[i].req.temperature if plan.emit[i] else 0.0
                    for i in range(self.slots)], np.float32)
                toks = self._sample(sel, temps)
        with span("engine.emit", step_no) if prof else off:
            self._emit_step(plan, toks)
        return True

    def _emit_step(self, plan, toks):
        """Land one step's tokens: advance each slot, emit, register
        prefixes, then the prefix stats and the incident tick."""
        tr = self.tracer
        for i in range(self.slots):
            st = self._slots[i]
            if st is None or plan.n_real[i] == 0:
                continue
            self.kv.lengths[i] += int(plan.n_real[i])
            advanced = plan.advances_prefill(i)
            if advanced:
                if tr.enabled:
                    tr.mark(st.req.uid, "prefill_chunk",
                            n=int(plan.n_real[i]), start=st.cursor)
                st.cursor += int(plan.n_real[i])
            if plan.emit[i]:
                self._emit_token(i, st, int(toks[i]))
            if advanced and not st.prefilling and self._slots[i] is not None:
                # Prompt fully in cache and the request is still live:
                # register its full blocks NOW so requests sharing this
                # prefix hit it while this one is still decoding
                # (system-prompt sharing, the dominant multi-tenant
                # pattern).  Checked AFTER the emit: a request finishing on
                # its first sampled token was already registered by
                # ``_emit_token`` — registering here too would walk the tree
                # twice for the same content (satellite fix, pinned in
                # tests/test_prefix_cache.py).
                self._register_prefix(i, st)
        self._sync_prefix_stats()
        if self.incidents is not None:
            ev = (int(self.stats["prefix_evictions"])
                  if self.prefix is not None else 0)
            self.incidents.step_tick(
                evictions=max(0, ev - self._evictions_seen))
            self._evictions_seen = ev

    def _preempt(self, i: int):
        """Recompute-style preemption (vLLM): return the youngest request to
        the queue head; its prompt + generated tokens re-prefill later.

        Before the victim's blocks are released, its already-computed FULL
        blocks are registered into the prefix cache (when one is attached):
        the blocks exist and are correct whether or not the prefill ever
        finished, so recompute-preemption's re-admission forks them back and
        re-prefills only the partial tail — preempting a request no longer
        throws away the prefill work it already paid for (the cached blocks
        stay evictable, so under real pressure the allocator can still
        reclaim them before any live request is preempted)."""
        st = self._slots[i]
        tr = self.tracer
        if tr.enabled:
            uid = st.req.uid
            ph = self._phase.get(uid)
            if ph in ("prefill", "decode"):
                tr.end(uid, ph, preempted=True)
            tr.mark(uid, "preempted", slot=i, cursor=st.cursor,
                    cached_len=st.cached_len)
            tr.begin(uid, "queued")
            self._phase[uid] = "queued"
        self._register_prefix(i, st)
        self.kv.free_slot(i)
        self._slots[i] = None
        self._queue.insert(0, st.req)
        self._c_preemptions.inc()
        st.req.n_preempted += 1
        if self.incidents is not None:
            self.incidents.observe("preemption", uid=st.req.uid, slot=i,
                                   cursor=st.cursor,
                                   n_preempted=st.req.n_preempted)

    @property
    def busy(self) -> bool:
        """True while any request is queued or resident in a slot."""
        return bool(self._queue) or any(s is not None for s in self._slots)

    @property
    def queue_len(self) -> int:
        return len(self._queue)

    def run(self, requests: list[Request]) -> list[Request]:
        for r in requests:
            self.submit(r)
        while self.busy:
            if not self.step() and self._queue:
                # Every slot is free yet the head-of-queue request still
                # failed the admission gate: the pool can never cover it.
                raise RuntimeError(
                    f"request uid={self._queue[0].uid} cannot be admitted: "
                    f"KV pool ({self.kv.num_blocks - 1} blocks of "
                    f"{self.kv.block_size}) smaller than the admission gate; "
                    "raise kv_blocks or lower prefill_chunk/max_len")
        return requests

    # -- benchmarking hooks ---------------------------------------------------

    def warmup(self, seq_len: int | None = None) -> None:
        """Compile the jitted step paths (prefill-chunk width, pure decode,
        and the block-table view buckets up to ``seq_len`` total positions)
        on a throwaway request, then :meth:`reset_run_stats` — so benchmark
        percentiles measure steady-state serving rather than XLA compile
        time.  Must be called on an idle engine."""
        if self.busy:
            raise RuntimeError("warmup() requires an idle engine")
        total = seq_len or (self.prefill_chunk + 3)
        # Leave room for the generated tokens + the headroom position.
        plen = max(2, min(total - 2, self.max_len - self._extra - 3))
        rng = np.random.default_rng(0x7e57)
        prompt = rng.integers(0, self.cfg.vocab_size, size=plen,
                              dtype=np.int32)
        self.run([Request(uid=-1, prompt=prompt, max_new_tokens=2)])
        self.reset_run_stats()

    def reset_run_stats(self) -> None:
        """Zero the per-run counters, drop any prefix-cache state, and clear
        recorded trace events, keeping init-time telemetry (plan/density
        keys).  Peak gauges (``peak_kv_blocks``/``max_step_tokens``) are
        REBASED to the post-reset live values rather than blindly zeroed, so
        warm-up can never leak into steady-state peaks while state the
        engine genuinely still holds is never undercounted.  Requires an
        idle engine; used by the workload runner after :meth:`warmup`."""
        if self.busy:
            raise RuntimeError("reset_run_stats() requires an idle engine")
        if self.prefix is not None:
            # All slots are free, so every cached block is evictable; a
            # fresh tree also resets the hit/miss telemetry.
            self.prefix.evict(self.prefix.cached_blocks, cause="reset")
            self.prefix = PrefixCache(self.kv,
                                      capacity_blocks=self.prefix.capacity)
            self.prefix.tracer = self.tracer
        self.sched.prefill_tokens_planned = 0
        self.sched.cached_tokens_skipped = 0
        self.sched.readmissions = 0
        self.sched.rejections = 0
        # Refresh gauge values to post-reset reality FIRST, then let the
        # registry reset counters/histograms and rebase every gauge peak to
        # its current value.
        self._g_kv.set(int(self.kv.blocks_in_use))
        self._g_step_tokens.set(0)
        self.metrics.reset_run()
        self._sync_prefix_stats()
        # A streaming sink truncates its on-disk segments here too, so
        # warm-up events never leak into saved long-run traces.
        self.tracer.reset()
        self._evictions_seen = 0
        if self.incidents is not None:
            # Warm-up incidents (e.g. a compile-inflated TTFT breach) are
            # noise: discard their files and re-arm the debouncing.
            self.incidents.reset_run()

    # -- metrics --------------------------------------------------------------

    def throughput(self) -> float:
        """Steady-state decode tokens/s (pure-decode steps only)."""
        return self.stats["decode_tokens"] / max(self.stats["decode_s"], 1e-9)

    def max_step_tokens(self) -> int:
        return self.stats["max_step_tokens"]

    def latency_stats(self, requests: list[Request]) -> dict:
        """Aggregate TTFT/TPOT over finished requests (seconds)."""
        ttfts = [r.ttft for r in requests if r.ttft is not None]
        tpots = [r.tpot for r in requests if r.tpot is not None]
        mean = lambda xs: sum(xs) / len(xs) if xs else float("nan")
        return {
            "ttft_mean_s": mean(ttfts),
            "ttft_max_s": max(ttfts, default=float("nan")),
            "tpot_mean_s": mean(tpots),
            "n": len(ttfts),
        }

    def latency_percentiles(self) -> dict:
        """{ttft_s, tpot_s, queue_s} -> {p50, p90, p99, mean, max, n} from
        the registry histograms — tail latencies straight off the engine,
        no external runner replay required."""
        return {name: self.metrics.get(name).summary()
                for name in ("ttft_s", "tpot_s", "queue_s")}
