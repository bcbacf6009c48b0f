"""The observability subsystem (``repro.obs``): typed metrics registry,
``engine.stats`` back-compat view, event tracer + Perfetto export, timeline
analysis, and the engine wiring contracts:

* tracing OFF is the default and near-free — an untraced engine runs the
  no-op recorder and its deterministic counters are bit-identical to a
  traced twin on the same workload trace;
* tracing ON yields a deterministic event *structure* — same-seed replays
  produce identical structure fingerprints (wall clock lives only in
  ts/dur), and every request's span sequence is well-formed
  (property-tested with hypothesis);
* ``reset_run_stats`` REBASES peak gauges to current state instead of
  zeroing them (the satellite fix pinned here);
* per-machine SLO calibration scales ``is_good`` thresholds and is recorded
  in the report provenance.
"""
import json
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.obs import NULL_TRACER, MetricsRegistry, StatsView
from repro.obs import timeline
from repro.obs import trace as obs_trace
from repro.obs.metrics import Counter, Gauge, Histogram
from repro.obs.trace import EventTracer


# ---------------------------------------------------------------------------
# metrics registry (pure, no jax)
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter(self):
        c = Counter("x")
        c.inc()
        c.inc(4)
        assert c.value == 5
        c.set(2)          # legacy write-through hook
        assert c.value == 2
        c.reset()
        assert c.value == 0

    def test_gauge_tracks_peak_and_rebases(self):
        g = Gauge("kv")
        g.set(7)
        g.set(3)
        assert (g.value, g.peak) == (3, 7)
        g.reset_peak()    # REBASE to current, not zero
        assert (g.value, g.peak) == (3, 3)
        g.set(5)
        assert g.peak == 5

    def test_histogram_summary(self):
        h = Histogram("lat")
        empty = h.summary()
        # empty histograms return an explicit NaN-free sentinel, not NaN
        assert empty["n"] == 0 and empty["empty"] is True
        assert empty["p50"] == 0.0 and empty["mean"] == 0.0
        assert not any(isinstance(v, float) and math.isnan(v)
                       for v in empty.values())
        for v in range(1, 101):
            h.observe(v / 100.0)
        h.observe(None)   # ignored, like an unfinished request's ttft
        s = h.summary()
        assert s["n"] == 100
        assert s["p50"] == pytest.approx(0.505, abs=0.01)
        assert s["p99"] <= s["max"] == 1.0
        assert h.percentile(50) == pytest.approx(s["p50"])

    def test_histogram_bounds_memory(self):
        h = Histogram("x", max_obs=8)
        for v in range(10):
            h.observe(v)
        assert h.count <= 8
        assert h.summary()["max"] == 9.0   # recent half survives

    def test_registry_typed_redeclare(self):
        reg = MetricsRegistry()
        c = reg.counter("steps")
        assert reg.counter("steps") is c          # declare-or-get
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("steps")
        f = reg.counter("t", labels=("phase",))
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("t")                      # labeled vs not

    def test_labels(self):
        reg = MetricsRegistry()
        f = reg.counter("step_time_s", labels=("phase",))
        f.labels(phase="prefill").inc(2.0)
        f.labels(phase="decode").inc(1.0)
        assert f.labels(phase="prefill").value == 2.0
        with pytest.raises(ValueError, match="declared labels"):
            f.labels(stage="prefill")
        snap = reg.snapshot()
        assert snap["step_time_s{phase=prefill}"] == 2.0

    def test_reset_run_semantics(self):
        reg = MetricsRegistry()
        c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
        c.inc(3)
        g.set(9)
        g.set(4)
        h.observe(1.0)
        reg.reset_run()
        assert c.value == 0
        assert (g.value, g.peak) == (4, 4)   # rebased, not zeroed
        assert h.count == 0
        snap = reg.snapshot()
        assert snap["g_peak"] == 4 and snap["c"] == 0


class TestStatsView:
    def _view(self):
        c = Counter("decode_tokens")
        g = Gauge("kv")
        v = StatsView({"decode_tokens": (lambda: c.value, c.set),
                       "peak_kv": (lambda: g.peak, None)})
        return v, c, g

    def test_read_write_through(self):
        v, c, g = self._view()
        c.inc(5)
        assert v["decode_tokens"] == 5
        v["decode_tokens"] = 0        # legacy reset idiom writes through
        assert c.value == 0
        v.update(decode_tokens=7)
        assert c.value == 7

    def test_read_only_key_raises(self):
        v, _, g = self._view()
        g.set(3)
        assert v["peak_kv"] == 3
        with pytest.raises(KeyError, match="read-only"):
            v["peak_kv"] = 0

    def test_extra_keys_and_order(self):
        v, _, _ = self._view()
        v["plan_layers"] = 4          # unknown key -> side dict
        assert list(v) == ["decode_tokens", "peak_kv", "plan_layers"]
        assert dict(v)["plan_layers"] == 4
        assert "plan_layers" in v and len(v) == 3
        del v["plan_layers"]
        assert "plan_layers" not in v


# ---------------------------------------------------------------------------
# tracer + document schema (pure, no jax)
# ---------------------------------------------------------------------------

def _tick():
    """Deterministic fake clock: one unit per call."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    return clock


class TestTracer:
    def test_null_tracer_is_inert(self):
        assert NULL_TRACER.enabled is False
        assert NULL_TRACER.begin(1, "req") is None
        assert NULL_TRACER.step(0.1, planned=4) is None
        NULL_TRACER.reset()
        assert not hasattr(NULL_TRACER, "events")

    def test_event_shapes(self):
        tr = EventTracer(clock=_tick())
        tr.begin(3, "req", prompt_len=5)
        tr.mark(3, "admitted", slot=0, cached_len=0, readmission=False)
        tr.instant("kv_pressure", need=2, free=1)
        tr.step(0.5, planned=8, realized=5, kv_blocks=3, active_slots=2)
        tr.end(3, "req")
        phs = [e["ph"] for e in tr.events]
        # step emits X + one counter sample per known series
        assert phs == ["b", "n", "i", "X", "C", "C", "C", "e"]
        x = tr.events[3]
        assert x["dur"] == pytest.approx(0.5e6)
        assert x["ts"] + x["dur"] == pytest.approx(tr.events[2]["ts"] + 1e6)
        names = {e["name"] for e in tr.events if e["ph"] == "C"}
        assert names == {"step_tokens", "kv_blocks", "active_slots"}
        for e in tr.events:
            if e["ph"] in ("b", "e", "n"):
                assert e["cat"] == "req" and e["id"] == 3

    def test_reset_drops_events_and_rebases_epoch(self):
        tr = EventTracer(clock=_tick())
        tr.begin(1, "req")
        first_ts = tr.events[0]["ts"]
        tr.reset()
        assert tr.events == []
        tr.begin(2, "req")
        # epoch rebased: second trace starts near zero again
        assert tr.events[0]["ts"] == pytest.approx(first_ts)

    def test_fingerprint_ignores_wall_clock_only(self):
        def record(clock):
            tr = EventTracer(clock=clock)
            tr.begin(1, "req")
            tr.step(0.1, planned=4, realized=4)
            tr.end(1, "req")
            return tr

        a, b = record(_tick()), record(lambda t=[0.0]: (t.__setitem__(
            0, t[0] + 17.3) or t[0]))
        fa = obs_trace.structure_fingerprint(a.events)
        assert fa == obs_trace.structure_fingerprint(b.events)
        # ...but any structural change shifts it
        c = record(_tick())
        c.events[1]["args"]["planned"] = 5
        assert obs_trace.structure_fingerprint(c.events) != fa

    def test_save_load_validate_roundtrip(self, tmp_path):
        tr = EventTracer(clock=_tick())
        tr.begin(1, "req")
        tr.step(0.2, planned=4, realized=3)
        tr.end(1, "req")
        p = tmp_path / "trace.json"
        doc = tr.save(str(p), rev="testrev")
        od = doc["otherData"]
        assert od["kind"] == obs_trace.TRACE_KIND
        assert od["schema_version"] == obs_trace.TRACE_SCHEMA_VERSION
        assert od["git_rev"] == "testrev"
        loaded = obs_trace.load(str(p))
        assert loaded == doc
        # canonical serialization round-trips byte-exact
        assert obs_trace.dumps(loaded) == p.read_text()
        # metadata events name the process/threads for the Perfetto UI
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {m["name"] for m in metas} == {"process_name", "thread_name"}

    def test_validate_rejects_tampering(self, tmp_path):
        tr = EventTracer(clock=_tick())
        tr.begin(1, "req")
        tr.end(1, "req")
        doc = tr.to_perfetto(rev="x")
        obs_trace.validate(doc)
        bad = json.loads(json.dumps(doc))
        bad["traceEvents"][-1]["args"]["injected"] = True
        with pytest.raises(ValueError, match="fingerprint"):
            obs_trace.validate(bad)
        bad = json.loads(json.dumps(doc))
        bad["traceEvents"][-1]["ph"] = "Z"
        with pytest.raises(ValueError, match="unknown phase"):
            obs_trace.validate(bad)
        bad = json.loads(json.dumps(doc))
        del bad["otherData"]["kind"]
        with pytest.raises(ValueError, match="kind"):
            obs_trace.validate(bad)

    def test_step_annotation_is_context_manager(self):
        # The engine step's profiler span, on and off, is a context manager.
        with obs_trace.profiler_span(obs_trace.STEP_SPAN, 3):
            pass
        with obs_trace.NULL_SPAN:
            pass


# ---------------------------------------------------------------------------
# timeline analysis CLI (synthetic docs, no jax)
# ---------------------------------------------------------------------------

def _synthetic_tracer():
    """A hand-built lifecycle with one preemption and one prefix hit."""
    tr = EventTracer(clock=_tick())
    tr.begin(1, "req", prompt_len=8)
    tr.begin(1, "queued")
    tr.end(1, "queued")
    tr.mark(1, "admitted", slot=0, cached_len=4, readmission=False)
    tr.mark(1, "prefix_hit", cached_len=4)
    tr.begin(1, "prefill", slot=0, cached_len=4)
    tr.step(0.2, step=0, planned=8, realized=6, prefill_tokens=4,
            decode_tokens=2, kv_blocks=3, active_slots=1, kernel="tsar_mxu")
    tr.instant("kv_pressure", slot=0, need=2, free=0)
    tr.end(1, "prefill", preempted=True)
    tr.mark(1, "preempted", slot=0, cursor=4, cached_len=4)
    tr.begin(1, "queued")
    tr.end(1, "queued")
    tr.mark(1, "admitted", slot=0, cached_len=4, readmission=True)
    tr.begin(1, "prefill", slot=0, cached_len=4)
    tr.end(1, "prefill")
    tr.begin(1, "decode")
    tr.mark(1, "first_token")
    tr.step(0.1, step=1, planned=2, realized=2, prefill_tokens=0,
            decode_tokens=2, kv_blocks=4, active_slots=1, kernel="tsar_mxu")
    tr.end(1, "decode")
    tr.mark(1, "finished", n_out=3, preemptions=1)
    tr.end(1, "req")
    return tr


class TestTimeline:
    def test_analyze_synthetic(self):
        doc = _synthetic_tracer().to_perfetto(rev="x")
        s = timeline.analyze(doc)
        st_ = s["steps"]
        assert st_["n"] == 2 and st_["prefill"] == 1 and st_["decode"] == 1
        assert st_["planned_tokens"] == 10 and st_["realized_tokens"] == 8
        assert st_["budget_utilization"] == pytest.approx(0.8)
        assert st_["kernel_steps"] == {"tsar_mxu": 2}
        assert s["n_requests"] == 1
        assert s["spans_us"]["queued"]["n"] == 2
        assert s["spans_us"]["prefill"]["n"] == 2
        pre = s["preemptions"]
        assert pre["n"] == 1 and pre["readmitted"] == 1
        chain = pre["chains"][0]
        assert chain["cause"]["event"] == "kv_pressure"
        assert chain["finished"]
        assert s["prefix"] == {"hits": 1, "hit_tokens": 4, "inserts": 0,
                               "evictions_by_cause": {}}
        assert s["kv_pressure_events"] == 1
        # the text renderer handles the full summary without crashing
        txt = timeline.format_summary(s)
        assert "budget utilization: 80.0%" in txt

    def test_cli_require_gate(self, tmp_path, capsys):
        p = tmp_path / "t.json"
        _synthetic_tracer().save(str(p), rev="x")
        assert timeline.main([str(p)]) == 0
        assert timeline.main([str(p), "--require", "prefill-span",
                              "decode-span", "prefix-hit", "preemption",
                              "step"]) == 0
        capsys.readouterr()
        assert timeline.main([str(p), "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["steps"]["n"] == 2
        # a step-only trace misses the lifecycle features -> exit 1
        tr = EventTracer(clock=_tick())
        tr.step(0.1, planned=2, realized=2)
        q = tmp_path / "steps.json"
        tr.save(str(q), rev="x")
        assert timeline.main([str(q), "--require", "prefill-span"]) == 1
        assert "MISSING" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# engine integration (reduced model)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    import jax

    import repro.configs as configs
    from repro.models import model_zoo as zoo

    cfg = configs.get("bitnet-2b-4t").reduced()
    return cfg, zoo.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def storm(model):
    """The preemption-storm quick trace replayed three ways: traced twice
    (same seed — structure must reproduce) and untraced (counters must be
    bit-identical to the traced runs)."""
    from benchmarks.workloads import runner
    from benchmarks.workloads.generator import generate, preset

    cfg, params = model
    spec = preset("preemption-storm", quick=True)
    trace = generate(spec)
    t1, t2 = EventTracer(), EventTracer()
    b1, e1, r1 = runner.run_workload(spec, cfg, params, trace=trace,
                                     tracer=t1)
    b2, e2, r2 = runner.run_workload(spec, cfg, params, trace=trace,
                                     tracer=t2)
    b0, e0, r0 = runner.run_workload(spec, cfg, params, trace=trace)
    return {"spec": spec, "trace": trace, "tracers": (t1, t2),
            "blocks": (b1, b2, b0), "engines": (e1, e2, e0),
            "reqs": (r1, r2, r0)}


def _spans_by_uid(events):
    seq: dict = {}
    for e in events:
        if e.get("ph") in ("b", "e", "n"):
            seq.setdefault(e["id"], []).append((e["ph"], e["name"], e))
    return seq


class TestEngineTracing:
    def test_untraced_engine_runs_null_tracer(self, storm):
        e0 = storm["engines"][2]
        assert e0.tracer is NULL_TRACER
        assert not hasattr(e0.tracer, "events")

    def test_tracing_off_counters_bit_identical(self, storm):
        """The near-zero-overhead contract, in its strongest observable
        form: attaching a tracer changes NO deterministic counter and no
        emitted token."""
        b1, _, b0 = storm["blocks"]
        r1, _, r0 = storm["reqs"]
        assert b1["counters"] == b0["counters"]
        assert b1["trace_fingerprint"] == b0["trace_fingerprint"]
        assert [r.out_tokens for r in r1] == [r.out_tokens for r in r0]

    def test_same_seed_replay_identical_structure(self, storm):
        t1, t2 = storm["tracers"]
        assert len(t1.events) == len(t2.events)
        assert (obs_trace.structure_fingerprint(t1.events)
                == obs_trace.structure_fingerprint(t2.events))

    def test_storm_trace_contains_lifecycle(self, storm):
        t1 = storm["tracers"][0]
        names = {(e["ph"], e["name"]) for e in t1.events}
        for needed in (("b", "req"), ("b", "queued"), ("b", "prefill"),
                       ("b", "decode"), ("n", "admitted"),
                       ("n", "first_token"), ("n", "finished"),
                       ("n", "preempted"), ("n", "prefix_hit"),
                       ("X", "step")):
            assert needed in names, f"missing {needed}"
        # preempted marks match the engine's preemption counter
        n_pre = sum(1 for e in t1.events
                    if e.get("ph") == "n" and e["name"] == "preempted")
        assert n_pre == storm["blocks"][0]["counters"]["preemptions"] > 0

    def test_timestamps_monotone_per_track(self, storm):
        t1 = storm["tracers"][0]
        by_tid: dict = {}
        for e in t1.events:
            by_tid.setdefault(e["tid"], []).append(e["ts"])
        for tid, ts in by_tid.items():
            assert all(a <= b for a, b in zip(ts, ts[1:])), \
                f"tid {tid} timestamps regressed"

    def test_saved_doc_validates_and_analyzes(self, storm, tmp_path):
        t1 = storm["tracers"][0]
        p = tmp_path / "storm.json"
        doc = t1.save(str(p))
        s = timeline.analyze(obs_trace.load(str(p)))
        c = storm["blocks"][0]["counters"]
        assert s["steps"]["n"] == c["steps"]
        assert s["steps"]["planned_tokens"] == c["planned_tokens"]
        assert s["steps"]["realized_tokens"] == c["realized_tokens"]
        assert 0.0 < s["steps"]["budget_utilization"] <= 1.0
        assert s["preemptions"]["n"] == c["preemptions"]
        assert s["preemptions"]["readmitted"] >= 1
        assert s["n_requests"] == storm["spec"].n_requests
        assert timeline.main([str(p), "--require", "prefill-span",
                              "decode-span", "preemption", "step"]) == 0


# -- hypothesis-style trace invariants (satellite) ---------------------------

class TestTraceInvariants:
    @settings(max_examples=20, deadline=None)
    @given(pick=st.integers(min_value=0, max_value=10**6))
    def test_request_span_sequences_well_formed(self, storm, pick):
        """For a sampled request: queued precedes admitted precedes
        prefill; no decode activity after finished; every preemption is
        followed by a re-admission or the request never finishes."""
        seq = _spans_by_uid(storm["tracers"][0].events)
        uids = sorted(seq)
        uid = uids[pick % len(uids)]
        evs = seq[uid]
        kinds = [(ph, name) for ph, name, _ in evs]
        # envelope: req opens first, closes last (if closed)
        assert kinds[0] == ("b", "req")
        if ("e", "req") in kinds:
            assert kinds[-1] == ("e", "req")
        open_spans: list = []
        admitted = finished = False
        for ph, name, e in evs:
            if ph == "b":
                if name == "prefill":
                    assert admitted, "prefill span before any admission"
                assert name not in open_spans, f"re-opened {name}"
                open_spans.append(name)
            elif ph == "e":
                assert open_spans and open_spans[-1] == name, (
                    f"unbalanced end {name} over {open_spans}")
                open_spans.pop()
            elif name == "admitted":
                assert "queued" not in open_spans, \
                    "admitted while still queued"
                admitted = True
            elif name == "preempted":
                admitted = False
            elif name == "finished":
                finished = True
            assert not (finished and name in ("prefill_chunk", "admitted",
                                              "preempted")), \
                f"{name} after finished"
        if finished:
            assert not open_spans, f"finished with open spans {open_spans}"
        # preempt => later re-admission (storm replays run to completion)
        pre_idx = [i for i, k in enumerate(kinds) if k == ("n", "preempted")]
        for i in pre_idx:
            later = kinds[i + 1:]
            assert ("n", "admitted") in later or ("n", "finished") not in later

    @settings(max_examples=10, deadline=None)
    @given(which=st.booleans())
    def test_monotone_and_deterministic_per_replay(self, storm, which):
        tr = storm["tracers"][int(which)]
        last: dict = {}
        for e in tr.events:
            t = last.get(e["tid"])
            assert t is None or e["ts"] >= t
            last[e["tid"]] = e["ts"]


# -- engine-level metrics surface -------------------------------------------

class TestEngineMetrics:
    def test_stats_view_keys_and_write_through(self, storm):
        eng = storm["engines"][2]
        keys = list(eng.stats)
        assert keys[:10] == ["prefill_s", "decode_s", "decode_tokens",
                             "total_tokens", "prefill_tokens", "steps",
                             "whole_prefills", "preemptions",
                             "peak_kv_blocks", "max_step_tokens"]
        # the legacy warm-reset idiom still works (test_system uses it)
        old = eng.stats["decode_tokens"]
        eng.stats.update(decode_s=0.0, decode_tokens=0)
        assert eng.stats["decode_tokens"] == 0
        eng.stats["decode_tokens"] = old   # restore for other tests

    def test_latency_percentiles_from_registry(self, storm):
        eng = storm["engines"][0]
        pct = eng.latency_percentiles()
        assert set(pct) == {"ttft_s", "tpot_s", "queue_s"}
        n_req = storm["spec"].n_requests
        assert pct["ttft_s"]["n"] == n_req
        for s in pct.values():
            if s["n"]:
                assert s["p50"] <= s["p99"] <= s["max"]

    def test_reset_run_stats_rebases_peaks(self, model):
        """Satellite: warm-up no longer leaks into steady-state peaks, and
        the rebase starts from live state, not zero."""
        from repro.serving import Request, ServingEngine

        cfg, params = model
        eng = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                            prefill_chunk=8, block_size=8)
        mk = lambda o: [Request(uid=o + i, prompt=np.arange(10) + 1,
                                max_new_tokens=4) for i in range(2)]
        eng.run(mk(0))
        assert eng.stats["peak_kv_blocks"] > 0
        assert eng.stats["max_step_tokens"] > 0
        assert eng.stats["steps"] > 0
        eng.reset_run_stats()
        assert eng.stats["steps"] == 0
        assert eng.stats["decode_tokens"] == 0
        # peaks REBASED to current occupancy (idle engine: nothing held)
        assert eng.stats["peak_kv_blocks"] == int(eng.kv.blocks_in_use)
        assert eng.stats["max_step_tokens"] == 0
        assert eng.latency_percentiles()["ttft_s"]["n"] == 0
        # a fresh run re-establishes peaks from the new run only
        eng.run(mk(10))
        assert eng.stats["peak_kv_blocks"] > 0
        assert eng.stats["max_step_tokens"] > 0

    def test_reset_clears_attached_tracer(self, model):
        from repro.serving import Request, ServingEngine

        cfg, params = model
        tr = EventTracer()
        eng = ServingEngine(cfg, params, max_len=48, batch_slots=2,
                            prefill_chunk=8, block_size=8, tracer=tr)
        eng.run([Request(uid=0, prompt=np.arange(8) + 1, max_new_tokens=3)])
        assert tr.events
        eng.reset_run_stats()
        assert tr.events == []   # warm-up events can't pollute a saved trace


# ---------------------------------------------------------------------------
# SLO calibration (satellite)
# ---------------------------------------------------------------------------

class _FakeReq:
    def __init__(self, ttft, tpot):
        self.out_tokens = [1]
        self.ttft, self.tpot = ttft, tpot


class _FakeTraceReq:
    def __init__(self, slo_ttft_s, slo_tpot_s):
        self.slo_ttft_s, self.slo_tpot_s = slo_ttft_s, slo_tpot_s


class TestSloCalibration:
    def test_is_good_scales_thresholds(self):
        from benchmarks.workloads import metrics as wl_metrics

        tr = _FakeTraceReq(slo_ttft_s=1.0, slo_tpot_s=0.1)
        req = _FakeReq(ttft=1.5, tpot=0.15)
        assert not wl_metrics.is_good(req, tr)                 # unscaled: miss
        assert wl_metrics.is_good(req, tr, slo_scale=2.0)      # slow box: ok
        assert not wl_metrics.is_good(req, tr, slo_scale=0.5)  # fast box

    def test_measure_slo_scale(self, model):
        from benchmarks.workloads import runner

        cfg, params = model
        scale, per_step = runner.measure_slo_scale(cfg, params)
        assert 0.2 <= scale <= 50.0
        assert per_step > 0
        # the report records the calibration as provenance
        from benchmarks.workloads import schema
        doc = schema.make_report(
            arch=cfg.name, seed=0, quick=True,
            workloads={"steady": _minimal_block()},
            created_unix=1.0, rev="t", slo_scale=scale,
            ref_decode_step_s=per_step)
        assert doc["slo_scale"] == scale


def _minimal_block():
    pct = {"p50": 0.1, "p90": 0.1, "p99": 0.1, "mean": 0.1, "max": 0.1,
           "n": 1}
    return {
        "spec": {"name": "s"}, "trace_fingerprint": "sha256:" + "0" * 64,
        "metrics": {"ttft_s": dict(pct), "tpot_s": dict(pct),
                    "queue_s": dict(pct),
                    "goodput": {"slo_attained": 1.0, "good": 1, "total": 1,
                                "good_per_s": 1.0},
                    "output_tok_s": 1.0, "wall_s": 1.0},
        "counters": {"steps": 1, "preemptions": 0,
                     "preempt_readmissions": 0, "prefill_tokens": 1,
                     "prefill_tokens_planned": 1,
                     "cached_tokens_skipped": 0, "decode_tokens": 1,
                     "total_tokens": 2, "max_step_tokens": 1,
                     "peak_kv_blocks": 1, "whole_prefills": 0,
                     "planned_tokens": 2, "realized_tokens": 2,
                     "prefill_steps": 1, "decode_steps": 0,
                     "admissions": 1, "plan_kernel": "tsar_mxu"},
    }
