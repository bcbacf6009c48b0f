"""Streaming trace sinks, the flight recorder + incident snapshots, and
the metrics export surface (PR 10):

* ``StreamingSink`` fingerprints **byte-for-byte identically** to a
  ``MemorySink`` export of the same run, survives segment rotation, keeps
  a bounded number of events resident, and truncates on ``reset()`` so
  warm-up never leaks into a saved stream;
* ``timeline`` analyzes the JSONL stream to exactly the document analysis
  (property-tested with hypothesis), and its CLI fails a
  ``--min-step-utilization`` gate on a zero-step trace with a clear
  message instead of silently passing;
* ``repro.obs.export`` renders the registry so a scrape matches
  ``registry.snapshot()`` sample-for-sample, over HTTP and textfile;
* ``IncidentMonitor`` dumps schema-valid snapshots with debouncing, and
  attaching it to an engine perturbs no exact-gated counter.
"""
import json
import math
import os
import tempfile
import urllib.error
import urllib.request

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.obs import timeline
from repro.obs import trace as obs_trace
from repro.obs.export import (MetricsServer, TextfileWriter, parse_samples,
                              render, start_server)
from repro.obs.incident import (INCIDENT_KIND, INCIDENT_SCHEMA_VERSION,
                                TRIGGERS, IncidentMonitor, load_incident,
                                validate_incident)
from repro.obs.metrics import DEFAULT_BUCKETS, MetricsRegistry
from repro.obs.trace import (EventTracer, MemorySink, RingSink, StreamReader,
                             StreamingSink, TeeSink, meta_events, read_stream,
                             stream_segments, stream_to_perfetto)


def _tick():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    return clock


def _emit_lifecycle(tr, uid=1):
    """One request lifecycle + two steps — enough to touch every phase."""
    tr.begin(uid, "req", prompt_len=8)
    tr.mark(uid, "admitted", slot=0, cached_len=4, readmission=False)
    tr.mark(uid, "prefix_hit", cached_len=4)
    tr.begin(uid, "prefill", slot=0)
    tr.step(0.2, planned=8, realized=6, prefill_tokens=4, decode_tokens=2,
            kv_blocks=3, active_slots=1, kernel="tsar_mxu")
    tr.instant("kv_pressure", slot=0, need=2, free=0)
    tr.end(uid, "prefill")
    tr.begin(uid, "decode")
    tr.mark(uid, "first_token")
    tr.step(0.1, planned=2, realized=2, prefill_tokens=0, decode_tokens=2,
            kv_blocks=4, active_slots=1, kernel="tsar_mxu")
    tr.end(uid, "decode")
    tr.mark(uid, "finished", n_out=3, preemptions=0)
    tr.end(uid, "req")


# ---------------------------------------------------------------------------
# sinks (pure, no jax)
# ---------------------------------------------------------------------------

class TestSinks:
    def test_memory_sink_recent_and_reset(self):
        s = MemorySink()
        for i in range(5):
            s.append({"i": i})
        assert s.n_appended == 5 and len(s.events) == 5
        assert s.recent(2) == [{"i": 3}, {"i": 4}]
        s.reset()
        assert s.events == []

    def test_ring_sink_drops_oldest(self):
        s = RingSink(capacity=3)
        for i in range(10):
            s.append({"i": i})
        assert s.events == [{"i": 7}, {"i": 8}, {"i": 9}]
        assert s.n_appended == 10 and s.n_dropped == 7
        assert s.recent(2) == [{"i": 8}, {"i": 9}]
        s.reset()
        assert s.events == [] and s.n_appended == 0 and s.n_dropped == 0

    def test_tee_fans_out_reads_primary(self, tmp_path):
        mem, ring = MemorySink(), RingSink(capacity=2)
        tee = TeeSink(mem, ring)
        for i in range(4):
            tee.append({"i": i})
        assert tee.events is mem.events and len(mem.events) == 4
        assert ring.events == [{"i": 2}, {"i": 3}]
        tee.reset()
        assert mem.events == [] and ring.events == []
        with pytest.raises(ValueError, match="at least one sink"):
            TeeSink()

    def test_streaming_sink_does_not_retain_events(self, tmp_path):
        sink = StreamingSink(str(tmp_path / "s.jsonl"))
        with pytest.raises(RuntimeError, match="read_stream"):
            sink.events
        sink.finalize()


# ---------------------------------------------------------------------------
# streaming sink <-> memory sink identity (the tentpole contract)
# ---------------------------------------------------------------------------

class TestStreamingSink:
    def _twin_run(self, tmp_path, **sink_kw):
        """The same emission sequence through a memory tracer and a
        streaming tracer (deterministic clocks)."""
        mem = EventTracer(clock=_tick())
        sink = StreamingSink(str(tmp_path / "t.jsonl"), rev="testrev",
                             **sink_kw)
        strm = EventTracer(clock=_tick(), sink=sink)
        for tr in (mem, strm):
            _emit_lifecycle(tr, uid=1)
            _emit_lifecycle(tr, uid=2)
        return mem, sink

    def test_fingerprint_identical_to_memory(self, tmp_path):
        mem, sink = self._twin_run(tmp_path)
        doc = mem.to_perfetto(rev="testrev")
        info = sink.finalize()
        assert info["fingerprint"] == doc["otherData"]["fingerprint"]
        # finalize is idempotent and append-after-finalize refuses
        assert sink.finalize() == info
        with pytest.raises(RuntimeError, match="finalized"):
            sink.append({"ph": "i", "name": "late", "ts": 0, "args": {}})
        with pytest.raises(RuntimeError, match="finalized"):
            sink.reset()

    def test_jsonl_roundtrips_events_exactly(self, tmp_path):
        mem, sink = self._twin_run(tmp_path)
        doc = mem.to_perfetto(rev="testrev")
        info = sink.finalize()
        evs, reader = read_stream(info["path"])
        # meta events are part of the stream, so the full traceEvents list
        # round-trips (ts included: deterministic twin clocks)
        assert evs == doc["traceEvents"]
        assert reader.complete and reader.n_events == info["n_events"]
        assert reader.fingerprint == info["fingerprint"]
        assert reader.header["git_rev"] == "testrev"

    def test_rotation_chains_segments(self, tmp_path):
        mem, sink = self._twin_run(tmp_path, max_segment_bytes=512)
        info = sink.finalize()
        assert info["segments"] > 1
        segs = stream_segments(info["path"])
        assert len(segs) == info["segments"]
        assert segs[-1] == info["path"]
        assert [f"{info['path']}.{i}" for i in range(1, len(segs))] \
            == segs[:-1]
        # the chained read still fingerprints identically
        _, reader = read_stream(info["path"])
        assert reader.complete
        assert reader.fingerprint \
            == mem.to_perfetto(rev="x")["otherData"]["fingerprint"]

    def test_peak_resident_events_bounded(self, tmp_path):
        _, sink = self._twin_run(tmp_path, flush_every=4)
        n = sink.n_events
        sink.finalize()
        assert n > 4                       # the bound actually binds
        assert sink.peak_resident_events <= 4

    def test_reset_truncates_stream(self, tmp_path):
        # 600B segments: small enough that the warm-up lifecycle rotates,
        # large enough that a fresh header + meta events alone do not.
        sink = StreamingSink(str(tmp_path / "t.jsonl"), rev="x",
                             max_segment_bytes=600)
        warm = EventTracer(clock=_tick(), sink=sink)
        _emit_lifecycle(warm, uid=99)      # warm-up, rotates a few segments
        rotated = stream_segments(sink.path)[:-1]
        assert rotated                     # rotation actually happened
        warm.reset()                       # the engine's reset_run_stats path
        assert all(not os.path.exists(p) for p in rotated)
        assert sink.n_events == len(meta_events())
        _emit_lifecycle(warm, uid=1)       # may legitimately rotate again
        info = sink.finalize()
        fresh = EventTracer(clock=_tick())
        _emit_lifecycle(fresh, uid=1)
        # no trace of uid 99 survives: the stream equals a fresh run's
        assert info["fingerprint"] \
            == fresh.to_perfetto(rev="x")["otherData"]["fingerprint"]
        evs, _ = read_stream(info["path"])
        assert not any(e.get("id") == 99 for e in evs)

    def test_footerless_stream_reads_incomplete(self, tmp_path):
        _, sink = self._twin_run(tmp_path)
        sink.flush()                       # no finalize: writer "died"
        evs, reader = read_stream(sink.path)
        assert evs and reader.complete is False
        s = timeline.analyze_stream(sink.path)
        assert s["stream"]["complete"] is False
        assert "INCOMPLETE" in timeline.format_summary(s)
        sink.finalize()

    def test_truncated_tail_tolerated_in_active_segment(self, tmp_path):
        _, sink = self._twin_run(tmp_path)
        sink.flush()
        with open(sink.path, "a") as f:
            f.write('{"ph": "i", "name": "half')   # died mid-line
        evs, reader = read_stream(sink.path)
        assert len(evs) == sink.n_events and not reader.complete

    def test_tampered_stream_raises(self, tmp_path):
        _, sink = self._twin_run(tmp_path)
        info = sink.finalize()
        lines = open(info["path"]).read().splitlines()
        for i, ln in enumerate(lines):
            obj = json.loads(ln)
            if obj.get("ph") == "X":
                obj["args"]["planned"] += 1
                lines[i] = json.dumps(obj, sort_keys=True,
                                      separators=(",", ":"))
                break
        with open(info["path"], "w") as f:
            f.write("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="fingerprint"):
            read_stream(info["path"])

    def test_corrupt_rotated_segment_raises(self, tmp_path):
        _, sink = self._twin_run(tmp_path, max_segment_bytes=512)
        info = sink.finalize()
        with open(f"{info['path']}.1", "a") as f:
            f.write("not json\n")          # corruption NOT in the active tail
        with pytest.raises(ValueError, match="not valid JSON"):
            read_stream(info["path"])

    def test_stream_to_perfetto_validates(self, tmp_path):
        mem, sink = self._twin_run(tmp_path)
        sink.finalize()
        doc = stream_to_perfetto(sink.path)
        assert doc["otherData"]["kind"] == obs_trace.TRACE_KIND
        assert doc["otherData"]["fingerprint"] \
            == mem.to_perfetto(rev="x")["otherData"]["fingerprint"]

    def test_load_any_sniffs_stream_vs_doc(self, tmp_path):
        mem, sink = self._twin_run(tmp_path)
        sink.finalize()
        p = tmp_path / "doc.json"
        mem.save(str(p), rev="x")
        kind, obj = obs_trace.load_any(sink.path)
        assert kind == "stream" and isinstance(obj, StreamReader)
        kind, obj = obs_trace.load_any(str(p))
        assert kind == "doc" and isinstance(obj, dict)


# ---------------------------------------------------------------------------
# timeline over streams + the zero-step satellite
# ---------------------------------------------------------------------------

class TestTimelineStream:
    def test_stream_analysis_matches_document(self, tmp_path):
        sink = StreamingSink(str(tmp_path / "t.jsonl"), rev="x")
        tr = EventTracer(clock=_tick(), sink=TeeSink(MemorySink(), sink))
        _emit_lifecycle(tr)
        doc = tr.to_perfetto(rev="x")
        sink.finalize()
        mem_s = timeline.analyze(doc)
        st_s = timeline.analyze_stream(sink.path)
        assert st_s.pop("stream") == {"complete": True, "segments": 1}
        assert mem_s == st_s

    def test_cli_over_jsonl(self, tmp_path, capsys):
        sink = StreamingSink(str(tmp_path / "t.jsonl"), rev="x")
        tr = EventTracer(clock=_tick(), sink=sink)
        _emit_lifecycle(tr)
        sink.finalize()
        assert timeline.main([sink.path, "--require", "prefill-span",
                              "decode-span", "prefix-hit", "step",
                              "--min-step-utilization", "0.5"]) == 0
        capsys.readouterr()
        assert timeline.main([sink.path, "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["steps"]["n"] == 2 and out["stream"]["complete"]

    @pytest.mark.parametrize("suffix", ["json", "jsonl"])
    def test_zero_step_trace_fails_utilization_gate(self, tmp_path, capsys,
                                                    suffix):
        """Satellite: ``nan < x`` is always False — a zero-step trace must
        fail the gate with a clear message, not silently pass."""
        p = tmp_path / f"empty.{suffix}"
        if suffix == "json":
            tr = EventTracer(clock=_tick())
            tr.begin(1, "req")
            tr.end(1, "req")
            tr.save(str(p), rev="x")
        else:
            sink = StreamingSink(str(p), rev="x")
            tr = EventTracer(clock=_tick(), sink=sink)
            tr.begin(1, "req")
            tr.end(1, "req")
            sink.finalize()
        assert timeline.main([str(p)]) == 0          # analysis itself is fine
        capsys.readouterr()
        assert timeline.main([str(p), "--min-step-utilization", "0.5"]) == 1
        err = capsys.readouterr().err
        assert "no step records" in err
        s = timeline.analyze_events([])
        assert s["steps"]["budget_utilization"] is None
        assert s["steps"]["mean_active_slots"] is None
        # the text renderer survives the all-None summary too
        s.update(n_events=0, schema_version=1, fingerprint="sha256:" + "0" * 64)
        assert "n/a" in timeline.format_summary(s)


# -- hypothesis property: stream == memory for arbitrary sequences ------

class TestStreamProperty:
    @settings(max_examples=15, deadline=None)
    @given(ops=st.lists(st.tuples(st.integers(min_value=0, max_value=4),
                                  st.integers(min_value=1, max_value=3)),
                        min_size=0, max_size=40),
           flush=st.integers(min_value=1, max_value=7),
           seg=st.integers(min_value=128, max_value=4096))
    def test_roundtrip_matches_memory(self, ops, flush, seg):
        """Any emission sequence streamed to JSONL (any flush cadence, any
        rotation threshold) analyzes and fingerprints identically to the
        in-memory path."""
        d = tempfile.mkdtemp(prefix="obs-stream-prop-")
        path = os.path.join(d, "t.jsonl")
        mem = EventTracer(clock=_tick())
        sink = StreamingSink(path, rev="x", flush_every=flush,
                             max_segment_bytes=seg)
        strm = EventTracer(clock=_tick(), sink=sink)

        def emit(tr):
            for op, uid in ops:
                if op == 0:
                    tr.begin(uid, "req", prompt_len=uid)
                elif op == 1:
                    tr.end(uid, "req")
                elif op == 2:
                    tr.mark(uid, "admitted", slot=0, cached_len=0,
                            readmission=False)
                elif op == 3:
                    tr.step(0.1, planned=2 * uid, realized=uid,
                            prefill_tokens=uid % 2, kv_blocks=uid,
                            active_slots=1)
                else:
                    tr.instant("kv_pressure", need=uid, free=0)

        emit(mem)
        emit(strm)
        doc = mem.to_perfetto(rev="x")
        info = sink.finalize()
        assert info["fingerprint"] == doc["otherData"]["fingerprint"]
        assert sink.peak_resident_events <= flush
        mem_s = timeline.analyze(doc)
        st_s = timeline.analyze_stream(path)
        st_s.pop("stream")
        assert mem_s == st_s


# ---------------------------------------------------------------------------
# metrics export surface (pure, no jax)
# ---------------------------------------------------------------------------

def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("steps", "engine steps").inc(7)
    g = reg.gauge("kv_blocks", "blocks in use")
    g.set(9)
    g.set(4)
    fam = reg.counter("step_time_s", "step wall", labels=("phase",))
    fam.labels(phase="prefill").inc(1.5)
    fam.labels(phase="decode").inc(2.5)
    h = reg.histogram("ttft_s", "time to first token")
    for v in (0.004, 0.02, 0.02, 0.3, 2.0):
        h.observe(v)
    reg.histogram("tpot_s", "per-token latency")   # stays empty
    return reg


class TestExportRender:
    def test_scrape_matches_snapshot_exactly(self):
        """The acceptance contract: every counter/gauge value in the
        exposition equals the ``snapshot()`` value under the corresponding
        name, histograms match summary-for-summary."""
        reg = _populated_registry()
        snap = reg.snapshot()
        samples = parse_samples(render(reg))
        assert samples["tsar_steps"] == snap["steps"]
        assert samples["tsar_kv_blocks"] == snap["kv_blocks"] == 4
        assert samples["tsar_kv_blocks_peak"] == snap["kv_blocks_peak"] == 9
        assert samples['tsar_step_time_s{phase="prefill"}'] \
            == snap["step_time_s{phase=prefill}"]
        assert samples['tsar_step_time_s{phase="decode"}'] == 2.5
        s = snap["ttft_s"]
        assert samples["tsar_ttft_s_count"] == s["n"] == 5
        assert samples["tsar_ttft_s_sum"] == pytest.approx(2.344)
        for q, p in (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99")):
            assert samples[f'tsar_ttft_s_quantile{{quantile="{q}"}}'] \
                == pytest.approx(s[p])
        assert samples["tsar_ttft_s_mean"] == pytest.approx(s["mean"])
        assert samples["tsar_ttft_s_max"] == s["max"] == 2.0

    def test_histogram_buckets_cumulative(self):
        reg = _populated_registry()
        samples = parse_samples(render(reg))
        counts = [samples[f'tsar_ttft_s_bucket{{le="{_le}"}}']
                  for _le in [repr(float(b)) for b in DEFAULT_BUCKETS]
                  + ["+Inf"]]
        assert counts == sorted(counts)            # cumulative
        assert counts[-1] == 5                     # +Inf == count
        assert samples['tsar_ttft_s_bucket{le="0.005"}'] == 1
        assert samples['tsar_ttft_s_bucket{le="0.025"}'] == 3
        # the empty histogram renders NaN-free zeros (sentinel satellite)
        assert samples["tsar_tpot_s_count"] == 0
        assert samples['tsar_tpot_s_quantile{quantile="0.5"}'] == 0.0
        assert "NaN" not in render(reg)

    def test_type_and_help_lines(self):
        text = render(_populated_registry())
        assert "# TYPE tsar_steps counter" in text
        assert "# TYPE tsar_kv_blocks gauge" in text
        assert "# TYPE tsar_ttft_s histogram" in text
        assert "# HELP tsar_ttft_s time to first token" in text
        assert "_total" not in text     # names stay the snapshot names

    def test_namespace_off(self):
        samples = parse_samples(render(_populated_registry(), namespace=""))
        assert "steps" in samples


class TestExportEndpoints:
    def test_http_scrape_matches_registry(self):
        reg = _populated_registry()
        srv = start_server(reg, port=0)
        try:
            assert srv.url.endswith(f":{srv.port}/metrics")
            body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
            assert parse_samples(body) == parse_samples(render(reg))
            js = urllib.request.urlopen(
                srv.url + ".json", timeout=5).read().decode()
            assert json.loads(js) == json.loads(json.dumps(reg.snapshot()))
            # live registry: a scrape after mutation sees the new value
            reg.get("steps").inc(3)
            body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
            assert parse_samples(body)["tsar_steps"] == 10
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/nope", timeout=5)
        finally:
            srv.stop()

    def test_server_context_manager(self):
        with MetricsServer(_populated_registry(), port=0) as srv:
            body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
            assert "tsar_steps" in body

    def test_textfile_writer(self, tmp_path):
        reg = _populated_registry()
        p = tmp_path / "metrics.prom"
        w = TextfileWriter(reg, str(p), interval_s=3600.0)
        w.write_once()
        assert parse_samples(p.read_text()) == parse_samples(render(reg))
        w.start()
        reg.get("steps").inc(5)
        w.stop()                 # final write flushes the last state
        assert parse_samples(p.read_text())["tsar_steps"] == 12
        assert w.n_writes >= 2
        assert not os.path.exists(str(p) + ".tmp")


# ---------------------------------------------------------------------------
# incident monitor (pure, no jax)
# ---------------------------------------------------------------------------

def _monitor(tmp_path, **kw):
    kw.setdefault("clock", lambda: 1700000000.0)
    kw.setdefault("rev", "testrev")
    return IncidentMonitor(str(tmp_path / "inc"), **kw)


class _FakeReq:
    def __init__(self, uid=7, ttft=None, tpot=None):
        self.uid, self.ttft, self.tpot = uid, ttft, tpot


class TestIncidentMonitor:
    def test_dump_is_schema_valid_with_ring_and_metrics(self, tmp_path):
        reg = _populated_registry()
        tr = EventTracer(clock=_tick(), sink=RingSink(capacity=4))
        _emit_lifecycle(tr)
        mon = _monitor(tmp_path).bind(registry=reg, tracer=tr)
        path = mon.observe("kv_pressure", slot=0, need=2, free=0)
        assert path and os.path.exists(path)
        doc = load_incident(path)
        assert doc["kind"] == INCIDENT_KIND
        assert doc["schema_version"] == INCIDENT_SCHEMA_VERSION
        assert doc["trigger"] == "kv_pressure"
        assert doc["context"] == {"slot": 0, "need": 2, "free": 0}
        assert doc["git_rev"] == "testrev"
        assert doc["metrics"]["steps"] == 7
        assert doc["ring"]["n_events"] == 4            # ring capacity
        assert doc["ring"]["n_dropped"] == tr.sink.n_dropped > 0
        assert doc["ring"]["events"] == tr.sink.events
        assert mon.summary() == {"n": 1, "by_trigger": {"kv_pressure": 1},
                                 "suppressed": 0, "paths": [path]}

    def test_validate_rejects_malformed(self, tmp_path):
        mon = _monitor(tmp_path)
        doc = load_incident(mon.observe("rejection", n=1))
        bad = dict(doc)
        del bad["ring"]
        with pytest.raises(ValueError, match="ring"):
            validate_incident(bad)
        bad = dict(doc, trigger="meteor_strike")
        with pytest.raises(ValueError, match="unknown trigger"):
            validate_incident(bad)
        bad = dict(doc, schema_version=99)
        with pytest.raises(ValueError, match="schema_version"):
            validate_incident(bad)

    def test_unknown_trigger_config_raises(self, tmp_path):
        with pytest.raises(ValueError, match="unknown incident triggers"):
            _monitor(tmp_path, triggers=("slo_breach", "meteor_strike"))

    def test_unconfigured_trigger_is_ignored(self, tmp_path):
        mon = _monitor(tmp_path, triggers=("preemption",))
        assert mon.observe("rejection", n=1) is None
        assert mon.summary()["n"] == 0 and mon.suppressed == 0

    def test_cooldown_debounces_per_trigger(self, tmp_path):
        mon = _monitor(tmp_path, cooldown_steps=10)
        assert mon.observe("preemption", uid=1)
        assert mon.observe("preemption", uid=2) is None    # in cooldown
        assert mon.observe("rejection", n=1)               # other trigger ok
        for _ in range(10):
            mon.step_tick()
        assert mon.observe("preemption", uid=3)            # cooldown expired
        assert mon.suppressed == 1

    def test_max_incidents_caps_total(self, tmp_path):
        mon = _monitor(tmp_path, max_incidents=2, cooldown_steps=0)
        assert mon.observe("preemption", uid=1)
        assert mon.observe("preemption", uid=2)
        assert mon.observe("preemption", uid=3) is None
        assert mon.summary()["n"] == 2 and mon.suppressed == 1

    def test_eviction_storm_sliding_window(self, tmp_path):
        mon = _monitor(tmp_path, eviction_storm_n=6, eviction_window_steps=4)
        # a slow trickle never accumulates 6 within 4 steps
        for _ in range(12):
            mon.step_tick(evictions=1)
            mon.step_tick()
            mon.step_tick()
            mon.step_tick()
        assert mon.summary()["by_trigger"].get("eviction_storm") is None
        # a burst does
        for _ in range(3):
            mon.step_tick(evictions=2)
        assert mon.summary()["by_trigger"]["eviction_storm"] == 1
        doc = load_incident(mon.paths[-1])
        assert doc["context"]["evictions"] >= 6

    def test_slo_breach_hooks(self, tmp_path):
        mon = _monitor(tmp_path, slo_ttft_s=0.5, slo_tpot_s=0.05,
                       cooldown_steps=0)
        mon.request_first_token(_FakeReq(ttft=0.4))        # under threshold
        mon.request_first_token(_FakeReq(ttft=None))       # unfinished
        assert mon.summary()["n"] == 0
        mon.request_first_token(_FakeReq(uid=3, ttft=0.9))
        mon.request_finished(_FakeReq(uid=4, tpot=0.2))
        assert mon.summary()["by_trigger"]["slo_breach"] == 2
        kinds = {load_incident(p)["context"]["kind"] for p in mon.paths}
        assert kinds == {"ttft", "tpot"}
        # thresholds unset -> hooks are inert
        off = _monitor(tmp_path, prefix="off")
        off.request_first_token(_FakeReq(ttft=100.0))
        assert off.summary()["n"] == 0

    def test_reset_run_discards_warmup_files(self, tmp_path):
        mon = _monitor(tmp_path, cooldown_steps=0)
        paths = [mon.observe("preemption", uid=i) for i in range(2)]
        assert all(os.path.exists(p) for p in paths)
        mon.reset_run()
        assert all(not os.path.exists(p) for p in paths)
        assert mon.summary() == {"n": 0, "by_trigger": {}, "suppressed": 0,
                                 "paths": []}
        # re-armed: fires again from seq 0
        p = mon.observe("preemption", uid=9)
        assert p and "-000-" in os.path.basename(p)


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


def _small_engine(model, **kw):
    from repro.serving import ServingEngine

    cfg, params = model
    return ServingEngine(cfg, params, max_len=48, batch_slots=2,
                         prefill_chunk=8, block_size=8, **kw)


class TestEngineIncidents:
    def test_flight_recorder_kwarg(self, model):
        from repro.serving import Request

        eng = _small_engine(model, flight_recorder=64)
        assert isinstance(eng.tracer.sink, RingSink)
        assert eng.tracer.sink.capacity == 64
        eng.run([Request(uid=0, prompt=np.arange(8) + 1, max_new_tokens=3)])
        assert eng.tracer.sink.events                  # recorder recorded
        assert isinstance(_small_engine(model, flight_recorder=True)
                          .tracer.sink, RingSink)

    def test_rejection_incident_fires(self, model, tmp_path):
        from repro.serving import Request

        mon = IncidentMonitor(str(tmp_path / "inc"), rev="t")
        eng = _small_engine(model, incidents=mon, flight_recorder=32)
        eng.submit(Request(uid=0, prompt=np.arange(100) + 1,
                           max_new_tokens=4))          # can never fit
        eng.step()
        assert mon.summary()["by_trigger"]["rejection"] == 1
        doc = load_incident(mon.paths[0])
        assert doc["context"]["n"] == 1
        assert doc["metrics"]["rejections"] == 1       # registry was bound
        assert doc["ring"]["events"]                   # flight recorder dump

    def test_slo_breach_incident_fires_end_to_end(self, model, tmp_path):
        from repro.serving import Request

        mon = IncidentMonitor(str(tmp_path / "inc"), slo_ttft_s=1e-9,
                              rev="t")                 # everything breaches
        eng = _small_engine(model, incidents=mon)
        eng.run([Request(uid=0, prompt=np.arange(8) + 1, max_new_tokens=3)])
        assert mon.summary()["by_trigger"]["slo_breach"] >= 1

    def test_warmup_incidents_discarded_on_reset(self, model, tmp_path):
        from repro.serving import Request

        mon = IncidentMonitor(str(tmp_path / "inc"), slo_ttft_s=1e-9,
                              rev="t")
        eng = _small_engine(model, incidents=mon)
        eng.run([Request(uid=0, prompt=np.arange(8) + 1, max_new_tokens=3)])
        warm_paths = list(mon.paths)
        assert warm_paths
        eng.reset_run_stats()
        assert mon.summary()["n"] == 0
        assert all(not os.path.exists(p) for p in warm_paths)
        eng.run([Request(uid=1, prompt=np.arange(8) + 1, max_new_tokens=3)])
        assert mon.summary()["by_trigger"]["slo_breach"] >= 1


@pytest.fixture(scope="module")
def storm_twin(model):
    """The preemption-storm quick trace replayed with and without an
    armed monitor — the counters must be bit-identical (attaching the
    incident path cannot perturb the exact-gated baseline)."""
    from benchmarks.workloads import runner
    from benchmarks.workloads.generator import generate, preset

    cfg, params = model
    spec = preset("preemption-storm", quick=True)
    trace = generate(spec)
    d = tempfile.mkdtemp(prefix="obs-incidents-")
    mon = IncidentMonitor(d, prefix="storm", rev="t")
    tr = EventTracer(sink=RingSink(capacity=256))
    b1, e1, r1 = runner.run_workload(spec, cfg, params, trace=trace,
                                     tracer=tr, incidents=mon)
    b0, e0, r0 = runner.run_workload(spec, cfg, params, trace=trace)
    return {"mon": mon, "blocks": (b1, b0), "reqs": (r1, r0),
            "engines": (e1, e0)}


class TestStormIncidents:
    def test_monitor_does_not_perturb_counters(self, storm_twin):
        b1, b0 = storm_twin["blocks"]
        r1, r0 = storm_twin["reqs"]
        assert b1["counters"] == b0["counters"]
        assert b1["trace_fingerprint"] == b0["trace_fingerprint"]
        assert [r.out_tokens for r in r1] == [r.out_tokens for r in r0]

    def test_preemption_incidents_fired_with_flight_recording(self,
                                                              storm_twin):
        mon = storm_twin["mon"]
        assert storm_twin["blocks"][0]["counters"]["preemptions"] > 0
        assert mon.summary()["by_trigger"].get("preemption", 0) >= 1
        doc = load_incident(
            next(p for p in mon.paths if "-preemption-" in p))
        assert doc["ring"]["events"]          # ring dump captured the lead-up
        assert {"uid", "slot", "cursor", "n_preempted"} <= set(doc["context"])
        assert doc["metrics"]["preemptions"] >= 1

    def test_metrics_scrape_of_live_engine(self, storm_twin):
        """Acceptance: a curl-equivalent fetch of the scrape endpoint
        exposes counters/histograms matching ``snapshot()`` exactly."""
        eng = storm_twin["engines"][0]
        snap = eng.metrics.snapshot()
        with MetricsServer(eng.metrics, port=0) as srv:
            js = urllib.request.urlopen(
                srv.url + ".json", timeout=5).read().decode()
            assert json.loads(js) == json.loads(json.dumps(snap))
            samples = parse_samples(
                urllib.request.urlopen(srv.url, timeout=5).read().decode())
        assert samples["tsar_steps"] == snap["steps"]
        assert samples["tsar_preemptions"] == snap["preemptions"]
        assert samples["tsar_ttft_s_count"] == snap["ttft_s"]["n"]
        assert samples["tsar_ttft_s_max"] == pytest.approx(
            snap["ttft_s"]["max"])
        assert math.isfinite(samples["tsar_ttft_s_sum"])

    def test_fresh_engine_percentiles_nan_free(self, model):
        """Satellite: ``latency_percentiles()`` on an engine that has
        served nothing returns the sentinel, never NaN."""
        eng = _small_engine(model)
        pct = eng.latency_percentiles()
        for s in pct.values():
            assert s["n"] == 0 and s["empty"] is True
            assert not any(isinstance(v, float) and math.isnan(v)
                           for v in s.values())
        json.dumps(pct, allow_nan=False)      # strict-JSON safe


class TestSharedPrefixStreamIdentity:
    def test_tee_stream_identity_on_engine_trace(self, model, tmp_path):
        """The tentpole acceptance on a real engine run: TeeSink(memory,
        streaming) over the shared-prefix quick replay — identical
        fingerprints, identical timeline analysis, bounded residency."""
        from benchmarks.workloads import runner
        from benchmarks.workloads.generator import generate, preset

        cfg, params = model
        spec = preset("shared-prefix", quick=True)
        trace = generate(spec)
        sink = StreamingSink(str(tmp_path / "sp.jsonl"), flush_every=64)
        tr = EventTracer(sink=TeeSink(MemorySink(), sink))
        block, eng, reqs = runner.run_workload(spec, cfg, params, trace=trace,
                                               tracer=tr)
        doc = tr.to_perfetto(rev="x")
        info = sink.finalize()
        assert info["fingerprint"] == doc["otherData"]["fingerprint"]
        assert info["n_events"] == len(doc["traceEvents"])
        assert sink.peak_resident_events <= 64
        mem_s = timeline.analyze(doc)
        st_s = timeline.analyze_stream(info["path"])
        st_s.pop("stream")
        assert mem_s == st_s
        assert mem_s["steps"]["n"] == block["counters"]["steps"] > 0
        assert mem_s["prefix"]["hits"] > 0
