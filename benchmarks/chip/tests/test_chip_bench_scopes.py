"""The step's parts and the engine's host phases read from a trace, on the
CPU: self time by scope on hand-made tables, the compiled programs' scopes
from a CPU trace, the parts' counts against the step's, the readers, and
traced runs of the harness at a tiny size: ``run.py``'s own traced path,
and a new architecture added as one file."""
import glob
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import pytest

import harness
import peaks
import readers
import scopes
import spec
import xtrace
from test_chip_bench_arith import BITNET, HAND

DENSE = spec.arch(BITNET)
NAMES = scopes.step_scopes(DENSE)
ENGINE_SPANS = {n for n in xtrace.HOST_SPANS if n.startswith("engine.")}

NEW_METRICS = [f"{m}.{s}" for m in ("kv_copy_ms", "attention_roofline",
                                    "bitlinear_roofline", "engine_host_ms")
               for s in ("serve", "batch")]


def _nested():
    # A layer loop [0, 100) (no scope) holding an attention fusion [10, 30)
    # with a BitLinear [15, 20) inside, and a norm [40, 60) with no scope;
    # an XLA gather loop [110, 150) scoped kv_gather whose body op
    # [120, 130) has no metadata; a head op [200, 220) overlapped by an op
    # with no scope [210, 230); a zero-length op.
    ops = [["while.4", 0, 100], ["fusion.1", 10, 30], ["fusion.2", 15, 20],
           ["fusion.3", 40, 60], ["while.5", 110, 150],
           ["fusion.9", 120, 130], ["fusion.7", 200, 220],
           ["copy.1", 210, 230], ["copy-done", 240, 240]]
    sc = [None, "attention", "bitlinear", None, "kv_gather", None, "head",
          None, "kv_scatter"]
    return ops, sc


def test_self_times_by_hand():
    ops, sc = _nested()
    got = scopes.self_times(ops, sc, NAMES)
    assert got == {"other": 10 + 10 + 20 + 40, "attention": 5 + 10,
                   "bitlinear": 5, "kv_gather": 10 + 10 + 20,
                   "head": 10 + 10 + 10, "kv_scatter": 0}
    busy = xtrace._union([s, e] for _, s, e in ops)
    assert sum(got.values()) == sum(e - s for s, e in busy)


def _table():
    ops, sc = _nested()
    return {
        "devices": {"/device:TPU:0": {
            "ops": ops, "scopes": sc,
            "modules": [["jit__lambda(7)", 0, 150], ["jit__argmax(8)", 150,
                                                     240]]}},
        # One driver step [0, 260): admit, plan, the jitted call with its
        # dispatch and wait, sample, emit; then a stamp.
        "host": [["bench.step", 0, 260], ["engine.admit", 0, 2],
                 ["engine.plan", 2, 5], ["tsar_engine_step", 5, 150],
                 ["engine.dispatch", 5, 8], ["engine.wait", 8, 150],
                 ["engine.sample", 150, 235], ["engine.emit", 235, 255],
                 ["bench.stamp", 260, 270]],
    }


def test_reduce_names_parts_and_phases():
    table = _table()
    out = scopes.reduce_table(table, NAMES)
    plain = xtrace.reduce_table({
        "devices": {d: {"ops": v["ops"], "modules": v["modules"]}
                    for d, v in table["devices"].items()},
        "host": table["host"]})
    assert {k: v for k, v in out.items() if k != "scope_s"} == plain
    scope_s = dict(out["scope_s"])
    assert list(scope_s) == list(NAMES) + [scopes.OTHER]
    assert sum(scope_s.values()) == pytest.approx(out["busy_s"])
    assert scope_s["head"] == pytest.approx(30e-9)
    assert out["host_span_s"]["engine.wait"] == pytest.approx(142e-9)
    assert out["host_span_s"]["bench.step"] == pytest.approx(260e-9)
    # idle: [100, 110) and [150, 200) in the wait and the sample, [230,
    # 235) in the sample, [235, 255) in the emit, [255, 260) in the step,
    # [260, 270) in the stamp
    idle = dict(out["idle_gaps"])
    assert idle == pytest.approx({
        "engine.wait": 10e-9, "engine.sample": 50e-9 + 5e-9,
        "engine.emit": 20e-9, "bench.step": 5e-9, "bench.stamp": 10e-9})
    assert sum(idle.values()) == pytest.approx(out["span_s"] - out["busy_s"])
    # the step's program ends as the wait closes, the sampler's starts as
    # the sample opens: the clocks agree
    assert out["clock_offset_ms"] == [0.0, 0.0]


def test_idle_labels_follow_the_device_clock():
    """The same step with the device clock 4 ns behind the host's reads
    the same idle labels: the engine's spans bound the offset to 4 ns."""
    table = _table()
    dev = table["devices"]["/device:TPU:0"]
    for ev in dev["ops"] + dev["modules"]:
        ev[1] -= 4
        ev[2] -= 4
    out = xtrace.reduce_table(table)
    assert out["clock_offset_ms"] == pytest.approx([4e-6, 4e-6])
    want = dict(xtrace.reduce_table(_table())["idle_gaps"])
    got = dict(out["idle_gaps"])
    # the trace's span now ends 4 ns later: [270, 274) is under no span
    assert got.pop(xtrace.NO_SPAN) == pytest.approx(4e-9)
    assert got == pytest.approx(want)
    del table["host"][5]                    # no engine.wait: no bounds
    assert xtrace.reduce_table(table)["clock_offset_ms"] is None


def test_text_proto_keeps_scopes():
    table = _table()
    pd = jax.profiler.ProfileData.from_text_proto(xtrace.to_text_proto(table))
    back = scopes.load(pd, None, 1, NAMES)
    dev, want = back["devices"]["/device:TPU:0"], table["devices"][
        "/device:TPU:0"]
    order = sorted(range(len(want["ops"])), key=lambda i: want["ops"][i][1])
    assert dev["ops"] == [want["ops"][i] for i in order]
    assert dev["scopes"] == [want["scopes"][i] for i in order]
    assert dev["modules"] == want["modules"]
    assert sorted(back["host"]) == sorted(table["host"])
    assert dict(scopes.reduce_table(back, NAMES)["scope_s"]) == \
        pytest.approx(dict(scopes.reduce_table(table, NAMES)["scope_s"]))


def test_cut_keeps_the_steps_events():
    sub = xtrace.cut(_table(), 100, 215)
    dev = sub["devices"]["/device:TPU:0"]
    assert [op[0] for op in dev["ops"]] == ["while.5", "fusion.9", "fusion.7",
                                            "copy.1"]
    assert dev["scopes"] == ["kv_gather", None, "head", None]
    assert [h[0] for h in sub["host"]] == ["engine.sample"]


WITHOUT_ATTENTION = tuple(n for n in NAMES if n != "attention")


@pytest.mark.parametrize("path, names, want", [
    ("jit(f)/while/body/closed_call/attention/bitlinear/dot_general", NAMES,
     "bitlinear"),
    ("jit(f)/while/body/closed_call/attention/dot_general", NAMES,
     "attention"),
    ("jit(f)/kv_gather/gather", NAMES, "kv_gather"),
    ("jit(f)/while/body/add", NAMES, None),
    ("jit(f)/headroom/add", NAMES, None),
    (None, NAMES, None),
    ("jit(f)/while/body/closed_call/attention/dot_general",
     WITHOUT_ATTENTION, None),
    ("jit(f)/while/body/closed_call/attention/bitlinear/dot_general",
     WITHOUT_ATTENTION, "bitlinear"),
])
def test_innermost_scope_wins(path, names, want):
    assert scopes.scope_of(path, names) == want


def test_compiled_programs_scopes_from_a_cpu_trace():
    @jax.jit
    def f(x):
        with jax.named_scope("attention"):
            y = x @ x
            with jax.named_scope("bitlinear"):
                y = y @ x
        return y.sum()

    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    with open(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], "rb") as fh:
        raw = fh.read()
    programs = scopes.hlo_scopes(raw, NAMES)
    (name,) = [n for n in programs if n.startswith("jit_f(")]
    got = set(programs[name].values())
    assert {"attention", "bitlinear"} <= got
    assert scopes.hlo_scopes(raw, NAMES, modules=set()) == {}


SLOTS = [
    ([(1, 1000)], 1),
    ([(256, 256)], 1),
    ([(1, 500), (3, 40), (200, 1700)], 2),
    ([], 0),
]


@pytest.mark.parametrize("slots, emit",
                         SLOTS + [(s, e) for s, e, *_ in HAND.values()])
def test_step_parts_sum_to_the_step(slots, emit):
    got = DENSE.step_parts(BITNET, slots, emit)
    ops, nbytes = DENSE.step_counts(BITNET, slots, emit)
    assert set(got) == set(DENSE.PARTS)
    assert sum(o for o, _ in got.values()) == ops
    assert sum(b for _, b in got.values()) <= nbytes


def test_step_parts_by_hand():
    # one decode token at position 999 of bitnet-2b-4t (test_chip_bench_arith)
    got = DENSE.step_parts(BITNET, [(1, 1000)], 1)
    assert got["bitlinear"] == (2 * 30 * 69_468_160,
                                30 * (69_468_160 / 4 + 2 * 22_784))
    assert got["attention"] == (30 * 10_240 * 1000, 76_800 * 1000)
    assert got["head"] == (2 * 2560 * 128_256, 2560 * 128_256 * 2)


def _record(**trace):
    steps = [{"t0": 1.0, "t1": 1.1, "slots": [(1, 1000)], "emit": 1},
             {"t0": 1.2, "t1": 1.3, "slots": [(1, 1001)], "emit": 1},
             {"t0": 9.0, "t1": 9.1, "slots": [(1, 9)], "emit": 1}]
    tr = {"t0": 0.5, "t1": 2.0, "window_s": 1.5, "busy_s": 0.2,
          "program_s": 0.2, **trace}
    return {"window_s": 10.0, "steps": steps, "trace": tr, "config": BITNET,
            "peaks": {"bf16_flops": 197e12, "int8_ops": 393e12,
                      "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_read_nothing_without_scopes(name):
    read = spec.metric_reader(name)
    assert read({"window_s": 1.0, "steps": [], "requests": []}) is None
    rec = _record()                     # a trace reduced without scopes
    assert read(rec) is None
    # a program compiled without the scopes, run without the engine spans
    rec = _record(scope_s=[[k, 0.0] for k in NAMES]
                  + [[scopes.OTHER, 0.2]], host_span_s={"bench.step": 0.2})
    assert read(rec) is None
    del rec["trace"]
    assert read(rec) is None


def test_new_readers_by_hand():
    scope_s = [["kv_gather", 0.010], ["kv_scatter", 0.030],
               ["attention", 0.050], ["bitlinear", 0.080], ["head", 0.01],
               ["other", 0.02]]
    spans = {"engine.admit": 0.001, "engine.plan": 0.002,
             "engine.dispatch": 0.003, "engine.wait": 0.19,
             "engine.sample": 0.004, "engine.emit": 0.005,
             "bench.step": 0.21}
    rec = _record(scope_s=scope_s, host_span_s=spans)
    read = lambda n: spec.metric_reader(n)(rec)  # noqa: E731
    assert readers.traced_steps(rec) == rec["steps"][:2]
    assert read("kv_copy_ms.serve") == pytest.approx(1e3 * 0.040 / 2)
    assert read("engine_host_ms.batch") == pytest.approx(1e3 * 0.015 / 2)
    attn = sum(max(DENSE.step_parts(BITNET, s["slots"], 1)["attention"][0]
                   / 197e12,
                   DENSE.step_parts(BITNET, s["slots"], 1)["attention"][1]
                   / 819e9) for s in rec["steps"][:2])
    assert read("attention_roofline.serve") == pytest.approx(
        100 * attn / 0.050)
    # decode BitLinears are bound by their planes: 0.52 GB at 819 GB/s
    planes = 30 * (69_468_160 / 4 + 2 * 22_784)
    assert read("bitlinear_roofline.batch") == pytest.approx(
        100 * 2 * planes / 819e9 / 0.080)


TINY = dict(BITNET, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=2,
            vocab_size=512)
PART_METRICS = [f"{m}.serve" for m in ("kv_copy_ms", "attention_roofline",
                                       "bitlinear_roofline", "engine_host_ms")]


def _tiny_cell(config, per_layer):
    bench = spec.load_benchmark()
    by = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    return spec.Cell(
        name="tiny.chat", chips=1, config=config,
        traffic={"name": "tiny", "loop": "open",
                 "arrival": {"kind": "poisson"},
                 "prompt_len": {"kind": "uniform", "lo": 8, "hi": 40},
                 "output_len": {"kind": "uniform", "lo": 4, "hi": 12}},
        settings={"engine": {"slots": 3, "token_budget": 19,
                             "prefill_chunk": 16, "max_len": 64,
                             "block_size": 16},
                  "rate_rps": 8.0, "preroll_s": 0.3,
                  "check": {"sample_requests": 4,
                            "limits": {"logit_mse": 1e-8}}},
        end_to_end=[by["setup_s"]], per_layer=[by[n] for n in per_layer])


def _keep_records(monkeypatch) -> list:
    """The run records the harness hands its metric readers."""
    seen, real = [], spec.metric_reader

    def reader(name):
        read = real(name)
        return lambda rec: (seen.append(rec), read(rec))[1]
    monkeypatch.setattr(spec, "metric_reader", reader)
    return seen


def test_traced_tiny_run_names_the_engine_phases(monkeypatch):
    """``run.py``'s traced path on the CPU at a tiny size: the harness
    builds the scoped tracer, the engine's six phases are on the profiler's
    clock, the host-phase reader reads them and the breakdown lists them.
    (The CPU trace has no device plane to scope.)"""
    seen = _keep_records(monkeypatch)
    res = harness.run_cell(_tiny_cell(TINY, PART_METRICS), 5, 1.5, True,
                           t_start=time.perf_counter(), require_chip=False)
    assert res["correct"], res["checks"]
    spans = seen[0]["trace"]["host_span_s"]
    assert ENGINE_SPANS | {"tsar_engine_step", "bench.step"} <= set(spans)
    assert res["metrics"]["engine_host_ms.serve"]["value"] > 0
    assert "kv_copy_ms.serve" not in res["metrics"]
    bd = res["breakdown"]
    assert [k for k, _ in bd["device_scopes"]] == list(NAMES) + [scopes.OTHER]
    assert len(bd["host_span_s"]) <= 10
    assert dict(bd["host_span_s"]).items() <= spans.items()


FIXTURE = spec.HERE / "tests" / "data" / "chip_trace_scopes.pbtxt"


def test_recorded_chip_trace_by_part_and_phase():
    """Two decode steps of bitnet2b.chat (10 slots, view bucket 128) traced
    on one v5e, each op with its scope, and the engine's spans: the
    reduction's numbers as first read from them, and the relations any
    trace must keep."""
    out = _fixture(NAMES)
    assert out["span_s"] == pytest.approx(0.253604729)
    assert out["busy_s"] == pytest.approx(0.244958026)
    scope_s = dict(out["scope_s"])
    assert scope_s == pytest.approx({
        "kv_gather": 0.052183127, "kv_scatter": 0.044520245,
        "attention": 0.071244042, "bitlinear": 0.016477124,
        "head": 0.003524348, "other": 0.05700914})
    assert sum(scope_s.values()) == pytest.approx(out["busy_s"])
    spans = out["host_span_s"]
    assert {n for n in spans if n.startswith("engine.")} == ENGINE_SPANS
    assert spans["engine.wait"] == pytest.approx(0.246139389)
    assert spans["engine.sample"] == pytest.approx(0.00278829)
    assert spans["engine.dispatch"] == pytest.approx(0.00317938)
    # the device clock runs 1.2-1.9 ms behind the host's in these steps
    assert out["clock_offset_ms"] == pytest.approx([1.198925, 1.8917])
    idle = dict(out["idle_gaps"])
    assert idle["engine.sample"] == pytest.approx(0.002751394)
    assert idle["engine.dispatch"] == pytest.approx(0.0021932375)
    assert idle["engine.wait"] == pytest.approx(0.001221064)
    assert idle["bench.step"] + idle["tsar_engine_step"] < 0.1 * sum(
        idle.values())
    assert sum(idle.values()) == pytest.approx(out["span_s"] - out["busy_s"])


def _fixture(names) -> dict:
    return scopes.reduce_table(scopes.load(
        jax.profiler.ProfileData.from_text_proto(FIXTURE.read_text()), None,
        1, names), names)


def test_scope_set_is_the_architectures():
    """The recorded steps split by a set without ``attention``: attention's
    own ops read ``other``; every other part keeps its self time."""
    full = dict(_fixture(NAMES)["scope_s"])
    got = dict(_fixture(WITHOUT_ATTENTION)["scope_s"])
    assert list(got) == list(WITHOUT_ATTENTION) + [scopes.OTHER]
    for k in WITHOUT_ATTENTION:
        assert got[k] == pytest.approx(full[k])
    assert got[scopes.OTHER] == pytest.approx(full[scopes.OTHER]
                                              + full["attention"])


def test_new_architecture_is_one_file(tmp_path, monkeypatch):
    """A configuration whose ``model_type`` has a file of its own in
    ``archs/`` (here the dense file under another name) runs traced through
    the harness with nothing else changed: the check passes against its
    reference, and each of its parts reads a roofline share.  The CPU trace
    has no device plane, so the recorded chip steps stand in for it, and
    the v5e's peaks for the CPU's."""
    archs = tmp_path / "archs"
    archs.mkdir()
    shutil.copy(spec.ARCHS / "bitnet.py", archs / "toy.py")
    monkeypatch.setattr(spec, "ARCHS", archs)
    config = dict(TINY, model_type="toy")
    toy = spec.arch(config)
    assert toy is not DENSE and toy.__file__ == str(archs / "toy.py")

    load = scopes.load
    chip = jax.profiler.ProfileData.from_text_proto(FIXTURE.read_text())
    monkeypatch.setattr(scopes, "load", lambda pd, xspace, chips, names:
                        load(chip, None, chips, names))
    monkeypatch.setattr(harness, "_peaks",
                        lambda dev: peaks.peaks("TPU v5 lite"))
    seen = _keep_records(monkeypatch)
    res = harness.run_cell(_tiny_cell(config, PART_METRICS), 6, 1.5, True,
                           t_start=time.perf_counter(), require_chip=False)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == set(PART_METRICS)
    rec = seen[0]
    assert [k for k, _ in rec["trace"]["scope_s"]] == \
        list(scopes.step_scopes(toy)) + [scopes.OTHER]
    for part in toy.PARTS:
        assert 0 < scopes.part_roofline(rec, part) <= 100
