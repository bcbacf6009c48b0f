"""The trace reduction on the CPU: on a hand-made table, and on a few steps
of a trace recorded on a TPU v5e, trimmed and kept as a text proto."""
from pathlib import Path

import jax
import pytest

import xtrace

FIXTURE = Path(__file__).with_name("data") / "chip_trace_steps.pbtxt"


def _table():
    # Device ops (ns): [10, 30) and [20, 40) overlap, then [60, 70).
    # Host: a step span [0, 80) holding the engine's jitted call [5, 45),
    # then a stamp span [70, 80).  Span of the table: 0..80.
    return {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 10, 30], ["copy.2", 20, 40],
                    ["fusion.1", 60, 70]],
            "modules": [["jit_step", 10, 40], ["jit_argmax", 60, 70]]}},
        "host": [["bench.step", 0, 60], ["tsar_engine_step", 5, 45],
                 ["bench.stamp", 70, 80]],
    }


def test_reduce_by_hand():
    out = xtrace.reduce_table(_table())
    assert out["span_s"] == pytest.approx(80e-9)
    assert out["busy_s"] == pytest.approx(40e-9)          # 30 + 10
    assert out["program_s"] == pytest.approx(30e-9 + 10e-9)
    assert dict(out["device_ops"]) == pytest.approx(
        {"fusion.1": 30e-9, "copy.2": 20e-9})
    # idle: [0,10) in the step (5..10 in the engine call), [40,60) in the
    # step, [70,80) in the stamp
    assert dict(out["idle_gaps"]) == pytest.approx({
        "bench.step": 5e-9 + 15e-9, "tsar_engine_step": 5e-9 + 5e-9,
        "bench.stamp": 10e-9})


def test_text_proto_round_trip():
    table = _table()
    pd = jax.profiler.ProfileData.from_text_proto(xtrace.to_text_proto(table))
    back = xtrace.load(pd)
    assert back["devices"]["/device:TPU:0"] == table["devices"]["/device:TPU:0"]
    assert sorted(back["host"]) == sorted(table["host"])


@pytest.mark.parametrize("arrivals, start", [
    ((), 96.0),                       # no schedule: the last 4 s
    ((93.0, 95.0), 92.0),             # 1 s before the earliest with room
    ((89.0, 99.0), 96.0),             # one too early, one with no room left
    ((91.5,), 90.5),                  # up to 10 s, never the first half
])
def test_trace_slice_holds_arrivals_with_room(arrivals, start):
    tr = xtrace.WindowTracer(1, None)
    tr.plan(50.0, 100.0, arrivals)
    assert (tr.start_at, tr.stop_at) == (pytest.approx(start), 100.0)
    tr.plan(0.0, 1.5, (0.9,))         # a short window: its second half
    assert tr.start_at == pytest.approx(0.75)


def test_recorded_chip_trace():
    """Two engine steps of bitnet2b.chat (10 slots, view buckets 256 and
    512) traced on one v5e: the reduction's numbers as first read from
    them, and the relations any trace must keep."""
    table = xtrace.load(jax.profiler.ProfileData.from_text_proto(
        FIXTURE.read_text()))
    out = xtrace.reduce_table(table)
    assert out["span_s"] == pytest.approx(0.077825467)
    assert out["busy_s"] == pytest.approx(0.065699537)
    # Each view bucket is a program of its own: both steps' programs (23.2
    # and 42.5 ms) count, with the sampler's two argmax runs.
    steps = [e - s for n, s, e in table["devices"]["/device:TPU:0"]["modules"]
             if n.startswith("jit__lambda")]
    assert sorted(steps) == pytest.approx([0.023200791e9, 0.042462828e9])
    assert out["program_s"] == pytest.approx(
        0.023200791 + 0.042462828 + 1.8664e-05 + 1.8468e-05)
    name, secs = out["device_ops"][0]
    assert name.startswith("%while.4 = (s32[], f32[1,266,2560]")
    assert len(name) <= xtrace.OP_NAME_CHARS and "{" not in name
    assert secs == pytest.approx(0.027616841)
    idle = dict(out["idle_gaps"])
    assert idle["tsar_engine_step"] == pytest.approx(0.005776281)
    assert idle["bench.step"] == pytest.approx(0.004089329)
    assert sum(idle.values()) == pytest.approx(out["span_s"] - out["busy_s"])
