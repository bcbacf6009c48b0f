"""The benchmark's arithmetic on the CPU, with no timing claims: operation
and byte counts against hand counts, latency and rate arithmetic on
synthetic stamps, and every cell resolving its files by name, its
architecture file by ``model_type``."""
import json
import statistics

import numpy as np
import pytest

import readers
import spec
import stats
import traffic

BITNET = json.loads((spec.HERE / "configs" / "bitnet2b.json").read_text())
CONFIGS = {p.stem: json.loads(p.read_text())
           for p in sorted((spec.HERE / "configs").glob("*.json"))}

# bitnet-2b-4t per layer: q, o 2560x2560; k, v 2560x640; gate, up 2560x6912;
# down 6912x2560: 6,553,600*2 + 1,638,400*2 + 17,694,720*3 = 69,468,160
# weights, 30 layers.  Attention 4*H*Dh = 10,240 ops per key per layer.
# Head 2560 x 128,256.  KV 30 layers x (k, v) x 5 x 128 x 2 B = 76,800 B/token.
# Planes 69,468,160/4 B + 2 B x 22,784 output channels, per layer.
HAND = {
    # one decode token at position 999 (its context: 1000 keys)
    "decode": ([(1, 1000)], 1,
               2 * 30 * 69_468_160 + 30 * 10_240 * 1000 + 2 * 2560 * 128_256,
               30 * (69_468_160 // 4 + 2 * 22_784) + 61 * 2560 * 2
               + 2560 * 2 + 2560 * 128_256 * 2 + 76_800 * 1000),
    # one 256-token prefill chunk at positions 0..255, one emitted row
    "prefill": ([(256, 256)], 1,
                256 * 2 * 30 * 69_468_160 + 30 * 10_240 * (256 * 257 // 2)
                + 2 * 2560 * 128_256,
                30 * (69_468_160 // 4 + 2 * 22_784) + 61 * 2560 * 2
                + 256 * 2560 * 2 + 2560 * 128_256 * 2 + 76_800 * 256),
}


@pytest.mark.parametrize("case", sorted(HAND))
def test_counts_match_hand_counts(case):
    slots, emit, ops, nbytes = HAND[case]
    assert spec.arch(BITNET).step_counts(BITNET, slots, emit) == (ops, nbytes)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_add_over_slots(name):
    config = CONFIGS[name]
    count = lambda slots, emit: spec.arch(config).step_counts(  # noqa: E731
        config, slots, emit)
    a = count([(1, 500)], 1)
    b = count([(3, 40)], 0)
    both = count([(1, 500), (3, 40)], 1)
    fixed = count([], 0)   # planes, norms, head bytes
    assert both[0] == pytest.approx(a[0] + b[0])
    assert both[1] == pytest.approx(a[1] + b[1] - fixed[1])


def _record():
    # Window of 10 s.  Request 0 was due before the window (pre-roll); 3
    # never got a first token; the last step ended at 12.0 s.
    reqs = [
        {"due": -1.0, "submit": -1.0, "admit": -0.9, "stamps": [0.5, 0.7]},
        {"due": 1.0, "submit": 1.002, "admit": 1.1,
         "stamps": [1.5, 1.6, 1.8]},
        {"due": 2.0, "submit": 2.01, "admit": 2.2, "stamps": [3.0, 3.1]},
        {"due": 9.5, "submit": 9.5, "admit": None, "stamps": []},
    ]
    steps = [{"t0": 11.9, "t1": 12.0, "kv_blocks": 7}]
    return {"window_s": 10.0, "requests": reqs, "steps": steps,
            "counters": {"open": {"planned_tokens": 100, "realized_tokens": 10,
                                  "prefill_s": 1.0, "prefill_steps": 2,
                                  "decode_s": 1.0, "decode_steps": 10},
                         "close": {"planned_tokens": 300, "realized_tokens": 60,
                                   "prefill_s": 1.5, "prefill_steps": 4,
                                   "decode_s": 1.2, "decode_steps": 20}},
            "setup_s": 33.0, "compiles_in_window": 0, "pool_blocks": 14}


def test_ttft_counts_from_due_time_and_failures_until_the_end():
    rec = _record()
    # due in window: 1.0 -> 0.5, 2.0 -> 1.0, 9.5 -> 12.0 - 9.5 = 2.5
    assert sorted(readers.ttfts(rec)) == pytest.approx([0.5, 1.0, 2.5])
    assert spec.metric_reader("ttft_p50_s")(rec) == pytest.approx(1.0)


def test_itl_from_stamps_over_all_requests():
    rec = _record()
    gaps = [0.2, 0.1, 0.2, 0.1]     # 0.5->0.7, 1.5->1.6->1.8, 3.0->3.1
    assert sorted(readers.token_gaps(rec)) == pytest.approx(sorted(gaps))
    assert spec.metric_reader("itl_p95_ms")(rec) == pytest.approx(
        1e3 * np.percentile(gaps, 95))


def test_rates_and_counters_over_the_whole_window():
    rec = _record()
    assert spec.metric_reader("output_tok_s")(rec) == pytest.approx(7 / 10)
    assert spec.metric_reader("step_fill.batch")(rec) == pytest.approx(25.0)
    assert spec.metric_reader("prefill_step_ms")(rec) == pytest.approx(250.0)
    assert spec.metric_reader("decode_step_ms")(rec) == pytest.approx(20.0)
    assert spec.metric_reader("gen_lag_p99_ms")(rec) == pytest.approx(
        1e3 * np.percentile([0.002, 0.01, 0.0], 99))
    assert spec.metric_reader("queue_wait_p90_s")(rec) == pytest.approx(
        np.percentile([0.1, 0.2], 90))
    assert spec.metric_reader("setup_s")(rec) == 33.0
    assert spec.metric_reader("kv_peak_frac")(rec) is None   # step after close


def test_trace_metrics_read_nothing_without_a_trace():
    rec = _record()
    for name in ("step_mfu.serve", "step_roofline.serve", "device_idle.serve"):
        assert spec.metric_reader(name)(rec) is None


def test_spread_is_the_driver_definition():
    xs = [10.0, 11.0, 9.0, 10.5, 9.5, 10.2]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


BENCH = spec.load_benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_its_files_by_name(workload):
    cell = spec.resolve(workload, BENCH)
    assert cell.config["name"] == workload.split(".")[0]
    assert cell.traffic["loop"] in ("open", "closed")
    assert set(cell.settings["check"]["limits"]) == {"logit_mse"}
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    entry = next(c for c in BENCH["configs"] if c["name"] == cell.config["name"])
    assert entry["reduced"] == cell.config["reduced"]
    model = spec.arch(cell.config).model_config(cell.config)
    assert model.d_model == cell.config["hidden_size"]


def test_unknown_model_type_names_the_file():
    with pytest.raises(FileNotFoundError, match=r"archs/mamba9\.py"):
        spec.arch(dict(BITNET, model_type="mamba9"))


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_seed_gets_the_same_work(workload):
    cell = spec.resolve(workload, BENCH)
    runs = [traffic.plan(cell.traffic, cell.settings, seed, 45.0, 1000)
            for seed in (1, 2**40 + 3)]
    work = lambda ps: [(p.due, len(p.prompt), p.max_new) for p in ps]  # noqa: E731
    assert work(runs[0]) == work(runs[1])
    assert any((a.prompt != b.prompt).any() for a, b in zip(*runs))
