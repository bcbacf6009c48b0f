"""A whole run of the harness on the CPU at a tiny size, with the look for a
chip skipped: a sound run is correct, and a run whose timed path is broken
underneath is not, for each fault a serving cell can have."""
import time

import json
from types import SimpleNamespace

import numpy as np
import pytest

import calibrate
import check
import faults
import harness
import spec

TINY = {
    "name": "tiny", "source": "test", "model_type": "bitnet",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "hidden_act": "silu", "reduced": [],
}
TRAFFIC = {
    "name": "tiny", "loop": "open", "arrival": {"kind": "poisson"},
    "prompt_len": {"kind": "uniform", "lo": 8, "hi": 40},
    "output_len": {"kind": "uniform", "lo": 4, "hi": 12},
}
# A limit for this tiny model on the CPU only: sound runs read a mean
# squared logit error of float32 rounding (under 1e-13), the bfloat16
# control one of bfloat16 rounding (about 1e-5), and every fault below one
# of the logits' own size.
LIMIT = 1e-8


def _cell(**settings):
    s = {"engine": {"slots": 3, "token_budget": 19, "prefill_chunk": 16,
                    "max_len": 64, "block_size": 16},
         "rate_rps": 8.0, "preroll_s": 0.3,
         "check": {"sample_requests": 64, "limits": {"logit_mse": LIMIT}}}
    s.update(settings)
    bench = spec.load_benchmark()
    by = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    return spec.Cell(name="tiny.chat", chips=1, config=TINY, traffic=TRAFFIC,
                     settings=s,
                     end_to_end=[by[n] for n in ("ttft_p50_s", "itl_p95_ms",
                                                 "output_tok_s", "setup_s")],
                     per_layer=[by[n] for n in ("gen_lag_p99_ms",
                                                "window_compiles",
                                                "queue_wait_p90_s",
                                                "kv_peak_frac",
                                                "prefill_step_ms",
                                                "decode_step_ms")])


def _run(prepare=None, trace=False, seed=3):
    return harness.run_cell(_cell(), seed, 1.5, trace, t_start=time.perf_counter(),
                            require_chip=False, prepare_engine=prepare)


def test_no_chip_no_result():
    with pytest.raises(harness.NoChip):
        harness.run_cell(_cell(), 0, 1.0, False, t_start=0.0)


def test_sound_run_is_correct_and_reports_its_metrics():
    res = _run(seed=2**40 + 11)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p50_s", "itl_p95_ms", "output_tok_s",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_traced_run_reports_per_layer_metrics():
    res = _run(trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert {"gen_lag_p99_ms", "queue_wait_p90_s", "kv_peak_frac",
            "prefill_step_ms", "decode_step_ms"} <= set(res["metrics"])


@pytest.mark.parametrize("fault", sorted(faults.ALL))
def test_broken_timed_path_is_not_correct(fault):
    res = _run(prepare=faults.ALL[fault])
    assert not res["correct"]
    assert res["checks"]["logit_mse"]["value"] > LIMIT


def test_bfloat16_control_is_not_correct():
    """The control: the reference one precision lower, in the program's
    place, on the requests a sound run compared, fails the same limit."""
    seen = {}

    def on_sample(picked, err):
        seen["program"] = err
        seen["control"] = check.errors(TINY, 5, picked, {}, pad_to=64,
                                       low="bfloat16")

    res = harness.run_cell(_cell(), 5, 1.5, False,
                           t_start=time.perf_counter(), require_chip=False,
                           on_sample=on_sample)
    assert res["correct"], res["checks"]
    assert seen["program"].size == seen["control"].size > 50
    control = {"logit_mse": float(np.mean(np.square(seen["control"])))}
    assert not check.judge(control, {"logit_mse": LIMIT})[0]


def test_calibrate_reads_the_control_and_faults_at_the_cells_limit(capsys):
    """``calibrate.py faults`` as the chip runs it, at the tiny size: the
    sound run is correct, and the control and each fault are judged at the
    cell's limit and are not."""
    calibrate.plant(_cell(), 7, 1.5, 0.3, ["state_unchanged", "half_batch"],
                    ["bfloat16"], require_chip=False)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["run"] for x in lines] == ["sound", "state_unchanged",
                                         "half_batch"]
    assert lines[0]["correct"] and lines[0]["control"]["bfloat16"]["tokens"]
    assert not lines[0]["control"]["bfloat16"]["correct"]
    assert not any(x["correct"] for x in lines[1:])


def test_error_counts_a_token_the_reference_would_not_choose():
    """A served token is held to the reference's choice: one the reference
    would not choose counts by the reference's own margin, even where the
    program's logit of it equals the reference's best (a slot handed the
    logits of another row)."""
    prompt = np.arange(1, 9, dtype=np.int32)
    greedy: list = []
    for _ in range(3):
        seq = np.concatenate([prompt, np.asarray(greedy, np.int32)])
        logits = spec.arch(TINY).logits_at(TINY, 1, [seq],
                                           [np.array([len(seq) - 1])])
        greedy.append(int(np.argmax(np.asarray(logits)[0])))

    def best_and_at(req):
        seqs, rows = check._teacher_forced([req])
        ref = np.asarray(spec.arch(TINY).logits_at(TINY, 1, seqs, rows,
                                                  pad_to=64))
        return ref.max(-1), ref[np.arange(len(req.out_tokens)), req.out_tokens]

    sound = SimpleNamespace(uid=0, prompt=prompt, out_tokens=greedy)
    _, at = best_and_at(sound)
    err = check.errors(TINY, 1, [sound], {0: dict(enumerate(at))}, pad_to=64)
    assert np.mean(np.square(err)) < LIMIT

    v = TINY["vocab_size"]
    wrong = SimpleNamespace(uid=0, prompt=prompt,
                            out_tokens=[(t + 1) % v for t in greedy])
    best, at = best_and_at(wrong)
    err = check.errors(TINY, 1, [wrong], {0: dict(enumerate(best))}, pad_to=64)
    # the margin, and the program's logit as far above the token's again
    assert err == pytest.approx(2 * (best - at), abs=1e-5)
    assert np.mean(np.square(err)) > LIMIT
