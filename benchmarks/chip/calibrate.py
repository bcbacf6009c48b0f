"""Readings that set a cell's numbers: the knee, the correctness limit, the
spread of a metric over runs.

    python3 benchmarks/chip/calibrate.py sweep  --workload bitnet2b.chat \
        --seed 1 --seconds 20 --rates 1.0 1.5 2.0 2.5
    python3 benchmarks/chip/calibrate.py limits --workload bitnet2b.chat \
        --seconds 8 --seeds 11 12 13 --control-seeds 11 12 13
    python3 benchmarks/chip/calibrate.py faults --workload bitnet2b.chat \
        --seconds 20 --preroll 10 --seed 71
    python3 benchmarks/chip/calibrate.py spread --runs runs.jsonl

``sweep`` finds the knee of an open-loop cell: one engine, warmed once,
serves the mix at each offered rate in turn (each window followed by an
untimed drain), and prints what was offered against what was served.
``limits`` runs the cell as ``run.py`` does at each seed, with a short
window, and prints the program's per-token logit errors (mean square,
widest, median); for the control seeds it also reads, on the same
sample, the errors of each control precision named, and whether the
control comes out correct at the cell's limit (``check.judge``).
``faults`` reads, at the cell's own size, the control at the cell's limit
on a sound run and then each fault of ``faults.py`` planted under the
timed path, each run with the given pre-roll and window.  ``spread`` reads
runs made with ``run.py`` and prints each metric's median and spread (the
distance between the quartiles over the median) per set.  Each line is
JSON.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def sweep(cell, seed: int, seconds: float, rates: list[float]) -> None:
    import jax

    import driver as driver_mod
    import harness
    import readers
    import spec
    import stats
    import traffic
    from repro.serving import ServingEngine, init_packed_params

    harness._devices(cell.chips, True)
    arch = spec.arch(cell.config)
    cfg = arch.model_config(cell.config)
    eng = cell.settings["engine"]
    params = init_packed_params(cfg, arch.weight_key(seed))
    jax.block_until_ready(params)
    engine = ServingEngine(
        cfg, params, packed=True, batch_slots=eng["slots"],
        max_len=eng["max_len"], prefill_chunk=eng["prefill_chunk"],
        token_budget=eng["token_budget"], block_size=eng["block_size"],
        profile_density=False)
    t0 = time.perf_counter()
    n = harness.warm(engine, traffic.shortest(cell.traffic["prompt_len"]))
    print(json.dumps({"warmed_programs": n,
                      "warm_s": time.perf_counter() - t0}), flush=True)
    for rate in rates:
        settings = dict(cell.settings, rate_rps=rate)
        engine.reset_run_stats()
        planned = traffic.plan(cell.traffic, settings, seed, seconds,
                               cell.config["vocab_size"])
        drv = driver_mod.Driver(engine, planned, cell.config,
                                loop=cell.traffic["loop"])
        rec = drv.run(float(settings["preroll_s"]), seconds,
                      counters=lambda: harness._counters(engine))
        drv.drain()
        win = readers.window_requests(rec)
        served = [r for r in rec["requests"] if r["done"]
                  and readers.in_window(rec, r["stamps"][-1])]
        queued_at_close = sum(1 for r in rec["requests"]
                              if r["due"] < seconds and (
                                  not r["stamps"] or r["stamps"][0] >= seconds))
        print(json.dumps({
            "rate_offered": rate, "due_in_window": len(win),
            "finished_in_window_per_s": len(served) / seconds,
            "queued_at_close": queued_at_close,
            "ttft_p90_s": stats.percentile(readers.ttfts(rec), 90),
            "ttft_p50_s": stats.percentile(readers.ttfts(rec), 50),
            "itl_p95_ms": readers.percentile_ms(readers.token_gaps(rec), 95),
            "itl_p50_ms": readers.percentile_ms(readers.token_gaps(rec), 50),
            "prefill_step_ms": readers.per_step_ms(rec, "prefill"),
            "decode_step_ms": readers.per_step_ms(rec, "decode"),
            "output_tok_s": sum(1 for r in rec["requests"] for t in r["stamps"]
                                if readers.in_window(rec, t)) / seconds}),
              flush=True)


def _summary(err) -> dict:
    import numpy as np

    return {"mse": float(np.mean(np.square(err))), "max": float(np.max(err)),
            "p50": float(np.median(err)), "tokens": int(np.size(err))}


def _control_reader(cell, seed: int, lows: list[str], got: dict):
    """An ``on_sample`` that reads the program's errors and, for each control
    precision, its errors and whether it passes the cell's limit."""
    import check

    limits_ = dict(cell.settings["check"]["limits"])

    def on_sample(picked, err):
        got["program"] = _summary(err)
        for low in lows:
            s = _summary(check.errors(
                cell.config, seed, picked, {},
                pad_to=cell.settings["engine"]["max_len"], low=low))
            s["correct"] = check.judge({"logit_mse": s["mse"]}, limits_)[0]
            got[low] = s
    return on_sample


def limits(cell, seconds: float, seeds: list[int], control: set[int],
           lows: list[str]) -> None:
    import harness

    for seed in seeds:
        got = {}
        on_sample = _control_reader(cell, seed,
                                    lows if seed in control else [], got)

        t0 = time.perf_counter()
        res = harness.run_cell(cell, seed, seconds, False, t_start=t0,
                               on_sample=on_sample)
        print(json.dumps({"seed": seed, "logit_err": got,
                          "attempted": res["attempted"],
                          "failed": res["failed"], "metrics": res["metrics"],
                          "run_s": time.perf_counter() - t0}), flush=True)


def plant(cell, seed: int, seconds: float, preroll: float, names: list[str],
          lows: list[str], require_chip: bool = True) -> None:
    import dataclasses

    import faults
    import harness

    cell = dataclasses.replace(cell, settings=dict(cell.settings,
                                                   preroll_s=preroll))
    for name in ["sound"] + names:
        got = {}
        t0 = time.perf_counter()
        res = harness.run_cell(
            cell, seed, seconds, False, t_start=t0,
            require_chip=require_chip, prepare_engine=faults.ALL.get(name),
            on_sample=_control_reader(cell, seed, lows, got)
            if name == "sound" else None)
        print(json.dumps({"run": name, "seed": seed,
                          "correct": res["correct"], "checks": res["checks"],
                          "control": got, "attempted": res["attempted"],
                          "failed": res["failed"],
                          "run_s": time.perf_counter() - t0}), flush=True)


def spreads(path: str) -> None:
    """Per metric and set of runs (``--trace 0`` lines of a JSON-lines file,
    each ``{"set": ..., "trace": 0, "result": <run.py's line>}``): the
    median, the spread and the values in run order."""
    import statistics

    import stats

    sets: dict = {}
    with open(path) as f:
        for line in f:
            run = json.loads(line)
            if run["trace"] == 0 and "metrics" in run["result"]:
                for name, m in run["result"]["metrics"].items():
                    sets.setdefault(name, {}).setdefault(
                        run["set"], []).append(m["value"])
    for name, by_set in sets.items():
        for set_name, xs in by_set.items():
            print(json.dumps({"metric": name, "set": set_name,
                              "median": statistics.median(xs),
                              "spread": stats.spread(xs) if len(xs) > 1
                              else None, "values": xs}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("sweep", "limits", "faults", "spread"))
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--runs", help="spread: JSON lines of runs")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--controls", nargs="*", default=["float8"],
                    choices=("bfloat16", "float8"))
    ap.add_argument("--preroll", type=float, default=10.0)
    ap.add_argument("--faults", nargs="*", default=["state_unchanged",
                                                    "half_batch"])
    args = ap.parse_args()
    if args.mode == "spread":
        spreads(args.runs)
        return 0

    import jax

    import spec
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.resolve(args.workload)
    if args.mode == "sweep":
        sweep(cell, args.seed, args.seconds, args.rates)
    elif args.mode == "faults":
        plant(cell, args.seed, args.seconds, args.preroll, args.faults,
              args.controls)
    else:
        limits(cell, args.seconds, args.seeds, set(args.control_seeds),
               args.controls)
    return 0


if __name__ == "__main__":
    sys.exit(main())
