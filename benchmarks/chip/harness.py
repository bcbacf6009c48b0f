"""One run of one cell: load, warm, serve the window, check, report.

``run.py`` is the command; this module holds the run so that the tests can
drive all of it on the CPU at a tiny size, with the look for a chip
skipped and, for the fault tests, the timed path broken underneath.
"""
from __future__ import annotations

import functools
import gc
import sys
import time

import numpy as np

import check
import driver as driver_mod
import peaks
import scopes
import spec
import traffic
import xtrace


class NoChip(RuntimeError):
    pass


def _devices(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs[:chips]


def _peaks(dev) -> dict | None:
    """The chip's published peaks; None off a TPU."""
    return peaks.peaks(dev.device_kind) if dev.platform == "tpu" else None


def _compile_log():
    """Host times of every backend compile from now on."""
    import jax

    times: list[float] = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            times.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(listen)
    return times


def warm(engine, min_tokens: int) -> int:
    """Compile and run every step program the cell's traffic can reach, and
    no other: both step widths (the token budget, with prefill; the slots,
    pure decode) at every power-of-two view bucket from the one that holds
    the shortest prompt (no step needs less: a lone request's first chunk
    already does) to the one that holds ``max_len``, then the sampler.  The
    rows are all padding, so the pool is left as it was.  Returns the number
    of step programs warmed."""
    import jax.numpy as jnp

    b = engine.slots
    lo = engine.kv.view_blocks(min_tokens)
    top = engine.kv.view_blocks(engine.max_len)
    n = 0
    for width in sorted({engine.token_budget, b}):
        vb = lo
        while vb <= top:
            sel, engine.kv.pools = engine._flat_fn(
                engine.params, engine.kv.pools, engine.kv.table_view(vb),
                jnp.asarray(np.zeros(width, np.int32)),
                jnp.asarray(np.full(width, b, np.int32)),
                jnp.asarray(np.zeros(width, np.int32)),
                jnp.asarray(np.zeros(b, np.int32)))
            engine._sample(sel, np.zeros(b, np.float32))
            vb *= 2
            n += 1
    return n


def _counters(engine) -> dict:
    reg = engine.metrics
    out = {k: float(reg.get(k).value) for k in (
        "planned_tokens", "realized_tokens", "prefill_steps", "decode_steps",
        "total_tokens", "preemptions")}
    out["prefill_s"] = float(engine.stats["prefill_s"])
    out["decode_s"] = float(engine.stats["decode_s"])
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             prepare_engine=None, on_sample=None) -> dict:
    """Serve one run of ``cell`` and return its result line.

    ``prepare_engine(engine)`` may replace parts of the engine before the
    warm-up (the fault tests break the timed path with it);
    ``on_sample(picked, errors)`` sees the requests the check compared and
    their per-token errors (the calibration reads the control on them)."""
    import jax

    from repro.serving import ServingEngine, init_packed_params

    devs = _devices(cell.chips, require_chip)
    dev = devs[0]
    chip_peaks = _peaks(dev)
    compiles = _compile_log()

    arch = spec.arch(cell.config)
    cfg = arch.model_config(cell.config)
    eng = cell.settings["engine"]
    params = init_packed_params(cfg, arch.weight_key(seed))
    jax.block_until_ready(params)
    engine = ServingEngine(
        cfg, params, packed=True, batch_slots=eng["slots"],
        max_len=eng["max_len"], prefill_chunk=eng["prefill_chunk"],
        token_budget=eng["token_budget"], block_size=eng["block_size"],
        profile_density=False, profiler_annotations=trace)
    if prepare_engine is not None:
        prepare_engine(engine)
    warm(engine, traffic.shortest(cell.traffic["prompt_len"]))
    engine.reset_run_stats()

    planned = traffic.plan(cell.traffic, cell.settings, seed, seconds,
                           cell.config["vocab_size"])
    drv = driver_mod.Driver(engine, planned, cell.config,
                            loop=cell.traffic["loop"],
                            clients=int(cell.settings.get("clients", 0)))
    opened = {}
    tracer = xtrace.WindowTracer(cell.chips, functools.partial(
        scopes.reduce_xspace, names=scopes.step_scopes(arch))) \
        if trace else None
    record = drv.run(float(cell.settings["preroll_s"]), seconds,
                     on_open=lambda: opened.setdefault(
                         "t", time.perf_counter()),
                     tracer=tracer, counters=lambda: _counters(engine))
    w0 = opened["t"]
    record["setup_s"] = w0 - t_start
    record["compiles_in_window"] = sum(1 for t in compiles
                                       if w0 <= t <= w0 + seconds)
    record["pool_blocks"] = engine.kv.num_blocks - 1
    record["config"] = cell.config
    record["peaks"] = chip_peaks
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0)) for d in devs)
    finished = drv.finished()
    served = drv.served_logits()
    del drv, engine, params
    gc.collect()

    if tracer is not None:
        record["trace"] = tracer.reduce()

    chk = cell.settings["check"]
    picked = check.sample(finished, seed, int(chk["sample_requests"]))
    numbers = {}
    n_tokens = 0
    if picked:
        err = check.errors(cell.config, seed, picked, served,
                           pad_to=eng["max_len"])
        n_tokens = int(err.size)
        if np.isfinite(err).all():
            numbers["logit_mse"] = float(np.mean(np.square(err)))
        if on_sample is not None:
            on_sample(picked, err)
    correct, compared = check.judge(numbers, dict(chk["limits"]))
    print(f"served tokens compared: {n_tokens} in {len(picked)} requests",
          file=sys.stderr)

    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        value = spec.metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    window_reqs = [r for r in record["requests"]
                   if r["due"] is not None and 0 <= r["due"] < seconds]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(window_reqs),
              "failed": sum(1 for r in window_reqs if not r["stamps"]),
              "metrics": metrics, "device": device}
    tr = record.get("trace") if trace else None
    if tr:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {
            "device_ops": tr["device_ops"],
            "idle_gaps": tr["idle_gaps"][:10],
            "device_scopes": xtrace.top(dict(tr["scope_s"])),
            "host_span_s": xtrace.top(tr["host_span_s"])}
        if tr["busy_s"] and not any(v for k, v in tr["scope_s"]
                                    if k != scopes.OTHER):
            print("no device op fell under a step scope: were the programs "
                  "loaded from a compile cache filled by code without the "
                  "scopes?", file=sys.stderr)
    result["checks"] = compared
    for name, c in compared.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return result
