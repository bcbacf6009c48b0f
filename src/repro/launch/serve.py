"""Production serving driver: packed 2-bit T-SAR weights, batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch bitnet-2b-4t --smoke \
        --requests 8 --max-new 16

Latency is reported as p50/p90/p99 straight off the engine's metrics
registry (``repro.obs.metrics`` histograms).  ``--trace-out PATH`` records
the run as a Perfetto ``trace_event`` timeline (request lifecycle spans +
step/counter tracks) — inspect with ``python -m repro.obs.timeline PATH``
or load it in https://ui.perfetto.dev; see docs/observability.md.

Durable-telemetry flags (all composable):

* ``--trace-stream PATH``  — stream events to a rotated JSONL file with
  bounded memory (``repro.obs.trace.StreamingSink``); analyze with the
  same timeline CLI.  Combine with ``--trace-out`` to record both ways.
* ``--incident-dir DIR``   — arm incident snapshots (SLO breach,
  preemption, rejection, kv pressure, eviction storm); each dump carries
  the flight-recorder ring + a metrics snapshot.  Without another trace
  flag this attaches a ring-buffer tracer automatically.
* ``--metrics-port PORT``  — Prometheus scrape endpoint over the live
  registry (``/metrics`` text, ``/metrics.json`` snapshot); port 0 binds
  an ephemeral port and prints it.
* ``--metrics-textfile PATH`` — atomically rewrite a Prometheus textfile
  every ``--metrics-interval`` seconds (node-exporter textfile style).
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np
import jax

import repro.configs as configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo as zoo
from repro.obs import export as obs_export
from repro.obs.incident import IncidentMonitor
from repro.obs.trace import EventTracer, MemorySink, RingSink, StreamingSink, TeeSink
from repro.plan import ModelPlan, format_plan
from repro.serving import Request, ServingEngine, init_packed_params


def _print_percentiles(engine) -> None:
    pct = engine.latency_percentiles()
    t, p = pct["ttft_s"], pct["tpot_s"]
    print(f"TTFT p50/p90/p99 {t['p50'] * 1e3:.0f}/{t['p90'] * 1e3:.0f}/"
          f"{t['p99'] * 1e3:.0f}ms | TPOT p50/p99 {p['p50'] * 1e3:.2f}/"
          f"{p['p99'] * 1e3:.2f}ms | "
          f"queue p99 {pct['queue_s']['p99'] * 1e3:.0f}ms | "
          f"policy={engine.policy}")


def _save_trace(tracer, path: str) -> None:
    doc = tracer.save(path)
    print(f"obs trace: {path} ({len(doc['traceEvents'])} events, "
          f"{doc['otherData']['fingerprint'][:23]}…) — analyze with "
          f"python -m repro.obs.timeline {path}", file=sys.stderr)


def _obs_setup(args) -> dict:
    """Build the tracer (sink composition per flags) + incident monitor.
    Returns the state dict the start/finish helpers thread through."""
    sinks, stream = [], None
    if args.trace_out:
        sinks.append(MemorySink())
    if args.trace_stream:
        stream = StreamingSink(args.trace_stream)
        sinks.append(stream)
    if not sinks and args.incident_dir:
        # Flight recorder: incidents need *some* recent-event source, and a
        # ring is cheap enough to attach implicitly.
        sinks.append(RingSink())
    tracer = None
    if sinks:
        tracer = EventTracer(sink=sinks[0] if len(sinks) == 1
                             else TeeSink(*sinks))
    monitor = IncidentMonitor(args.incident_dir) if args.incident_dir else None
    return {"tracer": tracer, "stream": stream, "monitor": monitor,
            "server": None, "textfile": None}


def _obs_start(args, engine, obs: dict) -> None:
    """Bring up the export surface once the engine (and its registry)
    exists."""
    if args.metrics_port is not None:
        obs["server"] = obs_export.start_server(engine.metrics,
                                                port=args.metrics_port)
        print(f"metrics: scrape endpoint at {obs['server'].url} "
              f"(and /metrics.json)", file=sys.stderr)
    if args.metrics_textfile:
        obs["textfile"] = obs_export.TextfileWriter(
            engine.metrics, args.metrics_textfile,
            interval_s=args.metrics_interval).start()


def _obs_finish(args, obs: dict) -> None:
    """Flush/close every durable-telemetry surface at end of run."""
    if obs["textfile"] is not None:
        obs["textfile"].stop()
        print(f"metrics: textfile {args.metrics_textfile} "
              f"({obs['textfile'].n_writes} writes)", file=sys.stderr)
    if obs["server"] is not None:
        obs["server"].stop()
    if obs["tracer"] is not None and args.trace_out:
        _save_trace(obs["tracer"], args.trace_out)
    if obs["stream"] is not None:
        info = obs["stream"].finalize()
        print(f"obs stream: {info['path']} ({info['n_events']} events, "
              f"{info['segments']} segment(s), "
              f"{info['fingerprint'][:23]}…) — analyze with "
              f"python -m repro.obs.timeline {info['path']}", file=sys.stderr)
    mon = obs["monitor"]
    if mon is not None:
        s = mon.summary()
        if s["n"]:
            by = ", ".join(f"{k}: {v}" for k, v in sorted(s["by_trigger"].items()))
            print(f"incidents: {s['n']} snapshot(s) in {args.incident_dir} "
                  f"({by}; {s['suppressed']} debounced)", file=sys.stderr)
        else:
            print(f"incidents: none fired ({s['suppressed']} debounced)",
                  file=sys.stderr)


def _init_params(cfg, packed: bool) -> dict:
    """Random weights from seed 0: frozen to 2-bit planes inside the init
    program when serving packed, so full widths fit on one chip."""
    key = jax.random.PRNGKey(0)
    return init_packed_params(cfg, key) if packed else zoo.init_params(cfg, key)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--no-packed", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--plan-file", default=None, metavar="PATH",
                    help="execution-plan JSON: loaded if it exists (skips "
                         "re-costing), otherwise the compiled plan is saved "
                         "there (compile-once/serve-many)")
    ap.add_argument("--save-plan", default=None, metavar="PATH",
                    help="also write the engine's plan JSON here after init")
    ap.add_argument("--print-plan", action="store_true",
                    help="print the per-layer, per-bucket plan table")
    ap.add_argument("--prefix-cache", nargs="?", const=True, default=False,
                    type=int, metavar="CAPACITY_BLOCKS",
                    help="enable prefix-caching KV reuse; optional value "
                         "caps the cached-block footprint (LRU-evicted)")
    ap.add_argument("--workload", default=None, metavar="NAME",
                    help="serve a generated benchmark workload instead of "
                         "the built-in request list (see "
                         "benchmarks.workloads.WORKLOADS; requires running "
                         "from the repo root) and report percentile "
                         "TTFT/TPOT + goodput under the trace's SLOs")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="workload trace JSON: loaded if it exists "
                         "(replayed verbatim), otherwise the trace "
                         "generated by --workload is saved there")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload generation seed (with --workload)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record the run and save a Perfetto trace_event "
                         "JSON timeline (python -m repro.obs.timeline PATH "
                         "to analyze; docs/observability.md)")
    ap.add_argument("--profile-steps", action="store_true",
                    help="mark each engine step and its host phases "
                         "(admit, plan, dispatch, wait, sample, emit) with "
                         "jax.profiler spans so XLA device traces align "
                         "with them (docs/observability.md)")
    ap.add_argument("--trace-stream", default=None, metavar="PATH",
                    help="stream trace events to a rotated JSONL file "
                         "(bounded memory; OBS_TRACE_STREAM schema v1) — "
                         "same timeline CLI analyzes it")
    ap.add_argument("--incident-dir", default=None, metavar="DIR",
                    help="write incident snapshots (ring buffer + metrics "
                         "snapshot) here when SLO/preemption/rejection/"
                         "kv-pressure/eviction-storm triggers fire")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve Prometheus text exposition at "
                         "http://127.0.0.1:PORT/metrics (0 = ephemeral)")
    ap.add_argument("--metrics-textfile", default=None, metavar="PATH",
                    help="periodically rewrite a Prometheus textfile "
                         "(atomic replace) for scrape-less environments")
    ap.add_argument("--metrics-interval", type=float, default=5.0,
                    metavar="SECONDS",
                    help="rewrite interval for --metrics-textfile")
    args = ap.parse_args()
    enable_compile_cache()

    if args.workload or args.trace_file:
        return serve_workload(args)

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    params = _init_params(cfg, packed=not args.no_packed)
    plan = None
    if args.plan_file and os.path.exists(args.plan_file):
        plan = ModelPlan.load(args.plan_file)
        print(f"plan: loaded {args.plan_file} ({len(plan.layers)} layers, "
              f"buckets {list(plan.buckets)})")
    obs = _obs_setup(args)
    engine = ServingEngine(cfg, params, max_len=args.max_len,
                           batch_slots=args.slots, packed=not args.no_packed,
                           plan=plan, prefix_cache=args.prefix_cache,
                           tracer=obs["tracer"], incidents=obs["monitor"],
                           profiler_annotations=args.profile_steps)
    _obs_start(args, engine, obs)
    if engine.plan is not None:
        if plan is None and args.plan_file:
            engine.plan.save(args.plan_file)
            print(f"plan: compiled and saved to {args.plan_file}")
        if args.save_plan:
            engine.plan.save(args.save_plan)
        s = engine.plan.summary()
        print(f"plan: {s['layers']} layers | decode -> {s['decode_kernel']} | "
              f"prefill -> {s['prefill_kernel']}")
        if args.print_plan:
            print(format_plan(engine.plan))

    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=4 + i % 8),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature)
            for i in range(args.requests)]
    engine.run(reqs)
    for r in reqs[:4]:
        print(f"req {r.uid}: {r.out_tokens}")
    print(f"prefill {engine.stats['prefill_s']:.2f}s | "
          f"decode {engine.stats['decode_s']:.2f}s | "
          f"{engine.throughput():.1f} tok/s steady-state "
          f"({'packed 2-bit' if not args.no_packed else 'latent fp'})")
    _print_percentiles(engine)
    if engine.prefix is not None:
        print(f"prefix cache: hit rate {engine.stats['prefix_hit_rate']:.2f} | "
              f"{engine.stats['cached_blocks']} cached blocks | "
              f"{engine.stats['prefix_evictions']} evictions")
    _obs_finish(args, obs)


def serve_workload(args):
    """Trace-driven serving: generate (or load) a benchmark workload trace,
    replay it in virtual time, and report percentile latencies + goodput.

    The ``benchmarks`` package lives at the repo root (not under ``src``),
    so this path requires launching from the repository root.
    """
    try:
        from benchmarks.workloads import generator, metrics, runner
        from benchmarks.workloads.trace import Trace
    except ImportError as e:
        raise SystemExit(
            "--workload/--trace-file need the benchmarks package on "
            "sys.path — run from the repository root "
            f"(import failed: {e})")

    if args.trace_file and os.path.exists(args.trace_file):
        trace = Trace.load(args.trace_file)
        spec = generator.WorkloadSpec.from_dict(trace.spec)
        print(f"trace: loaded {args.trace_file} ({trace.n_requests} requests,"
              f" workload {trace.name!r}, {trace.fingerprint()[:18]}…)")
    else:
        if not args.workload:
            raise SystemExit("--trace-file points at a missing file and no "
                             "--workload was given to generate one")
        spec = generator.preset(args.workload, quick=args.smoke,
                                seed=args.seed)
        trace = generator.generate(spec)
        print(f"trace: generated workload {spec.name!r} "
              f"({trace.n_requests} requests, seed {args.seed}, "
              f"{trace.fingerprint()[:18]}…)")
        if args.trace_file:
            trace.save(args.trace_file)
            print(f"trace: saved to {args.trace_file}")

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    params = _init_params(cfg, packed=not args.no_packed)
    obs = _obs_setup(args)
    engine = runner.build_engine(spec, cfg, params,
                                 packed=not args.no_packed,
                                 tracer=obs["tracer"],
                                 incidents=obs["monitor"])
    _obs_start(args, engine, obs)
    reqs, wall = runner.replay(engine, trace)
    m = metrics.latency_metrics(reqs, trace, wall)
    c = metrics.engine_counters(engine)
    t, p, g = m["ttft_s"], m["tpot_s"], m["goodput"]
    print(f"TTFT p50/p90/p99 {t['p50'] * 1e3:.0f}/{t['p90'] * 1e3:.0f}/"
          f"{t['p99'] * 1e3:.0f}ms | TPOT p50/p99 {p['p50'] * 1e3:.2f}/"
          f"{p['p99'] * 1e3:.2f}ms")
    print(f"goodput {g['good']}/{g['total']} ({g['slo_attained']:.0%}) "
          f"under SLO | {m['output_tok_s']:.1f} out tok/s | "
          f"wall {m['wall_s']:.2f}s")
    print(f"counters: steps={c['steps']} preemptions={c['preemptions']} "
          f"prefill_tokens={c['prefill_tokens']} "
          f"prefix_hit_rate={c.get('prefix_hit_rate', 0.0):.3f} "
          f"plan_kernel={c['plan_kernel']}")
    _obs_finish(args, obs)


if __name__ == "__main__":
    main()
