"""One run of a cell as ``run.py`` makes it, read by the step's parts and
the engine's host phases (``scopes.py``).

    python3 benchmarks/chip/scopetrace.py --workload bitnet2b.chat \
        --seed 7 --seconds 51 --trace 1 --record rec.json \
        --fixture steps.pbtxt
    python3 benchmarks/chip/scopetrace.py --workload bitnet2b.chat \
        --seed 7 --seconds 51 --trace 0 --annotations 1

``--trace 1`` traces as ``run.py`` does, reduces the trace with
``scopes.ScopeTracer`` and reports, beside the cell's per-layer metrics,
the eight that read it (``kv_copy_ms``, ``attention_roofline``,
``bitlinear_roofline``, ``engine_host_ms``, each ``.serve`` where the cell
reports ``itl_p95_ms``, ``.batch`` where it reports ``output_tok_s``);
the result line's ``breakdown`` adds ``device_scopes`` and
``host_span_s``.  ``--record`` writes the run record (steps, counters,
the reduced trace); ``--fixture`` writes ``FIXTURE_STEPS`` engine steps
from the middle of the traced table as a text proto; ``--xplane`` keeps
the raw trace.
``--trace 0`` reports ``decode_step_ms`` and ``prefill_step_ms`` beside
the end-to-end metrics, with the engine's profiler spans on
(``--annotations 1``) or off, and no profiler running: what the spans
cost.  The last line on standard output is JSON.

A program's scopes reach the trace only if the executable was compiled by
code that names them: the persistent compile cache's key leaves out the
``op_name`` metadata, so an executable cached by code without scopes
loads in their place and its ops all read ``other`` (a warning says so).
Point ``JAX_COMPILATION_CACHE_DIR`` at a cache this code filled.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

PART_METRICS = (("kv_copy_ms", "ms"), ("attention_roofline", "%"),
                ("bitlinear_roofline", "%"), ("engine_host_ms", "ms"))
STEP_METRICS = ("prefill_step_ms", "decode_step_ms")
# Driver steps kept by ``--fixture``: a test fixture of two steps shows the
# engine's phases around a whole step and the idle between two.
FIXTURE_STEPS = 2


def part_metrics(cell) -> list:
    """The scope metrics' entries for ``cell``, named by the end-to-end
    metric they move."""
    names = {m["name"] for m in cell.end_to_end}
    suffix = "batch" if "output_tok_s" in names else "serve"
    return [{"name": f"{n}.{suffix}", "unit": u} for n, u in PART_METRICS]


def step_cut(table: dict, steps: int) -> tuple:
    """The start and end (ns) of ``steps`` whole driver steps from the
    middle of the traced table."""
    marks = sorted(s for n, s, _ in table["host"] if n == "bench.step")
    mid = max(0, len(marks) // 2 - steps // 2)
    if len(marks) < mid + steps + 1:
        raise ValueError(f"the trace holds {len(marks)} steps, fewer than "
                         f"{steps} whole ones")
    return marks[mid], marks[mid + steps]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--annotations", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record")
    ap.add_argument("--fixture")
    ap.add_argument("--xplane")
    args = ap.parse_args(argv)

    import driver as driver_mod
    import harness
    import scopes
    import spec
    import xtrace
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.resolve(args.workload)
    if args.trace:
        cell.per_layer = cell.per_layer + part_metrics(cell)
    else:
        by = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
        cell.end_to_end = cell.end_to_end + [by[n] for n in STEP_METRICS]
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    tracers, records = [], []

    def make_tracer(chips):
        tracers.append(scopes.ScopeTracer(chips, keep=args.xplane))
        return tracers[-1]

    run = driver_mod.Driver.run

    def keeping_run(self, *a, **kw):
        records.append(run(self, *a, **kw))
        return records[-1]

    # The harness of record reduces its trace without the scopes; these two
    # swaps give it the scoped tracer and hand the run record back.
    xtrace.WindowTracer = make_tracer
    driver_mod.Driver.run = keeping_run

    def annotate(engine):
        engine._profile_steps = bool(args.annotations)

    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START,
                                  prepare_engine=None if args.trace
                                  else annotate)
    except harness.NoChip as e:
        print(f"scopetrace.py: {e}", file=sys.stderr)
        return 2
    rec = records[0]
    tr = rec.get("trace") or {}
    if "breakdown" in result:
        result["breakdown"]["device_scopes"] = tr.get("scope_s")
        result["breakdown"]["host_span_s"] = tr.get("host_span_s")
        named = dict(tr.get("scope_s") or {})
        if tr.get("busy_s") and not any(named.get(k)
                                        for k in scopes.STEP_SCOPES):
            print("scopetrace.py: no device op names a step scope; were the "
                  "programs loaded from a compile cache filled by code "
                  "without the scopes?", file=sys.stderr)
    if args.record:
        with open(args.record, "w") as f:
            json.dump({k: v for k, v in rec.items() if k != "requests"}, f)
    if args.fixture and tracers and tracers[0].table:
        t0, t1 = step_cut(tracers[0].table, FIXTURE_STEPS)
        with open(args.fixture, "w") as f:
            f.write(scopes.to_text_proto(scopes.cut(tracers[0].table, t0, t1)))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
