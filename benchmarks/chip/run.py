"""The benchmark of record: one run of one cell on the chip.

    python3 benchmarks/chip/run.py --workload bitnet2b.chat --seed 7 \
        --seconds 45 --trace 0

Runs from the root of a checkout.  Loads the cell named in
``BENCHMARK.json``, makes its weights and traffic from ``--seed``, warms every
step shape, serves the window, checks a sample of what was served against
the plain reference and prints one JSON line last on standard output.
``--trace 1`` traces a part of the window with the profiler and reports the
cell's per-layer metrics in place of its end-to-end ones.

Without the chips the cell asks for it prints no result and exits 2.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    import spec
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.resolve(args.workload)
    # JAX's persistent cache, in JAX_COMPILATION_CACHE_DIR where that is set,
    # else at the checkout's fixed .jax_cache/: every program, however quick
    # to compile, so that only a checkout's first run compiles.
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
