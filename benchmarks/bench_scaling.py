"""Fig. 10 reproduction: kernel microbenchmarks on the paper's exact shapes +
scaling study.

The paper's Fig. 10 runs BitNet-b1.58-2B-4T kernel shapes (128x2560x6912
GEMM, 1x2560x6912 / 1x8192x45568-class GEMV) over 1-16 CPU threads.  The TPU
analogue of thread-scaling is chip-scaling: we evaluate the roofline terms of
the T-SAR BitLinear at mesh sizes {1, 4, 16, 64, 256} chips, and measure
wall-clock for the jitted kernels on this container's CPU at the paper shapes
(relative T-SAR vs baseline = the reproduced quantity).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, timeit
from repro.core import lut, ternary
from repro.core import hw

C = 4
# The paper's Fig. 10 kernel shapes (N, K, M).
SHAPES = [
    (128, 2560, 6912),   # GEMM prefill (2B-4T mlp up)
    (1, 2560, 6912),     # GEMV decode
    (1, 8192, 45568),    # GEMV (the paper's Mobile LLC case study shape)
]  # (128x6912x2560 mlp-down omitted: same regime as mlp-up, single-core budget
CHIPS = [1, 4, 16, 64, 256]


def measured(quick: bool = False):
    rows = []
    for (n, k, m) in SHAPES:
        key = jax.random.PRNGKey(n * 7 + m)
        t = ternary.random_ternary(key, (k, m))
        a = jax.random.normal(key, (n, k))
        ip, iz = ternary.pack_indices(t, C)
        li = lut.ternary_lut_indices(t, C)
        scale = jnp.ones((m,))

        # All variants recompute from fresh activations (steady-state decode
        # semantics), and all weight encodings are jit ARGUMENTS so XLA can
        # neither constant-fold the baseline away nor stall folding gathers.
        fns = {
            "tsar": (jax.jit(lambda a, ip, iz: lut.tsar_lut_matmul(a, ip, iz, C)),
                     (ip, iz)),
            "tsar_mxu": (jax.jit(lambda a, t, s: lut.bitlinear_matmul_fast(a, t, s)),
                         (t, scale)),
            "memlut": (jax.jit(lambda a, li: lut.memory_lut_matmul(a, li, C)), (li,)),
            "dense": (jax.jit(lambda a, w: a @ w), (t.astype(jnp.float32),)),
        }
        times = {name: timeit(fn, a, *extra, reps=2, warmup=1)
                 for name, (fn, extra) in fns.items()}
        best_tsar = min(times["tsar"], times["tsar_mxu"])
        csv_row(f"kernel_{n}x{k}x{m}_tsar", best_tsar * 1e6,
                f"vs_memlut={times['memlut']/best_tsar:.2f}x;"
                f"vs_dense={times['dense']/best_tsar:.2f}x")
        rows.append({"shape": (n, k, m), **{f"t_{k_}": v for k_, v in times.items()}})
    return rows


def chip_scaling():
    """Roofline chip-scaling of one 2B-4T BitLinear layer set (analytic)."""
    rows = []
    pk = hw.chip_peaks()
    for chips in CHIPS:
        # Per-chip share of the 2B-4T decode GEMV workload (M sharded).
        n, k, m = 1, 2560, 6912 * 3  # qkv+mlp aggregate width
        m_local = max(m // chips, 128)
        flops = 2 * n * k * m_local
        mem = k * m_local * 0.25 + n * k + n * m_local * 4
        t_c = flops / pk.int8_ops
        t_m = mem / pk.hbm_bw
        t = max(t_c, t_m)
        rows.append({"chips": chips, "t_us": t * 1e6,
                     "bound": "memory" if t_m > t_c else "compute"})
        csv_row(f"chip_scaling_gemv_{chips}", t * 1e6,
                f"bound={'memory' if t_m > t_c else 'compute'}")
    return rows


def run(quick: bool = False):
    return {"measured": measured(quick), "chip_scaling": chip_scaling()}


if __name__ == "__main__":
    run()
