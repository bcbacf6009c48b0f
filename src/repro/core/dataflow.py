"""Adaptive kernel / dataflow selection (paper Sec. III-D).

The paper ships two microkernel dataflows and picks per layer at compile time:

* **AP (activation-persistent)** — activations (and the LUTs derived from
  them) stay resident; weight tiles stream past.  Wins when the LUT build cost
  is amortized over many output channels and the activation tile is reused
  (high N, K) — the GEMM/prefill regime.
* **OP (output-persistent)** — output accumulators stay resident; activation
  (LUT) tiles stream past.  Minimizes write-back traffic; wins for
  high-M GEMV/decode.

On TPU the same knob is the Pallas grid iteration order + which operand's
BlockSpec is pinned across the inner grid dimension.

Since the execution-plan redesign, the per-kernel cost models live on the
kernel implementations themselves (``repro.plan.registry`` — each
:class:`KernelImpl` carries ``cost(n, k, m, c, density, block_density)``);
:func:`select_kernel` is the argmin over the registry's selectable costs, and
:func:`layer_plan` is a thin wrapper over
``repro.plan.plan.compile_plan_from_shapes`` kept for compatibility.  The
durable, whole-model version of this choice is
``repro.plan.compile_plan`` -> ``ModelPlan``.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.core import hw
from repro.plan import registry as _registry
from repro.plan.registry import (  # noqa: F401  (canonical home is the registry)
    DEFAULT_DENSITY,
    SPARSE_BLOCK,
    SPARSE_KERNELS,
)


@dataclass(frozen=True)
class KernelChoice:
    kernel: str          # 'tsar_lut' | 'tsar_mxu' | 'tsar_sparse'
    dataflow: str        # 'AP' | 'OP'
    est_time_s: float
    bound: str           # 'compute' | 'memory'
    detail: dict


# Back-compat aliases: the cost models moved behind the registry impls'
# ``cost()`` methods; these keep the old private names callable.

def _tsar_mxu_cost(n: int, k: int, m: int) -> tuple[float, float]:
    return _registry.get("tsar_mxu").cost(n, k, m)


def _tsar_lut_cost(n: int, k: int, m: int, c: int) -> tuple[float, float]:
    return _registry.get("tsar_lut").cost(n, k, m, c)


def _tsar_sparse_cost(n: int, k: int, m: int, block_density: float,
                      block_shape: tuple = SPARSE_BLOCK) -> tuple[float, float]:
    return _registry.get("tsar_sparse").cost(
        n, k, m, block_density=block_density, block_shape=block_shape)


def select_kernel(n: int, k: int, m: int, c: int = 4,
                  density: float = DEFAULT_DENSITY,
                  block_density: float | None = None,
                  block_shape: tuple = SPARSE_BLOCK,
                  sparse_ok: tuple | None = None) -> KernelChoice:
    """Compile-time per-layer selection (paper: 'empirically selects the
    fastest kernel for each layer'); an analytic roofline argmin over the
    registry's selectable kernels.

    ``density`` is the measured nonzero-weight fraction (defaults to the
    BitNet ~2/3 prior); ``block_density`` the measured live-block fraction at
    ``block_shape`` tiling.  When ``block_density`` is omitted it is estimated
    from ``density`` assuming unstructured zeros — which makes essentially
    every block live (``1 - (1-d)^(bk*bm) ~ 1``), so the sparse path is only
    chosen on *measured* structured sparsity, never speculatively.

    ``sparse_ok`` restricts the sparse-family candidates
    (``registry.SPARSE_KERNELS``) to the formats the layer actually carries:
    ``compile_plan`` passes the subset whose ``supports()`` gate passes, so a
    plan never commits to e.g. ``tsar_sparse`` on a layer that only holds a
    padded pool.  ``None`` keeps every selectable kernel in play (legacy
    shape-only calls; resolve-time degradation still guards execution).

    Serve-path note: this runs at PLAN time only.  The serving engine calls
    it (via ``repro.plan.compile_plan``) once at init; the jitted step then
    dispatches through the frozen ``ModelPlan``.
    """
    if block_density is None:
        block_density = _registry.estimate_block_density(density, block_shape)
    costs = _registry.candidate_costs(n, k, m, c, density=density,
                                     block_density=block_density,
                                     block_shape=block_shape)
    if sparse_ok is not None:
        costs = {kn: v for kn, v in costs.items()
                 if kn not in SPARSE_KERNELS or kn in sparse_ok}
    cands = {name: max(comp, mem) for name, (comp, mem) in costs.items()}
    # Strict improvement required: at/above break-even the dense paths win
    # (no format conversion for a wash).
    dense_cands = {kn: v for kn, v in cands.items()
                   if kn not in SPARSE_KERNELS}
    kernel = min(dense_cands, key=dense_cands.get)
    sparse_cands = {kn: v for kn, v in cands.items() if kn in SPARSE_KERNELS}
    if sparse_cands:
        best_sparse = min(sparse_cands, key=sparse_cands.get)
        if sparse_cands[best_sparse] < dense_cands[kernel]:
            kernel = best_sparse
    comp, mem = costs[kernel]
    dataflow = select_dataflow(n, k, m, c)
    return KernelChoice(
        kernel=kernel,
        dataflow=dataflow,
        est_time_s=cands[kernel],
        bound="compute" if comp >= mem else "memory",
        detail={"compute_s": comp, "memory_s": mem, "candidates": cands,
                "density": density, "block_density": block_density},
    )


def sparse_break_even(n: int, k: int, m: int, c: int = 4,
                      block_shape: tuple = SPARSE_BLOCK,
                      kernel: str = "tsar_sparse") -> float:
    """Block density below which ``kernel`` (a sparse-family member — the
    compacted ``tsar_sparse`` by default, or ``tsar_sparse_padded``) beats
    the best dense kernel.

    The sparse cost is monotonically increasing in block density and the
    dense costs are constant, so the crossover is unique; found by bisection
    to stay consistent with :func:`select_kernel` exactly.
    """
    if kernel not in SPARSE_KERNELS:
        raise ValueError(f"{kernel!r} is not a sparse kernel: {SPARSE_KERNELS}")
    best_dense = min(
        max(*_registry.get(name).cost(n, k, m, c))
        for name in _registry.selectable_names()
        if name not in SPARSE_KERNELS)
    sp = _registry.get(kernel)

    def sparse(bd: float) -> float:
        sc, sm = sp.cost(n, k, m, c, block_density=bd, block_shape=block_shape)
        return max(sc, sm)

    if sparse(1.0) < best_dense:
        return 1.0
    if sparse(0.0) >= best_dense:
        return 0.0
    lo, hi = 0.0, 1.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if sparse(mid) < best_dense:
            lo = mid
        else:
            hi = mid
    return lo


def select_dataflow(n: int, k: int, m: int, c: int = 4,
                    vmem_budget: int | None = None) -> str:
    """AP vs OP (paper Fig. 7).

    AP pins the activation/LUT tile in VMEM and streams weights: write-back of
    partial outputs happens once per weight pass, LUTs are built exactly once.
    OP pins the (n, m_tile) accumulator and streams LUT tiles: zero
    intermediate write-back, LUTs may be rebuilt per m-tile.

    Heuristic mirror of the paper's empirical rule: high activation reuse
    (large n*k working set relative to outputs) -> AP; output-channel-heavy
    GEMV (m >> n) -> OP.

    ``vmem_budget`` defaults to the VMEM one Pallas kernel may use on the
    planned chip: Mosaic's scoped limit, not the chip's whole VMEM.
    """
    if vmem_budget is None:
        vmem_budget = hw.chip_peaks().vmem_scoped_bytes
    act_bytes = n * k                      # int8 activations
    lut_bytes = n * (k / c) * (2 ** c) * 2  # bf16 shared LUTs
    out_bytes = n * m * 4                  # f32 accumulators
    if act_bytes + lut_bytes <= vmem_budget * 0.5 and n >= 8:
        return "AP"
    if out_bytes <= vmem_budget * 0.5 and m >= n:
        return "OP"
    return "AP" if n * k >= m else "OP"


def layer_plan(shapes: dict, c: int = 4) -> dict[str, KernelChoice]:
    """Whole-model compile-time plan: layer name -> choice.

    Thin compatibility wrapper over ``repro.plan.compile_plan_from_shapes``.
    Specs may be ``(n, k, m)``, ``(n, k, m, c)``, or dicts with optional
    per-layer ``c`` / ``density`` / ``block_density`` — so e.g. MoE expert
    layers with a different LUT block size or measured density cost
    correctly.  Prefer ``repro.plan.compile_plan`` for a durable, savable
    ModelPlan.
    """
    from repro.plan.plan import compile_plan_from_shapes

    mp = compile_plan_from_shapes(shapes, c=c)
    out: dict[str, KernelChoice] = {}
    for name, by_bucket in mp.layers.items():
        ((n, lp),) = by_bucket.items()
        out[name] = KernelChoice(
            kernel=lp.kernel, dataflow=lp.dataflow, est_time_s=lp.est_time_s,
            bound=lp.bound,
            detail={"density": lp.density, "tile_sizes": lp.tile_sizes,
                    "bucket": n})
    return out
