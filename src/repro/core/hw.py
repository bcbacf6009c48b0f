"""Per-chip hardware constants — the one shared definition.

``PEAKS`` holds each chip's published peaks, keyed by the ``device_kind``
that JAX reports.  Every cost model (the kernel registry's rooflines, the
dataflow choice, the dry-run roofline) reads them from here, so the planner
and the roofline cannot drift apart.  :func:`chip_peaks` resolves the entry
for the device the planner runs on.  A TPU whose kind is not in the table is
an error, not a silent v5e.  Off the TPU (CPU tests, dry-runs) planning
targets the entry named by ``OFF_TPU_KIND``.

Besides the fixed datasheet numbers, this module owns the **calibratable**
cost-model constants.  ``SPARSE_ISSUE_TAX`` started life as an analytic guess
(the sparse kernels' scalar-prefetched pool gather walks HBM non-sequentially
and masked tail steps still burn grid issue slots); the calibration mode in
``benchmarks/bench_kernels.py`` fits it from measured interpret-mode timings
and installs the fitted value here (``set_calibration``), which every
registry cost model then reads through :func:`sparse_issue_tax` — so a
measured machine overrides the guess without touching the cost formulas.
"""
from __future__ import annotations

import functools
import json
from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float          # FLOP/s
    int8_ops: float            # int8 op/s
    hbm_bw: float              # bytes/s
    hbm_bytes: float
    ici_link_bw: float         # bytes/s per inter-chip link
    vmem_scoped_bytes: int     # Mosaic's default scoped VMEM limit per kernel


# Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect
# (four links of 50 GB/s).  The scoped VMEM limit is Mosaic's default on v5e.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9, hbm_bytes=16e9,
        ici_link_bw=50e9, vmem_scoped_bytes=16 * 1024 * 1024),
}

# The chip that planning targets when the process holds no TPU.
OFF_TPU_KIND = "TPU v5 lite"


@functools.cache
def chip_peaks() -> ChipPeaks:
    """Peaks of the chip this process plans for: its own TPU, or the
    ``OFF_TPU_KIND`` entry off the TPU.  Raises on an unlisted TPU kind."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return PEAKS[OFF_TPU_KIND]
    try:
        return PEAKS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for TPU kind {dev.device_kind!r} in repro.core.hw."
            f"PEAKS (known: {sorted(PEAKS)}); add its published figures "
            "before planning on it") from None


# Issue-efficiency tax on the sparse kernels' live-block work (analytic
# default; see module docstring).  Puts the break-even near 1/1.1 ~ 0.9 live
# blocks instead of degenerately at 1.0.
SPARSE_ISSUE_TAX = 1.1

# Cost of one MASKED grid step in the padded-pool sparse kernel, as a
# fraction of a live block's compute: the static s_steps walk issues the
# step (grid bookkeeping + predicated-off DMA slot) even when the
# ``s < counts[j]`` guard drops the MXU work.
SPARSE_PAD_STEP_FRAC = 0.05

# Calibratable keys and their analytic defaults.  Values installed via
# set_calibration() shadow the module constants for every reader that goes
# through the accessor functions (the kernel registry cost models do).
_CALIBRATION_DEFAULTS = {
    "sparse_issue_tax": SPARSE_ISSUE_TAX,
    "sparse_pad_step_frac": SPARSE_PAD_STEP_FRAC,
}
_CALIBRATED: dict[str, float] = {}


def sparse_issue_tax() -> float:
    """The live value: calibrated if installed, else the analytic default."""
    return _CALIBRATED.get("sparse_issue_tax", SPARSE_ISSUE_TAX)


def sparse_pad_step_frac() -> float:
    return _CALIBRATED.get("sparse_pad_step_frac", SPARSE_PAD_STEP_FRAC)


def set_calibration(**values: float) -> None:
    """Install measured cost-model constants (``benchmarks/bench_kernels.py
    --calibrate`` is the producer).  Unknown keys / non-positive values are
    rejected loudly — a typo'd calibration silently reverting to defaults
    would defeat the point."""
    for key, val in values.items():
        if key not in _CALIBRATION_DEFAULTS:
            raise ValueError(
                f"unknown calibration key {key!r}; known: "
                f"{sorted(_CALIBRATION_DEFAULTS)}")
        val = float(val)
        if not val > 0.0:
            raise ValueError(f"calibration {key}={val!r} must be > 0")
        _CALIBRATED[key] = val


def clear_calibration(*keys: str) -> None:
    """Drop calibrated values (all of them when called with no args)."""
    if not keys:
        _CALIBRATED.clear()
        return
    for key in keys:
        _CALIBRATED.pop(key, None)


def calibration() -> dict[str, float]:
    """The effective constants (defaults overlaid with calibrated values)."""
    out = dict(_CALIBRATION_DEFAULTS)
    out.update(_CALIBRATED)
    return out


def save_calibration(path, values: dict | None = None) -> None:
    """Write the calibration JSON ``load_calibration`` consumes.

    ``values`` defaults to the currently installed calibration; an explicit
    dict (validated against the known keys) lets a fit be persisted without
    installing it process-globally — either way this function is the one
    writer of the file format.
    """
    if values is None:
        values = dict(_CALIBRATED)
    else:
        for key, val in values.items():
            if key not in _CALIBRATION_DEFAULTS:
                raise ValueError(
                    f"unknown calibration key {key!r}; known: "
                    f"{sorted(_CALIBRATION_DEFAULTS)}")
            if not float(val) > 0.0:
                raise ValueError(f"calibration {key}={val!r} must be > 0")
    with open(path, "w") as f:
        json.dump({"version": 1, "calibration": dict(values)}, f, indent=2)


def load_calibration(path) -> dict[str, float]:
    with open(path) as f:
        payload = json.load(f)
    if payload.get("version") != 1:
        raise ValueError(f"calibration version {payload.get('version')!r} != 1")
    set_calibration(**payload["calibration"])
    return dict(payload["calibration"])
