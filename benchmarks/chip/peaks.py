"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s.  The same figures as the
program's ``repro.core.hw.PEAKS``, copied here so that no change to the
program can move the yardstick.  A chip that is not listed is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for {device_kind!r} in "
                         f"benchmarks/chip/peaks.py (known: {sorted(PEAKS)})"
                         ) from None
