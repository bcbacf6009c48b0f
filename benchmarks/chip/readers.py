"""Arithmetic shared by the metric readers in ``metrics/``.

Each reader takes the run record (``driver.Driver.run`` plus what
``harness.run_cell`` adds) and returns its number, or None where the run
holds nothing to read.  Times in the record are seconds from the opening of
the window, which lasts ``record["window_s"]``.
"""
from __future__ import annotations

import stats

# The engine's host phases, without the wait on the device.
ENGINE_HOST = ("engine.admit", "engine.plan", "engine.dispatch",
               "engine.sample", "engine.emit")


def in_window(rec: dict, t) -> bool:
    return t is not None and 0 <= t < rec["window_s"]


def window_requests(rec: dict) -> list:
    """Requests due inside the window."""
    return [r for r in rec["requests"] if in_window(rec, r["due"])]


def ttfts(rec: dict) -> list:
    """First-token time minus due time of every request due in the window;
    one with no first token counts until the run stopped."""
    end = max((s["t1"] for s in rec["steps"]), default=rec["window_s"])
    return [(r["stamps"][0] if r["stamps"] else end) - r["due"]
            for r in window_requests(rec)]


def token_gaps(rec: dict) -> list:
    """Every gap between two tokens of one request, ending in the window."""
    out = []
    for r in rec["requests"]:
        st = r["stamps"]
        out += [b - a for a, b in zip(st, st[1:]) if in_window(rec, b)]
    return out


def counter_delta(rec: dict, name: str) -> float | None:
    snap = rec["counters"]
    if not snap.get("open") or not snap.get("close"):
        return None
    return snap["close"][name] - snap["open"][name]


def per_step_ms(rec: dict, phase: str) -> float | None:
    steps = counter_delta(rec, f"{phase}_steps")
    if not steps:
        return None
    return 1e3 * counter_delta(rec, f"{phase}_s") / steps


def traced_steps(rec: dict) -> list:
    tr = rec.get("trace")
    if not tr:
        return []
    return [s for s in rec["steps"] if tr["t0"] <= s["t0"] and s["t1"] <= tr["t1"]]


def step_mfu(rec: dict) -> float | None:
    """Operations the traced window's real tokens require, over the traced
    window's wall time at the chip's int8 peak, in percent."""
    steps = traced_steps(rec)
    if not steps or not rec.get("peaks"):
        return None
    ops = sum(s["ops"] for s in steps)
    return 100.0 * ops / (rec["trace"]["window_s"] * rec["peaks"]["int8_ops"])


def step_roofline(rec: dict) -> float | None:
    """Least time the traced steps could take, each the larger of its
    operations at the int8 peak and its bytes at HBM bandwidth, over the
    device time of every program execution in the trace (the same steps,
    whatever programs each runs), in percent."""
    steps = traced_steps(rec)
    tr = rec.get("trace")
    if not steps or not rec.get("peaks") or not tr.get("program_s"):
        return None
    pk = rec["peaks"]
    least = sum(max(s["ops"] / pk["int8_ops"], s["bytes"] / pk["hbm_bytes_per_s"])
                for s in steps)
    return 100.0 * least / tr["program_s"]


def device_idle(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def engine_host_ms(rec: dict) -> float | None:
    """The engine's host phases (``ENGINE_HOST``: all but the wait) per
    traced step, in ms."""
    tr = rec.get("trace") or {}
    spans = tr.get("host_span_s") or {}
    steps = traced_steps(rec)
    if not steps or not any(n in spans for n in ENGINE_HOST):
        return None
    return 1e3 * sum(spans.get(n, 0.0) for n in ENGINE_HOST) / len(steps)


def percentile_ms(values, p: float) -> float | None:
    v = stats.percentile(values, p)
    return None if v is None else 1e3 * v
