"""The profiler trace of part of the window, and its reduction to numbers.

``WindowTracer`` starts JAX's profiler between two steps some seconds
before the window closes and stops it between two steps as it closes.
Where the traffic is open loop, the traced slice starts just before the
earliest request due in the window's last ``TRACE_MAX_S`` that leaves room
for its prefill, so that it holds steps with prompt chunks beside pure
decode steps; every seed has the same schedule, so the same slice.  The trace
(``.xplane.pb``) is read with ``jax.profiler.ProfileData`` into a small
table: the device's operations and program executions on each chip used,
and the benchmark's own host spans.  ``reduce_table`` turns the table into

* ``busy_s``: the union of the intervals in which an operation ran on the
  device, averaged over the chips used;
* ``program_s``: device time of every program (XLA module) execution in
  the trace, averaged over the chips used.  The trace starts and stops
  between engine steps, so these are the traced steps' programs, however
  many a step runs (the step, the sampler, the driver's logit capture);
* ``device_ops``: the ten operations that took the most device time;
* ``idle_gaps``: the device's idle time split by what the host was doing,
  the innermost benchmark span that covers each idle moment.

``to_text_proto`` writes such a table back as an ``XSpace`` text proto, so
that a few steps of a chip trace can be kept as a test fixture and read by
the same code.
"""
from __future__ import annotations

import glob
import re
import shutil
import tempfile
import time

# Host spans the driver and the engine put on the profiler's clock, from
# the outermost in.
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait_arrival",
              "bench.stamp", "tsar_engine_step")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TRACE_SECONDS = 4.0
# The longest slice, the lead before the first arrival it is placed at, and
# the room that arrival must leave before the close for its prompt chunks.
TRACE_MAX_S = 10.0
LEAD_S = 1.0
PREFILL_ROOM_S = 2.0
NO_SPAN = "(no benchmark span)"
OP_NAME_CHARS = 120


def op_name(hlo: str) -> str:
    """An op event's HLO text without layouts, cut to ``OP_NAME_CHARS``:
    enough to tell a KV copy from a matmul fusion in the breakdown."""
    return re.sub(r"\{[^{}]*\}", "", hlo)[:OP_NAME_CHARS]


def load(pd, chips: int = 1) -> dict:
    """A ``ProfileData`` as a table: per chip the op and module events, and
    the host spans, each ``[name, start_ns, end_ns]``."""
    want = {f"/device:TPU:{i}" for i in range(chips)}
    table = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name in want:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    name = op_name if key == "ops" else str
                    dev[key] += [[name(e.name), e.start_ns, e.end_ns]
                                 for e in line.events]
            table["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                table["host"] += [[e.name, e.start_ns, e.end_ns]
                                  for e in line.events if e.name in HOST_SPANS]
    return table


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _gaps(busy: list, lo: float, hi: float) -> list:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def _label_idle(gaps: list, host: list) -> dict:
    """Idle nanoseconds by the innermost host span covering them."""
    depth = {n: i for i, n in enumerate(HOST_SPANS)}
    spans = sorted(host, key=lambda h: h[1])
    out: dict = {}
    for gs, ge in gaps:
        cuts = {gs, ge}
        for _, s, e in spans:
            if s < ge and e > gs:
                cuts.update(t for t in (s, e) if gs < t < ge)
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [n for n, s, e in spans if s <= mid < e]
            name = max(cover, key=depth.get) if cover else NO_SPAN
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_table(table: dict) -> dict:
    """Busy time, the programs' device time, the top device ops and the idle
    time by host activity, all in seconds, over the trace's own span (first
    to last event on any line)."""
    per_chip_busy, ops_time, idle = [], {}, {}
    program_ns = 0.0
    every = [t for d in table["devices"].values()
             for k in ("ops", "modules") for _, s, e in d[k] for t in (s, e)]
    every += [t for _, s, e in table["host"] for t in (s, e)]
    if not every:
        return {}
    lo, hi = min(every), max(every)
    for dev in table["devices"].values():
        busy = _union([s, e] for _, s, e in dev["ops"])
        per_chip_busy.append(sum(e - s for s, e in busy))
        for name, s, e in dev["ops"]:
            ops_time[name] = ops_time.get(name, 0.0) + (e - s)
        program_ns += sum(e - s for _, s, e in dev["modules"])
        for k, v in _label_idle(_gaps(busy, lo, hi), table["host"]).items():
            idle[k] = idle.get(k, 0.0) + v / len(table["devices"])
    chips = max(len(table["devices"]), 1)
    top = lambda d: [[k, v * 1e-9] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"span_s": (hi - lo) * 1e-9,
            "busy_s": sum(per_chip_busy) / chips * 1e-9,
            "program_s": program_ns / chips * 1e-9,
            "device_ops": top({k: v / chips for k, v in ops_time.items()}),
            "idle_gaps": top(idle)}


def to_text_proto(table: dict) -> str:
    """``table`` as an ``XSpace`` text proto (``ProfileData.from_text_proto``
    reads it back)."""
    out = []

    def plane(pid: int, name: str, lines: dict) -> None:
        meta: dict = {}
        out.append(f"planes {{\n  id: {pid}\n  name: \"{name}\"")
        for lid, (lname, events) in enumerate(lines.items(), start=1):
            base = min((s for _, s, _ in events), default=0)
            out.append(f"  lines {{\n    id: {lid}\n    name: \"{lname}\"\n"
                       f"    timestamp_ns: {int(base)}")
            for n, s, e in events:
                mid = meta.setdefault(n, len(meta) + 1)
                out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round((s - base) * 1000))} duration_ps: "
                           f"{int(round((e - s) * 1000))} }}")
            out.append("  }")
        for n, mid in meta.items():
            quoted = n.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f"name: \"{quoted}\" }} }}")
        out.append("}")

    for i, (name, dev) in enumerate(sorted(table["devices"].items())):
        plane(i + 1, name, {OPS_LINE: dev["ops"], MODULES_LINE: dev["modules"]})
    plane(len(table["devices"]) + 1, "/host:CPU", {"python": table["host"]})
    return "\n".join(out) + "\n"


class WindowTracer:
    """Profiles the end of the window, between steps."""

    def __init__(self, chips: int = 1):
        self.dir = None
        self.chips = chips
        self.t0 = self.t1 = None

    def plan(self, w0: float, w1: float, arrivals=()) -> None:
        """Trace to the window's close: stopping the profiler stalls the host
        for seconds while it collects the trace, and that stall must fall
        after the window, not in it.  The slice is the last
        ``TRACE_SECONDS``, or longer (up to ``TRACE_MAX_S``, never the
        window's first half) so that it starts ``LEAD_S`` before the
        earliest of ``arrivals`` (due times) that leaves
        ``PREFILL_ROOM_S`` before the close."""
        self.w0 = w0
        half = (w1 - w0) / 2
        start = w1 - min(TRACE_SECONDS, half)
        due = [d - LEAD_S for d in arrivals
               if w1 - TRACE_MAX_S <= d - LEAD_S and w0 + half <= d - LEAD_S
               and d <= w1 - PREFILL_ROOM_S]
        self.start_at = min([start] + due)
        self.stop_at = w1

    def tick(self, now: float) -> None:
        import jax

        if self.t0 is None and now >= self.start_at:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
            self.t0 = time.perf_counter()
        elif self.t0 is not None and self.t1 is None and now >= self.stop_at:
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        """The reduction, with the traced window on the host clock (seconds
        from the window's opening); None if nothing was traced."""
        import jax

        if self.dir is None:
            return None
        try:
            if self.t1 is None:
                return None
            path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)[0]
            table = load(jax.profiler.ProfileData.from_file(path), self.chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        out = reduce_table(table)
        out.update(t0=self.t0 - self.w0, t1=self.t1 - self.w0,
                   window_s=self.t1 - self.t0)
        return out
