"""The profiler trace of part of the window, and its reduction to numbers.

``WindowTracer`` starts JAX's profiler between two steps some seconds
before the window closes and stops it between two steps as it closes.
Where the traffic is open loop, the traced slice starts just before the
earliest request due in the window's last ``TRACE_MAX_S`` that leaves room
for its prefill, so that it holds steps with prompt chunks beside pure
decode steps; every seed has the same schedule, so the same slice.  The trace
(``.xplane.pb``) is read with ``jax.profiler.ProfileData`` into a small
table: the device's operations and program executions on each chip used,
and the host spans of the driver and the engine.  ``reduce_table`` turns
the table into

* ``busy_s``: the union of the intervals in which an operation ran on the
  device, averaged over the chips used;
* ``program_s``: device time of every program (XLA module) execution in
  the trace, averaged over the chips used.  The trace starts and stops
  between engine steps, so these are the traced steps' programs, however
  many a step runs (the step, the sampler, the driver's logit capture);
* ``device_ops``: the ten operations that took the most device time;
* ``idle_gaps``: the device's idle time split by what the host was doing,
  the innermost host span that covers each idle moment;
* ``host_span_s``: total seconds of each host span;
* ``clock_offset_ms``: bounds on how far the trace's device clock runs
  behind its host clock (``clock_offset``), or None.  Where the engine's
  spans give such bounds, the idle time is labelled with the host spans
  moved onto the device clock by the middle of the bounds.

``to_text_proto(cut(table, t0, t1))`` writes a few steps of such a table
back as an ``XSpace`` text proto, each op's scope (``scopes.py``) riding on
its event, so that a chip trace can be kept as a test fixture and read by
the same code.
"""
from __future__ import annotations

import glob
import re
import shutil
import tempfile
import time

# Host spans the driver and the engine put on the profiler's clock, from
# the outermost in: the engine's phases sit in the driver's ``bench.step``;
# dispatch and wait in ``tsar_engine_step``.  The names are copied from the
# program's ``repro.obs.trace``, as the peaks are in ``peaks.py``.
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait_arrival",
              "bench.stamp", "engine.admit", "engine.plan", "engine.sample",
              "engine.emit", "tsar_engine_step", "engine.dispatch",
              "engine.wait")
SCOPE_STAT = "scope"
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


def clock_offset(table: dict) -> tuple | None:
    """Bounds (lower, upper), in ns, on how far the trace's device clock
    runs behind its host clock, from the engine's spans: a step's program
    (the longest execution beside ``engine.wait``) cannot end after the
    host saw it end, and the next program, the sampler's, cannot start
    before ``engine.sample`` opened.  None without those spans."""
    dev = next(iter(table["devices"].values()), None)
    waits = sorted((s, e) for n, s, e in table["host"] if n == "engine.wait")
    samples = sorted(s for n, s, _ in table["host"] if n == "engine.sample")
    if dev is None or not waits or not dev["modules"]:
        return None
    mods = sorted((s, e) for _, s, e in dev["modules"])
    lo, hi = float("-inf"), float("inf")
    for k, (ws, we) in enumerate(waits):
        over = [(min(e, we) - max(s, ws), i) for i, (s, e) in enumerate(mods)
                if s < we and e > ws]
        if not over:
            continue
        i = max(over)[1]
        hi = min(hi, we - mods[i][1])
        nxt = waits[k + 1][0] if k + 1 < len(waits) else float("inf")
        opened = [s for s in samples if we <= s < nxt]
        if opened and i + 1 < len(mods):
            lo = max(lo, opened[0] - mods[i + 1][0])
    if hi == float("inf"):
        return None
    return lo, hi


def reduce_table(table: dict) -> dict:
    """Busy time, the programs' device time, the top device ops, the idle
    time by host activity and each host span's total, all in seconds, over
    the trace's own span (first to last event on any line)."""
    per_chip_busy, ops_time, idle = [], {}, {}
    program_ns = 0.0
    every = [t for d in table["devices"].values()
             for k in ("ops", "modules") for _, s, e in d[k] for t in (s, e)]
    every += [t for _, s, e in table["host"] for t in (s, e)]
    if not every:
        return {}
    lo, hi = min(every), max(every)
    chips = max(len(table["devices"]), 1)
    offset = clock_offset(table)
    shift = sum(offset) / 2 if offset and offset[0] <= offset[1] else 0.0
    host = [[n, s - shift, e - shift] for n, s, e in table["host"]]
    for dev in table["devices"].values():
        busy = _union([s, e] for _, s, e in dev["ops"])
        per_chip_busy.append(sum(e - s for s, e in busy))
        for name, s, e in dev["ops"]:
            ops_time[name] = ops_time.get(name, 0.0) + (e - s)
        program_ns += sum(e - s for _, s, e in dev["modules"])
        for k, v in _label_idle(_gaps(busy, lo, hi), host).items():
            idle[k] = idle.get(k, 0.0) + v / chips
    spans: dict = {}
    for name, s, e in table["host"]:
        spans[name] = spans.get(name, 0.0) + (e - s) * 1e-9
    return {"span_s": (hi - lo) * 1e-9,
            "busy_s": sum(per_chip_busy) / chips * 1e-9,
            "program_s": program_ns / chips * 1e-9,
            "device_ops": top({k: v / chips for k, v in ops_time.items()},
                              1e-9),
            "idle_gaps": [[k, v * 1e-9] for k, v in
                          sorted(idle.items(), key=lambda kv: -kv[1])],
            "host_span_s": spans,
            "clock_offset_ms": None if offset is None else [
                offset[0] * 1e-6, offset[1] * 1e-6]}


def top(d: dict, scale: float = 1.0) -> list:
    """The ten largest entries of ``d`` as ``[[key, value * scale], ...]``."""
    return [[k, v * scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:10]]


def to_text_proto(table: dict) -> str:
    """``table`` as an ``XSpace`` text proto (``ProfileData.from_text_proto``
    reads it back); a device's ops carry their ``scopes``, where it has
    them, as a ``SCOPE_STAT`` stat."""
    out = []

    def plane(pid: int, name: str, lines: dict) -> None:
        meta: dict = {}
        out.append(f"planes {{\n  id: {pid}\n  name: \"{name}\"")
        for lid, (lname, events) in enumerate(lines.items(), start=1):
            base = min((ev[1] for ev in events), default=0)
            out.append(f"  lines {{\n    id: {lid}\n    name: \"{lname}\"\n"
                       f"    timestamp_ns: {int(base)}")
            for n, s, e, *scope in events:
                mid = meta.setdefault(n, len(meta) + 2)
                stat = (f" stats {{ metadata_id: 1 str_value: \"{scope[0]}\" }}"
                        if scope and scope[0] else "")
                out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round((s - base) * 1000))} duration_ps: "
                           f"{int(round((e - s) * 1000))}{stat} }}")
            out.append("  }")
        for n, mid in meta.items():
            quoted = n.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f"name: \"{quoted}\" }} }}")
        out.append(f"  stat_metadata {{ key: 1 value {{ id: 1 name: "
                   f"\"{SCOPE_STAT}\" }} }}")
        out.append("}")

    for i, (name, dev) in enumerate(sorted(table["devices"].items())):
        ops = dev["ops"]
        if "scopes" in dev:
            ops = [op + [sc] for op, sc in zip(ops, dev["scopes"])]
        plane(i + 1, name, {OPS_LINE: ops, MODULES_LINE: dev["modules"]})
    plane(len(table["devices"]) + 1, "/host:CPU", {"python": table["host"]})
    return "\n".join(out) + "\n"


def cut(table: dict, t0: float, t1: float) -> dict:
    """The events of ``table`` that start in [t0, t1)."""
    keep = lambda evs: [ev for ev in evs if t0 <= ev[1] < t1]  # noqa: E731
    devices = {}
    for d, dev in table["devices"].items():
        pick = [i for i, op in enumerate(dev["ops"]) if t0 <= op[1] < t1]
        devices[d] = {"ops": [dev["ops"][i] for i in pick],
                      "modules": keep(dev["modules"])}
        if "scopes" in dev:
            devices[d]["scopes"] = [dev["scopes"][i] for i in pick]
    return {"devices": devices, "host": keep(table["host"])}


class WindowTracer:
    """Profiles the end of the window, between steps, and reduces the trace
    with ``reduce_xspace(serialized XSpace, chips) -> dict``."""

    def __init__(self, chips: int, reduce_xspace):
        self.dir = None
        self.chips = chips
        self.reduce_xspace = reduce_xspace
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
        if self.dir is None:
            return None
        try:
            if self.t1 is None:
                return None
            path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)[0]
            with open(path, "rb") as f:
                raw = f.read()
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        out = self.reduce_xspace(raw, self.chips)
        out.update(t0=self.t0 - self.w0, t1=self.t1 - self.w0,
                   window_s=self.t1 - self.t0)
        return out
