"""The model step's parts and the engine's host phases, read from a trace.

The program names the parts of its jitted serving step with
``jax.named_scope`` (``STEP_SCOPES``) and, with ``profiler_annotations``,
the host phases of ``ServingEngine.step`` with profiler spans
(``ENGINE_SPANS``).  The names are copied here, as the peaks are in
``peaks.py``, so that no change to the program can move the yardstick.

A device op's scope comes from its ``op_name`` metadata, which the trace
carries in the compiled HLO of every program it saw (the ``Hlo Proto``
stats of its ``/host:metadata`` plane).  A v5e trace also holds it as the
``tf_op`` stat of each op's event metadata, but
``jax.profiler.ProfileData`` shows only an event's own stats, so either
source needs the small protobuf reader here; the HLO keys each op by its
program.  An op belongs to the innermost scope on its ``op_name`` path (a
BitLinear inside attention is ``bitlinear``).  An op whose path names no
scope takes the scope of the op that encloses it on its line (the body of a
loop XLA made for a gather carries no metadata of its own), else ``OTHER``:
embedding, block norms, residuals, the layer scan's own loop.

``scoped`` adds to ``xtrace.reduce_table``'s numbers

* ``scope_s``: per scope, the ops' device self time (an op's duration
  less what the ops nested in it on its line cover), averaged over the
  chips used; it sums to ``busy_s``;
* ``host_span_s``: total seconds of each host span;
* ``idle_gaps``: idle time labelled by the innermost span of the whole
  vocabulary, the engine's phases included.

``ScopeTracer`` is ``xtrace.WindowTracer`` with this reduction.  The
metric arithmetic at the end reads ``scope_s`` and ``host_span_s`` from
a run record's ``trace``, and returns None where they are missing.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import re

import parts
import readers
import xtrace

STEP_SCOPES = ("kv_gather", "kv_scatter", "attention", "bitlinear", "head")
OTHER = "other"
ENGINE_SPANS = ("engine.admit", "engine.plan", "engine.dispatch",
                "engine.wait", "engine.sample", "engine.emit")
# The engine's host work, without the wait on the device.
ENGINE_HOST = ("engine.admit", "engine.plan", "engine.dispatch",
               "engine.sample", "engine.emit")
# Every host span, from the outermost in: the engine's phases sit in the
# driver's ``bench.step``; dispatch and wait in ``tsar_engine_step``.
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait_arrival",
              "bench.stamp", "engine.admit", "engine.plan", "engine.sample",
              "engine.emit", "tsar_engine_step", "engine.dispatch",
              "engine.wait")
SCOPE_STAT = "scope"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_INSTR = re.compile(r"^%?([^\s=]+)")


def scope_of(op_name: str | None) -> str | None:
    """The innermost of ``STEP_SCOPES`` on an ``op_name`` path."""
    for part in reversed((op_name or "").split("/")):
        if part in STEP_SCOPES:
            return part
    return None


# -- the compiled programs' metadata, from the serialized trace ------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of a protobuf message; a
    length-delimited value is a memoryview, a varint an int."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = buf[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _first(buf, number: int):
    return next((v for f, v in _fields(buf) if f == number), None)


def _instruction_scopes(module_proto) -> dict:
    """Instruction name -> scope of one ``HloModuleProto`` (computations 3;
    their instructions 2; an instruction's name 1, metadata 7, whose
    ``op_name`` is 2)."""
    out = {}
    for f, comp in _fields(module_proto):
        if f != 3:
            continue
        for g, instr in _fields(comp):
            if g != 2:
                continue
            name = meta = None
            for h, v in _fields(instr):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    meta = v
            op_name = _first(meta, 2) if meta is not None else None
            out[name] = scope_of(bytes(op_name).decode() if op_name else None)
    return out


def hlo_scopes(xspace: bytes, modules=None) -> dict:
    """``"module(program id)"`` -> {instruction name: scope or None} for
    every program in a serialized ``XSpace``'s metadata plane (of those
    named in ``modules``, where given).  XSpace: planes 1; a plane's name
    2, event metadata 4 (map entries: value 2), stat metadata 5; event
    metadata's name 2 and stats 5; a stat's metadata id 1 and bytes 6;
    an ``HloProto``'s module 1."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1 or bytes(_first(plane, 2) or b"") != METADATA_PLANE.encode():
            continue
        stat_names = {}
        for g, entry in _fields(plane):
            if g == 5:
                meta = _first(entry, 2)
                stat_names[_first(meta, 1)] = bytes(_first(meta, 2)).decode()
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = _first(entry, 2)
            name = bytes(_first(meta, 2) or b"").decode()
            if modules is not None and name not in modules:
                continue
            for h, stat in _fields(meta):
                if h == 5 and stat_names.get(_first(stat, 1)) == HLO_PROTO_STAT:
                    out[name] = _instruction_scopes(_first(_first(stat, 6), 1))
    return out


# -- the table ---------------------------------------------------------------

def load(pd, xspace: bytes | None = None, chips: int = 1) -> dict:
    """``xtrace.load``'s table with each device op's scope beside it
    (``devices[d]["scopes"]``, parallel to ``ops``) and the engine's spans
    added to the host's.  The scope is the op's ``SCOPE_STAT`` where the
    event carries one (a fixture), else looked up in ``xspace``'s compiled
    programs by the module whose execution covers the op; None where
    neither names one."""
    table = xtrace.load(pd, chips)
    names = {m[0] for dev in table["devices"].values() for m in dev["modules"]}
    programs = hlo_scopes(xspace, names) if xspace is not None else {}
    for plane in pd.planes:
        dev = table["devices"].get(plane.name)
        if dev is not None:
            mods = sorted(dev["modules"], key=lambda m: m[1])
            starts = [m[1] for m in mods]
            dev["scopes"] = []
            for line in plane.lines:
                if line.name == xtrace.OPS_LINE:
                    dev["scopes"] += [_op_scope(e, mods, starts, programs)
                                      for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                table["host"] += [[e.name, e.start_ns, e.end_ns]
                                  for e in line.events
                                  if e.name in ENGINE_SPANS]
    return table


def _op_scope(event, mods: list, starts: list, programs: dict):
    scope = dict(event.stats).get(SCOPE_STAT)
    if scope is not None:
        return scope
    k = bisect.bisect_right(starts, event.start_ns) - 1
    if k < 0 or mods[k][2] <= event.start_ns or mods[k][0] not in programs:
        return None
    instr = _INSTR.match(event.name)
    return programs[mods[k][0]].get(instr.group(1) if instr else None)


def self_times(ops, op_scopes) -> dict:
    """Device self time per scope (``OTHER`` included) of one line's ops,
    in the ops' time unit.  Each moment the line is busy counts once, for
    the innermost op running then (the latest started); an op with no
    scope takes that of the op it started in.  So the values sum to the
    union of the ops' intervals."""
    marks = []
    for i, (_, s, e) in enumerate(ops):
        if e > s:
            marks += [(s, 1, -e, i), (e, 0, 0, i)]
    marks.sort()
    out = {k: 0.0 for k in STEP_SCOPES + (OTHER,)}
    eff: dict = {}
    stack: list = []
    t = None
    for when, starts, _, i in marks:
        if stack:
            out[eff[stack[-1]]] += when - t
        t = when
        if starts:
            eff[i] = op_scopes[i] or (eff[stack[-1]] if stack else OTHER)
            stack.append(i)
        else:
            stack.remove(i)
    return out


def _label_idle(gaps: list, host: list) -> dict:
    """Idle time by the innermost of ``HOST_SPANS`` covering it: as
    ``xtrace._label_idle``, which orders only its own spans."""
    depth = {n: i for i, n in enumerate(HOST_SPANS)}
    out: dict = {}
    for gs, ge in gaps:
        cuts = {gs, ge}
        for _, s, e in host:
            if s < ge and e > gs:
                cuts.update(t for t in (s, e) if gs < t < ge)
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [n for n, s, e in host if s <= mid < e]
            name = max(cover, key=depth.get) if cover else xtrace.NO_SPAN
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
    """``xtrace.reduce_table`` of the table, updated with ``scoped``."""
    plain = {"devices": {d: {"ops": v["ops"], "modules": v["modules"]}
                         for d, v in table["devices"].items()},
             "host": [h for h in table["host"] if h[0] in xtrace.HOST_SPANS]}
    out = xtrace.reduce_table(plain)
    if out:
        out.update(scoped(table))
    return out


def scoped(table: dict) -> dict:
    """``scope_s``, ``host_span_s`` and the idle time by every span
    (seconds) of a non-empty table.  Where the engine's spans bound the
    offset between the trace's device and host clocks (``clock_offset``,
    reported in ms), the idle time is labelled with the host spans moved
    onto the device clock by the middle of the bounds."""
    chips = max(len(table["devices"]), 1)
    scope_ns = {k: 0.0 for k in STEP_SCOPES + (OTHER,)}
    idle: dict = {}
    lo, hi = _edges(table)
    offset = clock_offset(table)
    shift = sum(offset) / 2 if offset and offset[0] <= offset[1] else 0.0
    host = [[n, s - shift, e - shift] for n, s, e in table["host"]]
    for dev in table["devices"].values():
        for k, v in self_times(dev["ops"], dev["scopes"]).items():
            scope_ns[k] += v / chips
        busy = xtrace._union([s, e] for _, s, e in dev["ops"])
        for k, v in _label_idle(xtrace._gaps(busy, lo, hi), host).items():
            idle[k] = idle.get(k, 0.0) + v / chips
    spans: dict = {}
    for name, s, e in table["host"]:
        spans[name] = spans.get(name, 0.0) + (e - s) * 1e-9
    return {"scope_s": [[k, v * 1e-9] for k, v in scope_ns.items()],
            "host_span_s": spans,
            "clock_offset_ms": None if offset is None else [
                offset[0] * 1e-6, offset[1] * 1e-6],
            "idle_gaps": [[k, v * 1e-9] for k, v in
                          sorted(idle.items(), key=lambda kv: -kv[1])]}


def _edges(table: dict) -> tuple:
    every = [t for d in table["devices"].values()
             for k in ("ops", "modules") for _, s, e in d[k] for t in (s, e)]
    every += [t for _, s, e in table["host"] for t in (s, e)]
    return min(every), max(every)


def to_text_proto(table: dict) -> str:
    """``table`` as an ``XSpace`` text proto that ``load`` reads back: each
    op's scope rides on its event as a ``SCOPE_STAT`` stat."""
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
        ops = [op + [sc] for op, sc in zip(dev["ops"], dev["scopes"])]
        plane(i + 1, name, {xtrace.OPS_LINE: ops,
                            xtrace.MODULES_LINE: dev["modules"]})
    plane(len(table["devices"]) + 1, "/host:CPU", {"python": table["host"]})
    return "\n".join(out) + "\n"


def cut(table: dict, t0: float, t1: float) -> dict:
    """The events of ``table`` that start in [t0, t1)."""
    keep = lambda evs: [ev for ev in evs if t0 <= ev[1] < t1]  # noqa: E731
    devices = {}
    for d, dev in table["devices"].items():
        pick = [i for i, op in enumerate(dev["ops"]) if t0 <= op[1] < t1]
        devices[d] = {"ops": [dev["ops"][i] for i in pick],
                      "scopes": [dev["scopes"][i] for i in pick],
                      "modules": keep(dev["modules"])}
    return {"devices": devices, "host": keep(table["host"])}


class ScopeTracer(xtrace.WindowTracer):
    """``xtrace.WindowTracer`` whose reduction adds ``scoped``; it keeps
    the table (``self.table``) for a fixture, and the raw trace, gzipped,
    at ``keep`` where that is given."""

    def __init__(self, chips: int = 1, keep: str | None = None):
        super().__init__(chips)
        self.keep = keep
        self.table = None

    def reduce(self) -> dict | None:
        import jax

        raw = None
        if self.dir is not None and self.t1 is not None:
            path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)[0]
            with open(path, "rb") as f:
                raw = f.read()
        out = super().reduce()          # reads the trace, then removes it
        if not out or raw is None:
            return out
        if self.keep:
            with gzip.open(self.keep, "wb") as f:
                f.write(raw)
        self.table = load(jax.profiler.ProfileData.from_serialized_xspace(
            raw), raw, self.chips)
        out.update(scoped(self.table))
        return out


# -- per-layer metrics -------------------------------------------------------

def _scoped(rec: dict) -> tuple:
    """(traced steps, scope_s, host_span_s), or None where the run record
    holds no scoped trace, no step was traced, or no device op named a
    step scope (a program compiled without them)."""
    tr = rec.get("trace") or {}
    steps = readers.traced_steps(rec)
    if "scope_s" not in tr or "host_span_s" not in tr or not steps:
        return None
    scope_s = dict(tr["scope_s"])
    if not any(scope_s.get(k) for k in STEP_SCOPES):
        return None
    return steps, scope_s, tr["host_span_s"]


def kv_copy_ms(rec: dict) -> float | None:
    """Device self time under ``kv_gather`` and ``kv_scatter`` per traced
    step, in ms."""
    got = _scoped(rec)
    if got is None:
        return None
    steps, scope_s, _ = got
    return 1e3 * (scope_s["kv_gather"] + scope_s["kv_scatter"]) / len(steps)


def part_roofline(rec: dict, part: str, peak: str) -> float | None:
    """Least time of ``part`` over the traced steps (each step the larger
    of its operations at the chip's ``peak`` and its bytes at HBM
    bandwidth, ``parts.step_parts``) over the device self time under the
    part's scope, in percent."""
    got = _scoped(rec)
    if got is None or not rec.get("peaks") or not got[1].get(part):
        return None
    steps, scope_s, _ = got
    pk = rec["peaks"]
    least = 0.0
    for s in steps:
        ops, nbytes = parts.step_parts(rec["config"], s["slots"],
                                       s["emit"])[part]
        least += max(ops / pk[peak], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / scope_s[part]


def engine_host_ms(rec: dict) -> float | None:
    """The engine's host phases (``ENGINE_HOST``: all but the wait) per
    traced step, in ms."""
    tr = rec.get("trace") or {}
    spans = tr.get("host_span_s") or {}
    steps = readers.traced_steps(rec)
    if not steps or not any(n in spans for n in ENGINE_HOST):
        return None
    return 1e3 * sum(spans.get(n, 0.0) for n in ENGINE_HOST) / len(steps)
