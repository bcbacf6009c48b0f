"""The model step's parts, read from a trace.

The program names the parts of its jitted serving step with
``jax.named_scope``.  Which names a step is read under is its
architecture's (``spec.arch``): the parts its file counts (``PARTS``) and
the scopes its KV pool is read and written under (``POOL``).  The names are
copied into the architecture files, as the peaks are in ``peaks.py``, so
that no change to the program can move the yardstick.

A device op's scope comes from its ``op_name`` metadata, which the trace
carries in the compiled HLO of every program it saw (the ``Hlo Proto``
stats of its ``/host:metadata`` plane).  A v5e trace also holds it as the
``tf_op`` stat of each op's event metadata, but
``jax.profiler.ProfileData`` shows only an event's own stats, so either
source needs the small protobuf reader here; the HLO keys each op by its
program.  An op belongs to the innermost named scope on its ``op_name``
path (a BitLinear inside attention is ``bitlinear``).  An op whose path
names none takes the scope of the op that encloses it on its line (the body
of a loop XLA made for a gather carries no metadata of its own), else
``OTHER``: embedding, block norms, residuals, the layer scan's own loop.

``reduce_table`` adds to ``xtrace.reduce_table``'s numbers ``scope_s``:
per scope, the ops' device self time (an op's duration less what the ops
nested in it on its line cover), averaged over the chips used; it sums to
``busy_s``; ``reduce_xspace`` is the reduction ``xtrace.WindowTracer``
makes of its trace.  The metric arithmetic at the end reads ``scope_s``
from a run record's ``trace``, and returns None where it is missing.
"""
from __future__ import annotations

import bisect
import re

import readers
import spec
import xtrace

OTHER = "other"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_INSTR = re.compile(r"^%?([^\s=]+)")


def step_scopes(arch) -> tuple:
    """The device scopes an architecture's step is read under."""
    return (*arch.PARTS, *arch.POOL)


def scope_of(op_name: str | None, names) -> str | None:
    """The innermost of ``names`` on an ``op_name`` path."""
    for part in reversed((op_name or "").split("/")):
        if part in names:
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


def _instruction_scopes(module_proto, names) -> dict:
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
            out[name] = scope_of(bytes(op_name).decode() if op_name else None,
                                 names)
    return out


def hlo_scopes(xspace: bytes, names, modules=None) -> dict:
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
                    out[name] = _instruction_scopes(_first(_first(stat, 6), 1),
                                                    names)
    return out


# -- the table ---------------------------------------------------------------

def load(pd, xspace: bytes | None, chips: int, names) -> dict:
    """``xtrace.load``'s table with each device op's scope among ``names``
    beside it (``devices[d]["scopes"]``, parallel to ``ops``).  The scope
    is the op's ``xtrace.SCOPE_STAT`` where the event carries one (a
    fixture), else looked up in ``xspace``'s compiled programs by the
    module whose execution covers the op; None where neither names one of
    ``names``."""
    table = xtrace.load(pd, chips)
    mods = {m[0] for dev in table["devices"].values() for m in dev["modules"]}
    programs = hlo_scopes(xspace, names, mods) if xspace is not None else {}
    for plane in pd.planes:
        dev = table["devices"].get(plane.name)
        if dev is None:
            continue
        by_start = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in by_start]
        dev["scopes"] = []
        for line in plane.lines:
            if line.name == xtrace.OPS_LINE:
                dev["scopes"] += [_op_scope(e, by_start, starts, programs,
                                            names) for e in line.events]
    return table


def _op_scope(event, mods: list, starts: list, programs: dict, names):
    scope = dict(event.stats).get(xtrace.SCOPE_STAT)
    if scope is not None:
        return scope_of(scope, names)
    k = bisect.bisect_right(starts, event.start_ns) - 1
    if k < 0 or mods[k][2] <= event.start_ns or mods[k][0] not in programs:
        return None
    instr = _INSTR.match(event.name)
    return programs[mods[k][0]].get(instr.group(1) if instr else None)


def self_times(ops, op_scopes, names) -> dict:
    """Device self time per scope (each of ``names``, and ``OTHER``) of one
    line's ops, in the ops' time unit.  Each moment the line is busy counts
    once, for the innermost op running then (the latest started); an op
    with no scope takes that of the op it started in.  So the values sum
    to the union of the ops' intervals."""
    marks = []
    for i, (_, s, e) in enumerate(ops):
        if e > s:
            marks += [(s, 1, -e, i), (e, 0, 0, i)]
    marks.sort()
    out = {k: 0.0 for k in (*names, OTHER)}
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


def reduce_table(table: dict, names) -> dict:
    """``xtrace.reduce_table`` of a non-empty table, with ``scope_s``
    (seconds) over ``names`` and ``OTHER``."""
    out = xtrace.reduce_table(table)
    if not out:
        return out
    chips = max(len(table["devices"]), 1)
    scope_ns = {k: 0.0 for k in (*names, OTHER)}
    for dev in table["devices"].values():
        for k, v in self_times(dev["ops"], dev["scopes"], names).items():
            scope_ns[k] += v / chips
    out["scope_s"] = [[k, v * 1e-9] for k, v in scope_ns.items()]
    return out


def reduce_xspace(raw: bytes, chips: int, names) -> dict:
    """``reduce_table`` of a serialized trace."""
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    return reduce_table(load(pd, raw, chips, names), names)


# -- per-layer metrics -------------------------------------------------------

def _scoped(rec: dict) -> tuple | None:
    """(traced steps, scope_s), or None where the run record holds no
    scoped trace, no step was traced, or no device op fell under a named
    scope (a program compiled without them)."""
    tr = rec.get("trace") or {}
    steps = readers.traced_steps(rec)
    scope_s = dict(tr.get("scope_s") or ())
    if not steps or not any(v for k, v in scope_s.items() if k != OTHER):
        return None
    return steps, scope_s


def pool_ms(rec: dict) -> float | None:
    """Device self time under the architecture's ``POOL`` scopes (the KV
    pool's read and write) per traced step, in ms."""
    got = _scoped(rec)
    if got is None:
        return None
    steps, scope_s = got
    pool = spec.arch(rec["config"]).POOL
    return 1e3 * sum(scope_s[k] for k in pool) / len(steps)


def part_roofline(rec: dict, part: str) -> float | None:
    """Least time of ``part`` over the traced steps (each step the larger
    of its operations at the peak the architecture's ``PARTS`` holds it to
    and its bytes at HBM bandwidth, ``step_parts``) over the device self
    time under the part's scope, in percent."""
    got = _scoped(rec)
    if got is None or not rec.get("peaks") or not got[1].get(part):
        return None
    steps, scope_s = got
    arch = spec.arch(rec["config"])
    pk = rec["peaks"]
    peak = pk[arch.PARTS[part]]
    least = 0.0
    for s in steps:
        ops, nbytes = arch.step_parts(rec["config"], s["slots"],
                                      s["emit"])[part]
        least += max(ops / peak, nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / scope_s[part]
