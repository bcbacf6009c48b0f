"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), all in seconds (TPU v5e constants):

    compute    = HLO_FLOPs / peak_FLOP/s          (cost_analysis, per device)
    memory     = HLO_bytes / HBM_bw               (cost_analysis, per device)
    collective = collective_bytes / link_bw       (parsed from partitioned HLO)

``cost_analysis()``/the HLO text describe the per-device (post-SPMD) module,
so no further division by chip count is needed.  collective_bytes sums the
*operand* sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op, i.e. bytes ingested by the interconnect
per device per step — a lower bound on wire traffic (ring algorithms move
~2x for all-reduce; we report the raw operand sum and note the convention).
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass

from repro.core import hw

# The dry-run compiles for TPU v5e meshes, so its roofline names that chip's
# entry of the shared peak table (core/hw) rather than the host's device.
_V5E = hw.PEAKS["TPU v5 lite"]
PEAK_FLOPS_BF16 = _V5E.bf16_flops
HBM_BW = _V5E.hbm_bw
ICI_LINK_BW = _V5E.ici_link_bw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLLECTIVE_RE = re.compile(
    r"=\s*(\w[\w\d-]*)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """'f32[128,256]' -> bytes.  Tuples handled by summing members."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# ---------------------------------------------------------------------------
# Trip-count-aware HLO program analysis
# ---------------------------------------------------------------------------
#
# XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE, not
# multiplied by trip count (verified empirically on this backend) — a 62-layer
# scanned model would be under-counted ~62x.  This parser walks the optimized
# HLO computation graph, scales each while body by its
# ``backend_config known_trip_count`` (fallback: the loop condition's compare
# constant), and accumulates dot FLOPs and collective bytes exactly.

_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*?)\)\s*->")
_DEF_RE = re.compile(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_TRIP_RE = re.compile(r'known_trip_count[^\d]*(\d+)')
_CALL_RE = re.compile(r"(?:calls=|to_apply=|body=)%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERANDS_RE = re.compile(r"\(([^)]*)\)")


def _parse_computations(hlo_text: str) -> dict:
    """name -> list of instruction lines (including the header)."""
    comps, cur, name = {}, None, None
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        if cur is None:
            m = _COMP_HDR.match(stripped)
            if m and stripped.endswith("{"):
                name, cur = m.group(1), [stripped]
        else:
            if stripped == "}":
                comps[name] = cur
                cur, name = None, None
            else:
                cur.append(stripped)
    return comps


def _dims(shape_str: str) -> list:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def hlo_program_costs(hlo_text: str) -> dict:
    """Trip-count-aware totals: {'flops', 'collectives': {...}, 'dot_count'}."""
    comps = _parse_computations(hlo_text)
    memo: dict[str, dict] = {}

    def analyze_comp(name: str) -> dict:
        if name in memo:
            return memo[name]
        memo[name] = {"flops": 0.0, "coll": {}, "dots": 0}  # cycle guard
        lines = comps.get(name, [])
        shapes: dict[str, str] = {}
        # header params: "a: f32[2,3], b: s32[]"
        if lines:
            hdr = _COMP_HDR.match(lines[0])
            if hdr:
                for part in hdr.group(2).split(","):
                    if ":" in part:
                        pname, ptype = part.split(":", 1)
                        shapes[pname.strip().lstrip("%")] = ptype.strip()
        total = {"flops": 0.0, "coll": {}, "dots": 0}

        def add(sub, mult=1.0):
            total["flops"] += sub["flops"] * mult
            total["dots"] += sub["dots"]
            for k, v in sub["coll"].items():
                total["coll"][k] = total["coll"].get(k, 0.0) + v * mult

        for line in lines[1:]:
            d = _DEF_RE.match(line)
            if not d:
                continue
            var, rhs = d.group(1), d.group(2)
            shapes[var] = rhs
            if " dot(" in rhs or rhs.startswith("dot(") or "= dot(" in line:
                res = 1
                for x in _dims(rhs.split("dot(")[0]):
                    res *= x
                cm = _CONTRACT_RE.search(rhs)
                contract = 1
                ops = rhs.split("dot(", 1)[1].split(")")[0].split(",")
                lhs_name = ops[0].strip().lstrip("%")
                lhs_shape = _dims(shapes.get(lhs_name, ""))
                if cm and lhs_shape:
                    for idx in cm.group(1).split(","):
                        if idx and int(idx) < len(lhs_shape):
                            contract *= lhs_shape[int(idx)]
                total["flops"] += 2.0 * res * contract
                total["dots"] += 1
                continue
            cmatch = re.search(
                r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                r"collective-permute)(-start)?\(", rhs)
            if cmatch and "-done(" not in rhs:
                kind = cmatch.group(1)
                b = _shape_bytes(rhs.split(cmatch.group(0))[0])
                total["coll"][kind] = total["coll"].get(kind, 0.0) + b
            if " while(" in rhs:
                trip = 1.0
                tm = _TRIP_RE.search(rhs)
                if tm:
                    trip = float(tm.group(1))
                else:
                    cnd = _COND_RE.search(rhs)
                    if cnd and cnd.group(1) in comps:
                        for cl in comps[cnd.group(1)]:
                            km = re.search(r"constant\((\d+)\)", cl)
                            if km:
                                trip = float(km.group(1))
                bm = re.search(r"body=%?([\w.\-]+)", rhs)
                if bm and bm.group(1) in comps:
                    add(analyze_comp(bm.group(1)), trip)
                continue
            if "fusion(" in rhs or " call(" in rhs or rhs.startswith("call("):
                cm2 = _CALL_RE.search(rhs)
                if cm2 and cm2.group(1) in comps:
                    add(analyze_comp(cm2.group(1)), 1.0)
        memo[name] = total
        return total

    entry = None
    for raw in hlo_text.splitlines():
        if raw.strip().startswith("ENTRY"):
            m = _COMP_HDR.match(raw.strip())
            if m:
                entry = m.group(1)
                break
    if entry is None:
        return {"flops": 0.0, "collectives": {}, "dot_count": 0}
    t = analyze_comp(entry)
    return {"flops": t["flops"], "collectives": t["coll"], "dot_count": t["dots"]}


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Sum result-shape bytes per collective kind from (partitioned) HLO text.

    Optimized HLO references operands by name (no inline shapes), so we read
    the RESULT shape between '=' and the op name: for all-reduce result ==
    operand; for all-gather the result is the gathered tensor (bytes landing
    per device); for reduce-scatter it underestimates wire bytes by ~Nx —
    conventions noted in EXPERIMENTS.md.  '-done' halves of async pairs are
    skipped (counted at '-start').
    """
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    pat = re.compile(
        r"=\s*(.*?)\s*"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(-start)?\(")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if not m:
            continue
        if re.search(r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)-done\(", line):
            continue
        kind = m.group(2)
        b = _shape_bytes(m.group(1))
        out[kind] += b
        out["count"] += 1
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    bound: str
    model_flops: float            # 6*N(_active)*D global
    useful_flops_ratio: float     # model_flops / (flops_per_device * chips)
    peak_memory_bytes: float | None = None
    collectives: dict | None = None
    note: str = ""

    def to_json(self) -> dict:
        return asdict(self)


def analyze(arch: str, shape_name: str, mesh_name: str, chips: int,
            cost: dict, hlo_text: str, model_flops: float,
            memory_stats=None, note: str = "") -> Roofline:
    """Derive the three roofline terms from a compiled per-device module.

    FLOPs and collective bytes come from the trip-count-aware HLO walk
    (``hlo_program_costs``) — the raw ``cost_analysis()`` counts while bodies
    once and under-counts scanned models by ~n_layers.  The memory term uses
    ``max(bytes-accessed, argument+output sizes)``: the latter is a sound
    floor (every argument byte — weights, caches, batch — crosses HBM at
    least once per step) immune to the same while-body undercount.
    """
    prog = hlo_program_costs(hlo_text)
    flops = float(max(prog["flops"], cost.get("flops", 0.0)))
    coll = {k: float(v) for k, v in prog["collectives"].items()}
    coll_bytes = float(sum(coll.values()))

    arg_out = 0.0
    peak_mem = None
    if memory_stats is not None:
        arg_out = float(getattr(memory_stats, "argument_size_in_bytes", 0)
                        + getattr(memory_stats, "output_size_in_bytes", 0))
        peak_mem = float(
            getattr(memory_stats, "temp_size_in_bytes", 0)
            + getattr(memory_stats, "argument_size_in_bytes", 0)
            + getattr(memory_stats, "output_size_in_bytes", 0)
        ) or None
    in_bytes = float(max(cost.get("bytes accessed", 0.0), arg_out))

    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = in_bytes / HBM_BW
    collective_s = coll_bytes / ICI_LINK_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bound = max(terms, key=terms.get)

    return Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=in_bytes,
        collective_bytes_per_device=coll_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bound=bound, model_flops=model_flops,
        useful_flops_ratio=(model_flops / (flops * chips)) if flops else 0.0,
        peak_memory_bytes=peak_mem, collectives=coll, note=note,
    )


def model_flops_for_cell(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE), D = tokens/step.

    Decode cells process one token per sequence per step but attention reads
    the full KV cache; the 6ND convention counts only parameter FLOPs (the
    deliverable's definition) — attention-KV flops show up in HLO_FLOPs and
    therefore in the useful-flops ratio, as intended.
    """
    n = cfg.n_active_params()
    if shape.kind in ("train", "prefill"):
        tokens = shape.global_batch * shape.seq_len
        mult = 6.0 if shape.kind == "train" else 2.0   # fwd-only for prefill
    else:
        tokens = shape.global_batch
        mult = 2.0
    return mult * n * tokens


def format_table(rows: list[dict]) -> str:
    hdr = (f"| {'arch':26s} | {'shape':11s} | {'mesh':6s} | {'bound':10s} "
           f"| compute_s | memory_s | collect_s | useful% | note |")
    sep = "|" + "-" * (len(hdr) - 2) + "|"
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']:26s} | {r['shape']:11s} | {r['mesh']:6s} "
            f"| {r['bound']:10s} | {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {r['collective_s']:.3e} | {100*r['useful_flops_ratio']:7.1f} "
            f"| {r.get('note','')} |"
        )
    return "\n".join(lines)
