"""Loop-aware statistics from optimized HLO text.

``compiled.cost_analysis()`` counts every computation once: a lax.scan over
60 layers contributes its body a single time, under-counting FLOPs, bytes
and collective traffic by the trip count.  This parser rebuilds the numbers
correctly from ``compiled.as_text()`` (the per-device SPMD program):

  * computations are parsed into instruction lists with result shapes;
  * while-loop trip counts are recovered from the canonical lax.scan
    condition (``compare(iter, constant), direction=LT``);
  * a multiplier propagates through the call graph (while bodies multiply
    by trip count; fusions/calls/conditionals inherit);
  * FLOPs  = 2 * prod(result_dims) * contraction_size per dot (+ per-op
    multiplier);
  * collective bytes = result bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute (x multiplier);
  * HBM bytes proxy  = dot operand+result bytes + cache-update traffic
    (dynamic-update-slice / gather / scatter) + entry argument bytes
    (params read once per step).  Pure-elementwise traffic is fused on TPU
    and intentionally not double-counted.

Validated in tests against hand-computed flops of known programs.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

__all__ = ["HLOStats", "parse_hlo_stats", "cost_analysis_dict"]


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()``: a flat dict (empty when unavailable)."""
    return compiled.cost_analysis() or {}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_CALLS = re.compile(
    r"(?:body|condition|calls|to_apply|branch_computations)=\{?%?([\w.\-]+)"
)
_CALLS_MULTI = re.compile(r"branch_computations=\{([^}]*)\}")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_info(type_str: str) -> tuple[int, int]:
    """(total elements, total bytes) across possibly-tuple types."""
    elems = 0
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES[dt]
    return elems, nbytes


@dataclasses.dataclass
class HLOStats:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    # HBM traffic of attention-score-shaped tensors (two trailing dims both
    # >= 1024): a flash-attention kernel keeps these in VMEM, so
    # ``bytes - score_bytes`` models the fused memory term.
    score_bytes: float = 0.0
    collective_counts: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int)
    )
    collective_bytes_by_kind: dict = dataclasses.field(
        default_factory=lambda: defaultdict(float)
    )
    while_trips: list = dataclasses.field(default_factory=list)
    unresolved_whiles: int = 0


def _score_like(type_str: str) -> bool:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return False
    dims = [int(d) for d in m.group(2).split(",") if d]
    return len(dims) >= 2 and dims[-1] >= 1024 and dims[-2] >= 1024


def _parse_computations(text: str):
    comps: dict[str, list[tuple[str, str]]] = {}
    entry = None
    cur = None
    for raw in text.splitlines():
        line = raw.rstrip()
        m = _COMP_HDR.match(line.strip())
        if m and line.strip().endswith("{"):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR.match(line)
        if mi:
            comps[cur].append((mi.group(1), mi.group(2)))
    return comps, entry


def _split_operands(arglist: str) -> list[str]:
    """Split an HLO operand list on top-level commas.

    Operand tokens may carry inline types whose dims/layouts contain commas
    (``f32[64,128]{1,0} %arg``), so a plain ``split(',')`` is wrong.
    """
    out, depth, cur = [], 0, []
    for ch in arglist:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur).strip())
    return [t for t in out if t]


def _operand_type(token: str, shapes: dict[str, str]) -> str:
    """Type string of one operand token.

    Newer XLA prints the type inline (``f32[64,128]{1,0} %name``); older
    text has only ``%name`` and the type comes from the computation's
    result-type symbol table.
    """
    if _SHAPE_RE.search(token):
        return token
    return shapes.get(token.strip().lstrip("%"), "")


def _dot_flops(rhs: str, shapes: dict[str, str]) -> float:
    # result type is the prefix of rhs up to ' dot('
    mres = _SHAPE_RE.search(rhs)
    if not mres:
        return 0.0
    res_elems, _ = _shape_info(rhs.split(" dot(")[0])
    # contraction size from lhs operand shape + lhs_contracting_dims
    mops = re.search(r"dot\(([^)]*)\)", rhs)
    mc = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rhs)
    if not (mops and mc):
        return 2.0 * res_elems  # dot with unknown contraction: lower bound
    operands = _split_operands(mops.group(1))
    lhs_type = _operand_type(operands[0], shapes) if operands else ""
    dims_m = _SHAPE_RE.search(lhs_type)
    if not dims_m:
        return 2.0 * res_elems
    lhs_dims = [int(d) for d in dims_m.group(2).split(",") if d]
    contract = 1
    for i in mc.group(1).split(","):
        if i and int(i) < len(lhs_dims):
            contract *= lhs_dims[int(i)]
    return 2.0 * res_elems * contract


def _while_trip(cond_name: str, comps, shapes_by_comp) -> int | None:
    """Recover the lax.scan trip count from the condition computation.

    Canonical lowering: the condition holds ``constant(N)`` and compares the
    iteration counter against it (possibly through a wrapped-compare
    fusion).  lax.scan counts 0..N-1 step 1, so the single positive scalar
    constant in the condition *is* the trip count.
    """
    instrs = comps.get(cond_name, [])
    consts: list[int] = []
    for name, rhs in instrs:
        mc = re.match(r"s(?:32|64)\[\]\s+constant\((-?\d+)\)", rhs)
        if mc:
            consts.append(int(mc.group(1)))
    pos = [c for c in consts if c > 0]
    if len(pos) >= 1:
        return max(pos)
    return None


def parse_hlo_stats(text: str) -> HLOStats:
    comps, entry = _parse_computations(text)
    # result-type symbol table per computation
    shapes_by_comp: dict[str, dict[str, str]] = {}
    for cname, instrs in comps.items():
        tbl = {}
        for name, rhs in instrs:
            tbl[name] = rhs.split(" ")[0] if rhs else ""
            # better: type is everything up to the opcode word; keep the
            # full rhs for shape regex fallback
            tbl[name] = rhs
        shapes_by_comp[cname] = tbl

    stats = HLOStats()
    if entry is None:
        return stats

    # propagate multipliers through the call graph (iterative DFS)
    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    stack = [entry]
    seen_edges = set()
    while stack:
        cname = stack.pop()
        m = mult[cname]
        for name, rhs in comps.get(cname, []):
            if " while(" in rhs:
                mbody = re.search(r"body=%?([\w.\-]+)", rhs)
                mcond = re.search(r"condition=%?([\w.\-]+)", rhs)
                # XLA annotates statically-known loops directly; prefer that
                # over reverse-engineering the condition's constant.
                mknown = re.search(
                    r"known_trip_count[\"':={\s]+n[\"':\s]*[:=]?\s*\"?(\d+)",
                    rhs,
                )
                trip = int(mknown.group(1)) if mknown else None
                if trip is None and mcond:
                    trip = _while_trip(mcond.group(1), comps, shapes_by_comp)
                if trip is None:
                    trip = 1
                    stats.unresolved_whiles += 1
                else:
                    stats.while_trips.append(trip)
                if mbody:
                    key = (cname, mbody.group(1))
                    if key not in seen_edges:
                        seen_edges.add(key)
                        mult[mbody.group(1)] += m * trip
                        stack.append(mbody.group(1))
                continue
            mbr = _CALLS_MULTI.search(rhs)
            called = []
            if mbr:
                called = [c.strip().lstrip("%") for c in
                          mbr.group(1).split(",")]
            else:
                for cm in _CALLS.finditer(rhs):
                    called.append(cm.group(1))
            for cal in called:
                if cal in comps:
                    key = (cname, name, cal)
                    if key not in seen_edges:
                        seen_edges.add(key)
                        mult[cal] += m
                        stack.append(cal)

    # accumulate statistics
    for cname, instrs in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        tbl = shapes_by_comp[cname]
        for name, rhs in instrs:
            head = rhs.split("(")[0]
            if " dot(" in rhs:
                stats.flops += m * _dot_flops(rhs, tbl)
                res_type = rhs.split(" dot(")[0]
                _, rb = _shape_info(res_type)
                if _score_like(res_type):
                    stats.score_bytes += m * rb
                mops = re.search(r"dot\(([^)]*)\)", rhs)
                ob = 0
                if mops:
                    for op in _split_operands(mops.group(1)):
                        t_op = _operand_type(op, tbl)
                        _, b = _shape_info(t_op)
                        ob += b
                        if _score_like(t_op):
                            stats.score_bytes += m * b
                stats.bytes += m * (rb + ob)
                continue
            for coll in _COLLECTIVES:
                if re.search(rf"\b{coll}(-start)?\(", rhs):
                    _, b = _shape_info(rhs.split(f" {coll}")[0])
                    stats.collective_bytes += m * b
                    stats.collective_counts[coll] += int(m)
                    stats.collective_bytes_by_kind[coll] += m * b
                    break
            else:
                if head.endswith(("dynamic-update-slice", "gather",
                                  "scatter", "dynamic-slice")):
                    # cache/update traffic: result bytes
                    _, b = _shape_info(rhs.split(" " + head.split()[-1])[0])
                    stats.bytes += m * b

    # entry arguments (params/caches) are read once per step
    # (approximation: count parameter instruction types in ENTRY)
    for name, rhs in comps.get(entry, []):
        if " parameter(" in rhs:
            _, b = _shape_info(rhs.split(" parameter(")[0])
            stats.bytes += b
    return stats
