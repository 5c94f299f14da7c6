"""Loop-aware HLO accounting for the roofline.

``compiled.cost_analysis()`` on this backend counts each ``while`` body ONCE
(scan-over-layers would be undercounted ~num_layers x) and reports no
collective traffic at all.  This module parses the post-SPMD, per-device HLO
text into a call graph and propagates three metrics with known trip counts:

* ``dot_flops``          — 2 * prod(result_dims) * contraction_size per dot
* ``collective_bytes``   — result-shape bytes of all-reduce / all-gather /
                           reduce-scatter / all-to-all / collective-permute
* ``traffic_bytes``      — operand + result bytes of every top-level
                           instruction (post-fusion ⇒ a reasonable HBM-
                           traffic proxy; intra-fusion temporaries excluded)

Known limitations: non-dot FLOPs (convolutions, transcendentals) are not
counted; dynamic trip counts default to 1; all-reduce bytes are counted
once (not 2x for its reduce-scatter + all-gather decomposition).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "all-reduce-start", "all-gather-start",
               "collective-permute-start", "ragged-all-to-all"}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?.*?\)?)\s+([a-z][\w\-]*)\((.*)$")
_TRIP_RE = re.compile(r'"known_trip_count":\s*\{"n":\s*"(\d+)"\}')
_CALLEE_LIST_RE = re.compile(
    r"(?:called_computations|branch_computations)=\{([^}]*)\}")


def _shapes(text: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt in DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _nbytes(text: str) -> int:
    total = 0
    for dt, dims in _shapes(text):
        n = 1
        for d in dims:
            n *= d
        total += n * DTYPE_BYTES[dt]
    return total


@dataclass
class Metrics:
    dot_flops: float = 0.0
    collective_bytes: float = 0.0
    traffic_bytes: float = 0.0
    coll_by_op: Dict[str, float] = field(default_factory=dict)
    coll_counts: Dict[str, int] = field(default_factory=dict)

    def add(self, other: "Metrics", mult: float = 1.0,
            include_traffic: bool = True):
        self.dot_flops += mult * other.dot_flops
        self.collective_bytes += mult * other.collective_bytes
        if include_traffic:
            self.traffic_bytes += mult * other.traffic_bytes
        for k, v in other.coll_by_op.items():
            self.coll_by_op[k] = self.coll_by_op.get(k, 0.0) + mult * v
        for k, v in other.coll_counts.items():
            self.coll_counts[k] = self.coll_counts.get(k, 0) + int(mult * v)


# ops whose operands/results never touch HBM at the instruction boundary
_FREE_OPS = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "iota", "after-all", "partition-id", "replica-id"}


@dataclass
class Computation:
    name: str
    metrics: Metrics = field(default_factory=Metrics)
    # (callee, multiplier, include_traffic): fusion bodies execute entirely
    # in registers/VMEM — their internal ops contribute FLOPs/collectives
    # but NOT HBM traffic (the call-site fusion op's operands/result do).
    calls: List[Tuple[str, float, bool]] = field(default_factory=list)
    is_entry: bool = False


def _parse_dot_flops(result_txt: str, args_txt: str,
                     symbols: Dict[str, int]) -> float:
    """FLOPs of a dot: 2 * prod(result) * contraction_size."""
    res_shapes = _shapes(result_txt)
    if not res_shapes:
        return 0.0
    _, rdims = res_shapes[0]
    out_elems = 1
    for d in rdims:
        out_elems *= d
    # contraction size: product of lhs contracting dims
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", args_txt)
    operands = re.findall(r"%([\w.\-]+)", args_txt)
    k = 1
    if m and operands:
        lhs_shape = symbols.get(operands[0])
        if lhs_shape:
            for ci in (int(x) for x in m.group(1).split(",") if x):
                if ci < len(lhs_shape):
                    k *= lhs_shape[ci]
    return 2.0 * out_elems * k


def parse_hlo(hlo_text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    symbols: Dict[str, List[int]] = {}

    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        header = re.match(
            r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$", stripped)
        if header:
            current = Computation(header.group(2),
                                  is_entry=bool(header.group(1)))
            comps[current.name] = current
            symbols = {}
            continue
        if current is None or not stripped or stripped == "}":
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, result_txt, op, rest = m.groups()
        # record result bytes in the symbol table (tuples: total bytes)
        symbols[name] = _nbytes(result_txt)
        sym_shapes = getattr(current, "_shapes", None)
        if sym_shapes is None:
            sym_shapes = {}
            current._shapes = sym_shapes          # dims for dot contraction
        sh = _shapes(result_txt)
        if sh:
            sym_shapes[name] = sh[0][1]
        met = current.metrics
        # traffic: result write + operand reads (resolved via symbol table);
        # metadata/aliasing ops are free; slicing ops read/write only the
        # slice, not their full operand (a 32k-step scan would otherwise
        # count its whole xs buffer once per iteration)
        if op in ("dynamic-slice", "slice", "gather") or (
                op == "fusion" and ("slice" in name
                                    or "dynamic_slice" in rest
                                    or "dynamic_update_slice" in rest)):
            met.traffic_bytes += 2 * symbols[name]          # read + write
        elif op == "dynamic-update-slice":
            # writes only the update region ~ smallest operand, twice
            arg_head = rest.split(")")[0]
            opsz = [symbols.get(o, 0) for o in
                    re.findall(r"%([\w.\-]+)", arg_head)]
            met.traffic_bytes += 2 * min([s for s in opsz if s] or [0])
        elif op not in _FREE_OPS:
            met.traffic_bytes += symbols[name]
            arg_head = rest.split(")")[0]
            for opnd in re.findall(r"%([\w.\-]+)", arg_head):
                met.traffic_bytes += symbols.get(opnd, 0)
        if op == "dot":
            met.dot_flops += _parse_dot_flops(result_txt, rest, sym_shapes)
        if op in COLLECTIVES:
            base = op.replace("-start", "")
            b = _nbytes(result_txt)
            met.collective_bytes += b
            met.coll_by_op[base] = met.coll_by_op.get(base, 0.0) + b
            met.coll_counts[base] = met.coll_counts.get(base, 0) + 1
        # call edges with loop multipliers; fusion bodies don't add traffic
        trip = 1.0
        if op == "while":
            tm = _TRIP_RE.search(rest)
            if tm:
                trip = float(tm.group(1))
        in_vmem = op in ("fusion", "reduce", "map", "sort", "scatter",
                         "reduce-window", "select-and-scatter")
        for am in re.finditer(r"(body|condition|to_apply|calls)=%?([\w.\-]+)",
                              rest):
            attr, callee = am.groups()
            mult = trip if (op == "while" and attr in ("body", "condition")) \
                else 1.0
            current.calls.append((callee, mult, not in_vmem))
        lm = _CALLEE_LIST_RE.search(rest)
        if lm:
            for callee in re.findall(r"%?([\w.\-]+)", lm.group(1)):
                current.calls.append((callee, 1.0, not in_vmem))
    return comps


def aggregate(comps: Dict[str, Computation]) -> Metrics:
    memo: Dict[str, Metrics] = {}

    def total(name: str, stack=()) -> Metrics:
        if name in memo:
            return memo[name]
        out = Metrics()
        if name not in comps or name in stack:
            return out
        c = comps[name]
        out.add(c.metrics)
        for callee, mult, inc_traffic in c.calls:
            out.add(total(callee, stack + (name,)), mult, inc_traffic)
        memo[name] = out
        return out

    called = {c for comp in comps.values() for c, _, _ in comp.calls}
    roots = [c for c in comps if c not in called]
    entry = next((c.name for c in comps.values() if c.is_entry), None)
    root = entry or (roots[0] if roots else next(iter(comps), None))
    return total(root) if root else Metrics()


def analyze(hlo_text: str) -> Dict:
    met = aggregate(parse_hlo(hlo_text))
    return {
        "dot_flops": met.dot_flops,
        "collective_bytes": met.collective_bytes,
        "traffic_bytes": met.traffic_bytes,
        "coll_by_op": met.coll_by_op,
        "coll_counts": met.coll_counts,
    }
