"""Trip-count-aware HLO cost analyzer.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE regardless of
trip count, so any scanned-layers model under-reports FLOPs, bytes and
collective traffic by ~n_layers.  This analyzer parses post-optimization HLO
text, builds the computation call graph (fusion ``calls=``, while ``body=``/
``condition=``, reduce ``to_apply=``), infers while trip counts from the
condition's loop-bound constants, and multiplies every op's cost by the
product of trip counts along its call chain.

Costs:
  flops            2 * prod(result) * prod(contracting dims) per dot;
                   elementwise/reduce ops contribute prod(result).
  bytes            operand + result buffer sizes per op, fusion interiors
                   excluded (their traffic is the fusion op's operands and
                   results at the call site) — an HBM-traffic proxy.
  collective bytes per-device operand size per cross-device collective.

Validated against ``cost_analysis()`` on unscanned modules (tests) and used
as the primary source for §Roofline.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .hlo_capture import group_size

__all__ = ["HloModule", "analyze_hlo", "OpCost"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([a-z][\w\-]*)\((.*)$"
)
_SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_CALL_ATTR = re.compile(r"\b(calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CONSTANT_VAL = re.compile(r"constant\((\-?\d+)\)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
}
_POINTWISE = {
    "add", "multiply", "subtract", "divide", "maximum", "minimum",
    "exponential", "tanh", "rsqrt", "power", "select", "compare", "and",
    "or", "negate", "abs", "log", "sqrt", "floor", "convert", "reduce",
    "exponential-minus-one", "logistic",
}
_NO_BYTES = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "reshape", "broadcast", "transpose", "copy",
    # control-flow boundaries: loop state lives in place; the body ops are
    # already charged per iteration — charging the while's operand tuple per
    # entry double-counts ~65% on scan-heavy models (measured, gemma3-1b)
    "while", "conditional", "call",
}
# layout/shape ops are free on TPU (fused or relaid); for fusion-island
# tracking they alias their first operand
_TRANSPARENT = {
    "get-tuple-element", "bitcast", "reshape", "broadcast", "transpose",
    "copy", "tuple",
}


def _type_bytes_elems(type_str: str) -> Tuple[int, int]:
    tb = te = 0
    for m in _SHAPE.finditer(type_str):
        nb = _DTYPE_BYTES.get(m.group(1))
        if nb is None:
            continue
        n = 1
        for d in m.group(2).split(","):
            if d.strip():
                n *= int(d)
        tb += n * nb
        te += n
    return tb, te


def _first_dims(type_str: str) -> List[int]:
    m = _SHAPE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d.strip()]


@dataclass
class OpCost:
    name: str
    opcode: str
    result_bytes: int
    result_elems: int
    flops: float = 0.0
    operand_bytes: int = 0
    collective_kind: Optional[str] = None
    collective_bytes: int = 0
    group_size: int = 1
    operands: Tuple[str, ...] = ()
    hbm_result: bool = True  # False: pointwise output consumed only pointwise


@dataclass
class _Computation:
    name: str
    ops: List[OpCost] = field(default_factory=list)
    callees: List[Tuple[str, str]] = field(default_factory=list)
    bytes_of: Dict[str, int] = field(default_factory=dict)
    dims_of: Dict[str, List[int]] = field(default_factory=dict)
    producer_of: Dict[str, str] = field(default_factory=dict)
    alias_of: Dict[str, str] = field(default_factory=dict)
    constants: List[int] = field(default_factory=list)

    def base(self, name: str) -> str:
        seen = 0
        while name in self.alias_of and seen < 64:
            name = self.alias_of[name]
            seen += 1
        return name

    def base_producer(self, name: str) -> str:
        return self.producer_of.get(self.base(name), "")


class HloModule:
    def __init__(self, comps: Dict[str, _Computation], entry: Optional[str]):
        self.comps = comps
        self.entry = entry
        self.fusion_bodies = {
            callee
            for comp in comps.values()
            for kind, callee in comp.callees
            if kind in ("calls", "to_apply")
        }
        self._mult = self._compute_multipliers()
        # fusion islands: a pointwise result stays in registers unless a
        # non-pointwise, non-transparent op (or the root) consumes it —
        # consumption is resolved through transparent aliases
        for comp in comps.values():
            escaping: set = set()
            consumed: set = set()
            for op in comp.ops:
                for o in op.operands:
                    b = comp.base(o)
                    consumed.add(b)
                    if op.opcode not in _POINTWISE and \
                            op.opcode not in _TRANSPARENT:
                        escaping.add(b)
            for op in comp.ops:
                if op.opcode in _POINTWISE and op.name in consumed and \
                        op.name not in escaping:
                    op.hbm_result = False

    # -- call-graph multipliers ------------------------------------------------

    def _trip_count(self, cond_name: str) -> int:
        cond = self.comps.get(cond_name)
        if cond is None or not cond.constants:
            return 1
        bounds = [c for c in cond.constants if 0 < c < 1_000_000]
        return max(bounds) if bounds else 1

    def _cond_for(self, caller: _Computation, body: str) -> str:
        conds = [n for k, n in caller.callees if k == "condition"]
        bodies = [n for k, n in caller.callees if k == "body"]
        if body in bodies:
            i = bodies.index(body)
            if i < len(conds):
                return conds[i]
        return conds[0] if conds else ""

    def _compute_multipliers(self) -> Dict[str, float]:
        if self.entry is None:
            return {c: 1.0 for c in self.comps}
        mult: Dict[str, float] = {c: 0.0 for c in self.comps}
        mult[self.entry] = 1.0
        for _ in range(len(self.comps) + 2):
            changed = False
            for cname, comp in self.comps.items():
                m = mult.get(cname, 0.0)
                if m == 0.0:
                    continue
                for kind, callee in comp.callees:
                    if callee not in mult:
                        continue
                    factor = m
                    if kind == "body":
                        factor = m * self._trip_count(self._cond_for(comp, callee))
                    if factor > mult[callee]:
                        mult[callee] = factor
                        changed = True
            if not changed:
                break
        return {c: (m if m > 0 else 1.0) for c, m in mult.items()}

    def multiplier(self, comp: str) -> float:
        return self._mult.get(comp, 1.0)

    # -- aggregates ---------------------------------------------------------------

    def total_flops(self) -> float:
        return sum(
            op.flops * self._mult[c]
            for c, comp in self.comps.items()
            for op in comp.ops
        )

    def dot_flops(self) -> float:
        return sum(
            op.flops * self._mult[c]
            for c, comp in self.comps.items()
            for op in comp.ops
            if op.opcode in ("dot", "ragged-dot", "convolution")
        )

    def total_bytes(self) -> float:
        return sum(
            ((op.result_bytes if op.hbm_result else 0) + op.operand_bytes)
            * self._mult[c]
            for c, comp in self.comps.items()
            if c not in self.fusion_bodies
            for op in comp.ops
            if op.opcode not in _NO_BYTES
        )

    def collective_bytes(self) -> float:
        return sum(
            op.collective_bytes * self._mult[c]
            for c, comp in self.comps.items()
            for op in comp.ops
            if op.collective_kind and op.group_size != 1
        )

    def collectives_by_kind(self) -> Dict[str, Tuple[float, float]]:
        out: Dict[str, Tuple[float, float]] = {}
        for c, comp in self.comps.items():
            for op in comp.ops:
                if not op.collective_kind or op.group_size == 1:
                    continue
                cnt, byt = out.get(op.collective_kind, (0.0, 0.0))
                out[op.collective_kind] = (
                    cnt + self._mult[c],
                    byt + op.collective_bytes * self._mult[c],
                )
        return out

    def max_while_trip(self) -> int:
        trips = [1]
        for comp in self.comps.values():
            for k, callee in comp.callees:
                if k == "body":
                    trips.append(self._trip_count(self._cond_for(comp, callee)))
        return max(trips)

    def top_collectives(self, n: int = 10):
        """Largest collective contributors: (total_bytes, mult, op)."""
        rows = []
        for c, comp in self.comps.items():
            for op in comp.ops:
                if op.collective_kind and op.group_size != 1:
                    rows.append(
                        (op.collective_bytes * self._mult[c], self._mult[c], op)
                    )
        return sorted(rows, key=lambda r: -r[0])[:n]

    def top_flops(self, n: int = 10):
        rows = []
        for c, comp in self.comps.items():
            for op in comp.ops:
                if op.flops > 0:
                    rows.append((op.flops * self._mult[c], self._mult[c], op))
        return sorted(rows, key=lambda r: -r[0])[:n]


def analyze_hlo(text: str) -> HloModule:
    comps: Dict[str, _Computation] = {}
    entry: Optional[str] = None
    cur: Optional[_Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        if not stripped:
            continue
        if "=" not in stripped.split("(")[0]:
            hm = _COMP_HEADER.match(stripped)
            if hm and stripped.endswith("{"):
                cur = _Computation(name=hm.group(2))
                comps[cur.name] = cur
                if hm.group(1):
                    entry = cur.name
                continue
        if stripped == "}":
            cur = None
            continue
        if cur is None:
            continue
        om = _OP_LINE.match(line)
        if not om:
            continue
        name, type_str, opcode, rest = om.groups()
        rbytes, relems = _type_bytes_elems(type_str)
        cur.bytes_of[name] = rbytes
        cur.dims_of[name] = _first_dims(type_str)
        op = OpCost(
            name=name, opcode=opcode, result_bytes=rbytes, result_elems=relems
        )
        for cm in _CALL_ATTR.finditer(rest):
            cur.callees.append((cm.group(1), cm.group(2)))
        bm = _BRANCHES.search(rest)
        if bm:
            for b in bm.group(1).split(","):
                b = b.strip().lstrip("%")
                if b:
                    cur.callees.append(("branch", b))
        if opcode == "constant":
            km = _CONSTANT_VAL.search(stripped)
            if km:
                try:
                    cur.constants.append(int(km.group(1)))
                except ValueError:
                    pass
        arglist = rest.split(")", 1)[0]
        operand_names = _OPERAND.findall(arglist)
        op.operand_bytes = sum(cur.bytes_of.get(o, 0) for o in operand_names)
        # fusion-island HBM model: on TPU, Mosaic/XLA fuses pointwise chains,
        # so a pointwise op consuming another pointwise op's output reads it
        # from registers, not HBM.  The CPU backend fuses far less, so without
        # this the byte proxy overcounts recurrent scan bodies ~10x.
        if opcode in _TRANSPARENT and operand_names:
            cur.alias_of[name] = operand_names[0]
        # slice-driven reads touch only what they emit, not the whole array
        if opcode in ("dynamic-slice", "slice", "gather"):
            op.operand_bytes = 0
        elif opcode in ("dynamic-update-slice", "scatter"):
            # in-place on TPU: read+write of the update region only
            upd = (
                cur.bytes_of.get(operand_names[1], 0)
                if len(operand_names) > 1 else 0
            )
            op.operand_bytes = 2 * upd
            op.hbm_result = False
        elif opcode == "fusion":
            # kLoop fusions are elementwise-rooted: interior slices mean the
            # operands are only partially read; bound traffic by fanin x out.
            # kInput/kOutput (reduce-rooted) fusions stream operands fully.
            if "kind=kLoop" in rest:
                op.operand_bytes = min(op.operand_bytes, 4 * op.result_bytes)
        if opcode in _POINTWISE:
            fused_in = sum(
                cur.bytes_of.get(o, 0)
                for o in operand_names
                if cur.base_producer(o) in _POINTWISE
            )
            op.operand_bytes -= fused_in
        op.operands = tuple(operand_names)
        cur.producer_of[name] = opcode
        if opcode in ("dot", "ragged-dot"):
            contract = 1
            cm2 = _CONTRACT.search(rest)
            lhs_dims: List[int] = []
            # prefer inline operand shape, else the def-site dims
            if operand_names:
                m = re.search(
                    r"([a-z][a-z0-9]*)\[([0-9,]*)\][^%]*%"
                    + re.escape(operand_names[0]) + r"\b",
                    arglist,
                )
                if m:
                    lhs_dims = [int(d) for d in m.group(2).split(",") if d.strip()]
                else:
                    lhs_dims = cur.dims_of.get(operand_names[0], [])
            if cm2 and lhs_dims:
                for d in (int(x) for x in cm2.group(1).split(",") if x.strip()):
                    if d < len(lhs_dims):
                        contract *= lhs_dims[d]
            op.flops = 2.0 * relems * contract
        elif opcode == "convolution":
            op.flops = 2.0 * relems
        elif opcode in _POINTWISE:
            op.flops = float(relems)
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base in _COLLECTIVES and not opcode.endswith("-done"):
            gsz = group_size(rest)
            op.collective_kind = base
            op.group_size = gsz
            if opcode.endswith("-start"):
                # async start: a tuple of (operand, result, context) buffers;
                # the result is the largest of them, not their sum
                rbytes = max(
                    (_type_bytes_elems(m.group(0))[0]
                     for m in _SHAPE.finditer(type_str)),
                    default=0,
                )
            if base == "all-gather":
                op.collective_bytes = rbytes // max(gsz, 1)
            elif base == "reduce-scatter":
                op.collective_bytes = rbytes * gsz
            else:
                op.collective_bytes = max(op.operand_bytes, rbytes)
        cur.ops.append(op)
    return HloModule(comps, entry)
