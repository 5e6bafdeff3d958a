"""While-loop-aware analysis of post-SPMD optimized HLO text.

XLA's built-in ``compiled.cost_analysis()`` counts every while-loop body ONCE
(verified empirically: a scan of 10 matmuls reports the FLOPs of 1). All of our
models scan over layers, query chunks and CE chunks, so both FLOPs and collective
bytes would be undercounted by 1–3 orders of magnitude. This module re-derives

  * dot FLOPs            (2 · output_elems · contracted_elems per dot op)
  * collective operand bytes, per collective type

from the optimized HLO *text*, walking the call graph (fusions, calls, whiles) and
multiplying while bodies by their trip counts (recovered from the loop-condition
constant — exact for lax.scan/fori loops, which is all we emit).

Shapes in the partitioned module are per-device, so all results are per-device.
Elementwise FLOPs are ignored (irrelevant at roofline granularity); dots and convs
dominate. Results are validated against XLA's own cost analysis on loop-free
modules in tests/test_hlo_analysis.py.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

def cost_analysis_dict(compiled) -> Dict:
    """``Compiled.cost_analysis()`` as a dict (the backend may return None)."""
    return dict(compiled.cost_analysis() or {})


_SHAPE_TOKEN = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_COMP_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->")
_OPCODE = re.compile(r"^((?:\([^)]*\)|[\w\[\],{}*/ ]+?))\s([\w\-]+)\(")


def _shape_dims(tok: str) -> List[Tuple[str, List[int]]]:
    """All dtype[dims] tokens in a shape string (tuples yield several)."""
    return [(d, [int(x) for x in dims.split(",") if x])
            for d, dims in _SHAPE_TOKEN.findall(tok)]


def _shape_bytes(tok: str) -> int:
    return sum(_DTYPE_BYTES.get(d, 4) * math.prod(dims or [1])
               for d, dims in _shape_dims(tok))


@dataclasses.dataclass
class Instr:
    name: str
    shape: str          # raw output-shape string
    opcode: str
    rest: str           # text after the opcode's '('


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    by_name: Dict[str, Instr]


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        if not line.strip():
            continue
        head = _COMP_HEAD.match(line)
        if head and line.rstrip().endswith("{"):
            cur = Computation(head.group(1), [], {})
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        om = _OPCODE.match(rhs)
        if not om:
            continue
        shape, opcode = om.groups()
        rest = rhs[om.end():]
        ins = Instr(name, shape.strip(), opcode, rest)
        cur.instrs.append(ins)
        cur.by_name[name] = ins
    return comps


def _operands(rest: str) -> List[str]:
    """Operand instruction names: %foo tokens before the closing paren."""
    depth, out, i = 1, [], 0
    while i < len(rest) and depth > 0:
        c = rest[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        i += 1
    body = rest[: i - 1]
    return re.findall(r"%([\w.\-]+)", body)


def _attr(rest: str, key: str) -> Optional[str]:
    m = re.search(key + r"=((?:\{[^}]*\})|(?:\[[^\]]*\][^,]*)|[^,\s]+)", rest)
    return m.group(1) if m else None


def _dims_list(attr: Optional[str]) -> List[int]:
    if not attr:
        return []
    return [int(x) for x in re.findall(r"\d+", attr)]


def _group_size(rest: str, n_devices: int) -> int:
    """Parse replica_groups=[G,S]<=... or explicit {{...},{...}}."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", rest)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", rest)
    if m:
        return len(m.group(1).split(","))
    return n_devices


@dataclasses.dataclass
class Totals:
    dot_flops: float = 0.0
    conv_flops: float = 0.0
    collective_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    collective_counts: Dict[str, float] = dataclasses.field(default_factory=dict)

    def add(self, other: "Totals", mult: float = 1.0):
        self.dot_flops += mult * other.dot_flops
        self.conv_flops += mult * other.conv_flops
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0.0) + mult * v
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = (self.collective_counts.get(k, 0.0)
                                         + mult * v)

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


class Analyzer:
    def __init__(self, text: str, n_devices: int = 1):
        self.comps = parse_module(text)
        self.n_devices = n_devices
        self._memo: Dict[str, Totals] = {}
        self.entry = self._find_entry(text)

    def _find_entry(self, text: str) -> str:
        m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M)
        if m:
            return m.group(1)
        # fall back: the largest computation
        return max(self.comps, key=lambda c: len(self.comps[c].instrs))

    # -- trip count ----------------------------------------------------------
    def trip_count(self, cond_name: str) -> float:
        comp = self.comps.get(cond_name)
        if comp is None:
            return 1.0
        consts = []
        for ins in comp.instrs:
            m = re.match(r"constant\((\-?\d+)\)", ins.opcode + "(" + ins.rest) \
                if False else None
        for ins in comp.instrs:
            if ins.opcode == "constant":
                m = re.match(r"(\-?\d+)\)", ins.rest)
                if m:
                    consts.append(int(m.group(1)))
        return float(max(consts)) if consts else 1.0

    # -- per-instruction costs ------------------------------------------------
    def _dot_flops(self, comp: Computation, ins: Instr) -> float:
        out_elems = sum(math.prod(d or [1]) for _, d in _shape_dims(ins.shape))
        ops = _operands(ins.rest)
        lhs_cdims = _dims_list(_attr(ins.rest, "lhs_contracting_dims"))
        contracted = 1
        if ops:
            lhs = comp.by_name.get(ops[0])
            if lhs is not None:
                dims_all = _shape_dims(lhs.shape)
                if dims_all:
                    _, ld = dims_all[0]
                    for ci in lhs_cdims:
                        if ci < len(ld):
                            contracted *= ld[ci]
        return 2.0 * out_elems * contracted

    def _conv_flops(self, comp: Computation, ins: Instr) -> float:
        # rough: 2 * out_elems * (kernel spatial × in_features per group)
        out_elems = sum(math.prod(d or [1]) for _, d in _shape_dims(ins.shape))
        ops = _operands(ins.rest)
        k_elems = 1
        if len(ops) > 1:
            ker = comp.by_name.get(ops[1])
            if ker is not None:
                dims_all = _shape_dims(ker.shape)
                if dims_all:
                    _, kd = dims_all[0]
                    k_elems = math.prod(kd or [1])
        return 2.0 * out_elems * max(k_elems, 1)

    def _collective(self, ins: Instr, t: Totals):
        op = ins.opcode.replace("-start", "")
        if op not in COLLECTIVES:
            return
        out_bytes = _shape_bytes(ins.shape)
        g = _group_size(ins.rest, self.n_devices)
        if op == "all-gather":
            operand = out_bytes / max(g, 1)
        elif op == "reduce-scatter":
            operand = out_bytes * g
        else:  # all-reduce, all-to-all, collective-permute: operand ≈ output
            operand = out_bytes
        t.collective_bytes[op] = t.collective_bytes.get(op, 0.0) + operand
        t.collective_counts[op] = t.collective_counts.get(op, 0.0) + 1

    # -- aggregation -----------------------------------------------------------
    def totals(self, comp_name: Optional[str] = None) -> Totals:
        comp_name = comp_name or self.entry
        if comp_name in self._memo:
            return self._memo[comp_name]
        t = Totals()
        self._memo[comp_name] = t          # cycles guard (shouldn't happen)
        comp = self.comps.get(comp_name)
        if comp is None:
            return t
        for ins in comp.instrs:
            if ins.opcode == "dot":
                t.dot_flops += self._dot_flops(comp, ins)
            elif ins.opcode in ("convolution",):
                t.conv_flops += self._conv_flops(comp, ins)
            elif ins.opcode.replace("-start", "") in COLLECTIVES:
                self._collective(ins, t)
            elif ins.opcode == "while":
                body = _attr(ins.rest, "body")
                cond = _attr(ins.rest, "condition")
                body = body.lstrip("%") if body else None
                cond = cond.lstrip("%") if cond else None
                trips = self.trip_count(cond) if cond else 1.0
                if body:
                    t.add(self.totals(body), trips)
                if cond:
                    t.add(self.totals(cond), trips)
            elif ins.opcode in ("fusion", "call", "custom-call"):
                callee = _attr(ins.rest, "calls")
                if callee:
                    t.add(self.totals(callee.lstrip("%")))
            elif ins.opcode == "conditional":
                for branch in re.findall(
                        r"(?:branch_computations=\{([^}]*)\}|"
                        r"(?:true|false)_computation=%([\w.\-]+))", ins.rest):
                    for b in branch:
                        for nm in re.findall(r"%?([\w.\-]+)", b or ""):
                            if nm in self.comps:
                                t.add(self.totals(nm))
        self._memo[comp_name] = t
        return t


def analyze(text: str, n_devices: int = 1) -> Dict:
    a = Analyzer(text, n_devices)
    t = a.totals()
    return {
        "dot_flops": t.dot_flops,
        "conv_flops": t.conv_flops,
        "collective_bytes": dict(t.collective_bytes),
        "collective_counts": dict(t.collective_counts),
        "total_collective_bytes": t.total_collective_bytes,
    }
