"""Trip-count-aware roofline analysis of compiled HLO.

``compiled.cost_analysis()`` counts each while-loop body ONCE (verified in
tests/test_hlo_analysis.py), which silently drops ~L× of the FLOPs of a
scanned L-layer model. The compiled HLO, however, annotates every while op
with ``backend_config={"known_trip_count":{"n":...}}`` — so we parse the
module and do the accounting ourselves, recursively multiplying loop bodies:

- FLOPs: 2·prod(result_dims)·prod(contracting_dims) per ``dot`` (+1 flop per
  output element of elementwise fusions — noise next to the matmuls).
- HBM bytes: operand+result bytes of every *materializing* instruction
  (fusion boundaries, dots, sorts, collectives …), which is exactly the
  post-fusion HBM-traffic model a TPU roofline uses. Control/aliasing ops
  (tuple, get-tuple-element, parameter, bitcast, constant) are free.
- Collective bytes: per-kind operand sums of all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute.

All quantities are PER DEVICE (the module is the per-device SPMD program).
Hardware peaks are v5e's; a program analysed on a TPU of another kind is
refused (:func:`check_device`), never measured against them.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

from repro.compat import cost_analysis_dict

#: ``jax.Device.device_kind`` of the chip whose peaks these are
DEVICE_KIND = "TPU v5 lite"
#: Published v5e per-chip peaks (Google Cloud documentation, "TPU v5e"):
#: bf16 FLOP/s, HBM bytes/s, and ICI bytes/s per link (1,600 Gbit/s of
#: chip-to-chip interconnect over 4 links).
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def check_device(device) -> None:
    """Refuse a TPU whose peaks are not v5e's. CPU devices pass: the dry
    run compiles on CPU placeholders that stand in for a v5e mesh."""
    if device.platform == "tpu" and device.device_kind != DEVICE_KIND:
        raise ValueError(f"roofline peaks are {DEVICE_KIND!r}'s, not "
                         f"{device.device_kind!r}'s")


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# ops that don't touch HBM (aliases / control / metadata)
_FREE_OPS = {
    "tuple", "get-tuple-element", "parameter", "constant", "bitcast",
    "after-all", "partition-id", "replica-id", "domain", "opt-barrier",
    "copy-start", "copy-done",
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*\{\s*$")


def _parse_instr_line(line: str):
    """'  [ROOT] %name = TYPE opcode(rest...' -> (name, type, opcode, rest).

    Handles tuple types (balanced parens, may contain /*index=N*/ comments).
    """
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%"):
        return None
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[1:eq].strip()
    rhs = s[eq + 3:].lstrip()
    if rhs.startswith("("):  # tuple type: find matching paren
        depth = 0
        end = -1
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        type_str = rhs[: end + 1]
        tail = rhs[end + 1:].lstrip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        type_str = rhs[:sp]
        tail = rhs[sp + 1:].lstrip()
    par = tail.find("(")
    if par <= 0:
        return None
    opcode = tail[:par].strip()
    rest = tail[par + 1:]
    if not re.fullmatch(r"[\w\-]+", opcode):
        return None
    return name, type_str, opcode, rest
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"(?:calls|body|condition|branch_computations)=\{?%?"
                       r"([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)\}?")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")


def _shape_dims(type_str: str) -> List[Tuple[str, List[int]]]:
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        out.append((dt, [int(d) for d in dims.split(",")] if dims else []))
    return out


def _type_bytes(type_str: str) -> int:
    return sum(_DTYPE_BYTES[dt] * math.prod(dims or [1])
               for dt, dims in _shape_dims(type_str))


@dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    rest: str            # everything after the '(' of the opcode call

    @property
    def result_bytes(self) -> int:
        return _type_bytes(self.type_str)


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    table: Dict[str, str] = field(default_factory=dict)  # instr -> type str


def parse_module(hlo_text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in hlo_text.splitlines():
        if not line.strip():
            continue
        stripped = line.strip()
        if (stripped.endswith("{") and "->" in stripped
                and (stripped.startswith("%") or stripped.startswith("ENTRY"))
                and " = " not in stripped.split("->")[0]):
            mc = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)", stripped)
            if mc:
                cur = Computation(mc.group(1))
                comps[cur.name] = cur
                continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        parsed = _parse_instr_line(line)
        if parsed:
            name, type_str, opcode, rest = parsed
            cur.instrs.append(Instr(name, type_str, opcode, rest))
            cur.table[name] = type_str
    return comps


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: Dict[str, float] = field(default_factory=dict)
    coll_n: Dict[str, int] = field(default_factory=dict)

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        for k, v in other.coll.items():
            self.coll[k] = self.coll.get(k, 0.0) + v * mult
        for k, v in other.coll_n.items():
            self.coll_n[k] = self.coll_n.get(k, 0) + int(v * mult)

    @property
    def coll_bytes(self) -> float:
        return sum(self.coll.values())


def _dot_flops(ins: Instr, comp: Computation) -> float:
    res = _shape_dims(ins.type_str)
    if not res:
        return 0.0
    out_elems = math.prod(res[0][1] or [1])
    mc = _CONTRACT_RE.search(ins.rest)
    ops = _OPERAND_RE.findall(ins.rest)
    if not mc or not ops:
        return 2.0 * out_elems  # fallback
    lhs_type = comp.table.get(ops[0])
    if lhs_type is None:
        return 2.0 * out_elems
    lhs_dims = _shape_dims(lhs_type)
    if not lhs_dims:
        return 2.0 * out_elems
    dims = lhs_dims[0][1]
    contract = 1
    for idx in (int(i) for i in mc.group(1).split(",") if i):
        if idx < len(dims):
            contract *= dims[idx]
    return 2.0 * out_elems * contract


def _operand_bytes(ins: Instr, comp: Computation) -> int:
    total = 0
    for op in _OPERAND_RE.findall(ins.rest.split(")")[0] + ")"):
        t = comp.table.get(op)
        if t:
            total += _type_bytes(t)
    return total


class ModuleAnalyzer:
    def __init__(self, hlo_text: str):
        self.comps = parse_module(hlo_text)
        self._memo: Dict[str, Cost] = {}
        entry = None
        m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo_text, re.M)
        if m:
            entry = m.group(1)
        else:  # fall back: computation named like the module
            entry = next(iter(self.comps))
        self.entry = entry

    def cost(self) -> Cost:
        return self._comp_cost(self.entry)

    def _comp_cost(self, name: str) -> Cost:
        if name in self._memo:
            return self._memo[name]
        comp = self.comps.get(name)
        total = Cost()
        if comp is None:
            self._memo[name] = total
            return total
        self._memo[name] = total  # guard cycles
        for ins in comp.instrs:
            op = ins.opcode
            if op == "while":
                trips = 1
                mt = _TRIP_RE.search(ins.rest)
                if mt:
                    trips = int(mt.group(1))
                called = re.findall(r"(?:body|condition)=%?([\w\.\-]+)", ins.rest)
                for c in called:
                    total.add(self._comp_cost(c), trips)
                # loop state aliases in place — body instrs already count
                # real traffic (dynamic-slice reads / dus writes per trip)
                continue
            if op in ("fusion", "call", "conditional", "sort", "reduce",
                      "scatter", "map", "reduce-window", "select-and-scatter",
                      "custom-call"):
                # descend for dots/collectives inside; bytes at the boundary
                for c in re.findall(r"(?:calls|to_apply|branch_computations="
                                    r"\{?)%?([\w\.\-]+)", ins.rest):
                    sub = self._comp_cost(c)
                    total.flops += sub.flops
                    for k, v in sub.coll.items():
                        total.coll[k] = total.coll.get(k, 0.0) + v
                total.bytes += ins.result_bytes + _operand_bytes(ins, comp)
                # elementwise fusion flops ~ 1/elem (noise, but honest)
                total.flops += math.prod(
                    (_shape_dims(ins.type_str)[0][1] or [1])) if \
                    _shape_dims(ins.type_str) else 0
                continue
            if op == "dot" or op.startswith("dot."):
                total.flops += _dot_flops(ins, comp)
                total.bytes += ins.result_bytes + _operand_bytes(ins, comp)
                continue
            if op == "convolution":
                # rare here; approximate 2 * out * (prod kernel spatial * Cin)
                total.flops += 2.0 * math.prod(
                    _shape_dims(ins.type_str)[0][1] or [1])
                total.bytes += ins.result_bytes + _operand_bytes(ins, comp)
                continue
            kind = next((c for c in COLLECTIVES if op.startswith(c)), None)
            if kind is not None:
                opb = _operand_bytes(ins, comp) or ins.result_bytes
                total.coll[kind] = total.coll.get(kind, 0.0) + opb
                total.coll_n[kind] = total.coll_n.get(kind, 0) + 1
                total.bytes += ins.result_bytes + opb
                continue
            if op in _FREE_OPS:
                continue
            # other materializing op (copy, broadcast, transpose, dus, ...)
            total.bytes += ins.result_bytes + _operand_bytes(ins, comp)
        self._memo[name] = total
        return total


@dataclass
class Roofline:
    flops: float                 # per device
    hbm_bytes: float             # per device
    coll_bytes: float            # per device
    coll_by_kind: Dict[str, float]
    coll_counts: Dict[str, int]
    xla_flops_once: float        # raw cost_analysis (loop bodies once)
    arg_bytes: int
    out_bytes: int
    temp_bytes: int

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def roofline_fraction(self) -> float:
        """compute-term share of the critical path: T_comp / max(terms).
        1.0 = compute-bound at the roofline."""
        worst = max(self.t_compute, self.t_memory, self.t_collective)
        return self.t_compute / worst if worst > 0 else 0.0

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes, "coll_by_kind": self.coll_by_kind,
            "coll_counts": self.coll_counts,
            "xla_flops_once": self.xla_flops_once,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bottleneck": self.bottleneck,
            "roofline_fraction": self.roofline_fraction,
            "arg_bytes": self.arg_bytes, "out_bytes": self.out_bytes,
            "temp_bytes": self.temp_bytes,
        }


def analyze_compiled(compiled) -> Roofline:
    """Roofline of a compiled program against v5e peaks, on the devices of
    this process (:func:`check_device`)."""
    check_device(jax.devices()[0])
    cost_xla = cost_analysis_dict(compiled)
    mem = compiled.memory_analysis()
    analyzer = ModuleAnalyzer(compiled.as_text())
    c = analyzer.cost()
    return Roofline(
        flops=c.flops, hbm_bytes=c.bytes, coll_bytes=c.coll_bytes,
        coll_by_kind=c.coll, coll_counts=c.coll_n,
        xla_flops_once=float(cost_xla.get("flops", 0.0)),
        arg_bytes=getattr(mem, "argument_size_in_bytes", 0),
        out_bytes=getattr(mem, "output_size_in_bytes", 0),
        temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
    )
