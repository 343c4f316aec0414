"""Loop-aware analysis of post-optimisation HLO text.

XLA's flat ``cost_analysis()`` counts every ``while`` body ONCE, so any
program built around ``lax.scan`` (stacked layers, chunked attention)
under-reports FLOPs, bytes, and collective traffic by the trip count.
This module re-derives the three roofline inputs from the compiled HLO
*with* loop multipliers:

  * computations are parsed into a call graph (while bodies, fusions,
    calls, conditionals), with a per-computation symbol table so operand
    shapes resolve even though the dump prints operands as bare names;
  * while trip counts are recovered from the canonical XLA loop form
    (condition compares the induction variable against a constant);
  * dot/convolution FLOPs, per-op HBM traffic (operands + results of
    top-level ops = post-fusion kernel boundaries), and collective operand
    bytes are accumulated over the graph, multiplying by trip counts.

Validated against ``cost_analysis()`` on loop-free programs and against
hand counts on scan programs (tests/test_hlo_analysis.py).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w\.\-]+)\s*=\s*"
    r"(?P<rtype>\([^=]*?\)|\S+)\s+"
    r"(?P<kind>[a-z][a-z0-9\-]*)\((?P<rest>.*)$")
_HDR_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*\((?P<params>.*)\)"
                     r"\s*->")
_PARAM_RE = re.compile(r"([\w\.\-]+):\s*((?:" + "|".join(_DTYPE_BYTES) +
                       r")\[[0-9,]*\])")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# ops that move no HBM bytes of their own (meta / control / aliases)
_SKIP_TRAFFIC = {"parameter", "constant", "get-tuple-element", "tuple",
                 "bitcast", "while", "call", "conditional", "after-all",
                 "iota", "partition-id", "replica-id", "domain",
                 "opt-barrier"}

# ops a TPU compiler fuses into neighbouring kernels: their top-level
# appearance in the CPU dump is a backend artifact, so they are excluded
# from the fusion-optimistic traffic figure (bytes_fused)
_ELEMENTWISE = {"add", "subtract", "multiply", "divide", "power", "negate",
                "exponential", "exponential-minus-one", "log", "log-plus-one",
                "tanh", "logistic", "sqrt", "rsqrt", "cbrt", "abs", "sign",
                "maximum", "minimum", "compare", "select", "and", "or",
                "not", "xor", "convert", "broadcast", "reshape", "clamp",
                "floor", "ceil", "round-nearest-afz", "round-nearest-even",
                "is-finite", "sine", "cosine", "concatenate", "pad", "slice",
                "reverse", "rem", "shift-left", "shift-right-logical",
                "shift-right-arithmetic", "reduce", "map", "atan2",
                "stochastic-convert", "real", "imag", "erf"}


def flat_cost_analysis(compiled) -> dict:
    """XLA's flat per-module cost analysis (each ``while`` body once)."""
    return dict(compiled.cost_analysis())


def _elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _type_bytes(type_str: str) -> int:
    return sum(_elems(dims) * _DTYPE_BYTES[dt]
               for dt, dims in _SHAPE_RE.findall(type_str))


def _first_shape(type_str: str) -> Optional[tuple[str, list[int]]]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    dt, dims = m.groups()
    return dt, [int(d) for d in dims.split(",") if d]


@dataclasses.dataclass
class OpInfo:
    name: str
    kind: str
    rtype: str
    operands: list[str]
    attrs: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: list[OpInfo]
    symbols: dict       # name -> result type str
    text: str


def parse_computations(hlo: str) -> tuple[dict[str, Computation],
                                          Optional[str]]:
    comps: dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    for line in hlo.splitlines():
        line = re.sub(r"/\*.*?\*/", "", line)  # strip /*index=N*/ comments
        hdr = _HDR_RE.match(line)
        if hdr and "{" in line and ("->" in line):
            cur = Computation(hdr.group(1), [], {}, "")
            comps[cur.name] = cur
            if line.lstrip().startswith("ENTRY"):
                entry = cur.name
            for pname, ptype in _PARAM_RE.findall(hdr.group("params")):
                cur.symbols[pname] = ptype
            continue
        if cur is None:
            continue
        cur.text += line + "\n"
        m = _OP_RE.match(line)
        if m:
            rest = m.group("rest")
            call_part = rest.split(")", 1)[0]
            operands = re.findall(r"%([\w\.\-]+)", call_part)
            if not operands:  # operands may be printed without '%'
                operands = [t.strip() for t in call_part.split(",")
                            if t.strip() and "=" not in t]
            attrs = rest[len(call_part):]
            op = OpInfo(m.group("name"), m.group("kind"), m.group("rtype"),
                        operands, attrs, line.strip())
            cur.ops.append(op)
            cur.symbols[op.name] = op.rtype
        if line.strip() == "}":
            cur = None
    return comps, entry


def trip_count(cond: Computation) -> int:
    consts: dict[str, int] = {}
    for mm in re.finditer(
            r"%?([\w\.\-]+)\s*=\s*[su]\d+\[\]\s*constant\((-?\d+)\)",
            cond.text):
        consts[mm.group(1)] = int(mm.group(2))
    for op in cond.ops:
        if op.kind != "compare":
            continue
        vals = [consts[n] for n in op.operands if n in consts]
        dm = re.search(r"direction=(\w+)", op.line)
        if vals:
            v = max(vals)
            if dm and dm.group(1) in ("LE", "GE"):
                v += 1
            return max(v, 1)
    if consts:
        return max(max(consts.values()), 1)
    return 1


def _dot_flops(op: OpInfo, symbols: dict) -> float:
    res = _first_shape(op.rtype)
    lhs_t = symbols.get(op.operands[0]) if op.operands else None
    lhs = _first_shape(lhs_t) if lhs_t else None
    if res is None or lhs is None:
        return 0.0
    res_elems = 1
    for d in res[1]:
        res_elems *= d
    cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
    contract = 1
    if cm and cm.group(1):
        for i in cm.group(1).split(","):
            contract *= lhs[1][int(i)]
    return 2.0 * res_elems * contract


def _conv_flops(op: OpInfo, symbols: dict) -> float:
    res = _first_shape(op.rtype)
    ker_t = symbols.get(op.operands[1]) if len(op.operands) > 1 else None
    ker = _first_shape(ker_t) if ker_t else None
    if res is None or ker is None:
        return 0.0
    res_elems = 1
    for d in res[1]:
        res_elems *= d
    k_elems = 1
    for d in ker[1]:
        k_elems *= d
    out_feat = ker[1][-1] if ker[1] else 1
    return 2.0 * res_elems * (k_elems / max(out_feat, 1))


@dataclasses.dataclass
class Totals:
    flops: float = 0.0
    bytes_accessed: float = 0.0      # upper bound (CPU fusion level)
    bytes_fused: float = 0.0         # TPU-fusion-optimistic lower bound
    collective_bytes: float = 0.0
    coll_breakdown: dict = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVES})

    def add(self, other: "Totals", mult: float = 1.0, *,
            bytes_too: bool = True):
        self.flops += other.flops * mult
        if bytes_too:
            self.bytes_accessed += other.bytes_accessed * mult
            self.bytes_fused += other.bytes_fused * mult
        self.collective_bytes += other.collective_bytes * mult
        for k in COLLECTIVES:
            self.coll_breakdown[k] += other.coll_breakdown[k] * mult


def analyse_hlo(hlo: str) -> Totals:
    comps, entry = parse_computations(hlo)
    if entry is None:
        entry = list(comps)[-1]

    memo: dict[str, Totals] = {}

    def visit(name: str) -> Totals:
        if name in memo:
            return memo[name]
        memo[name] = Totals()          # break cycles defensively
        comp = comps.get(name)
        if comp is None:
            return memo[name]
        t = Totals()
        for op in comp.ops:
            base = op.kind.removesuffix("-start").removesuffix("-done")
            if op.kind.endswith("-done"):
                continue
            if base == "dot":
                t.flops += _dot_flops(op, comp.symbols)
            elif base == "convolution":
                t.flops += _conv_flops(op, comp.symbols)
            if base in COLLECTIVES:
                b = sum(_type_bytes(comp.symbols.get(o, ""))
                        for o in op.operands)
                t.collective_bytes += b
                t.coll_breakdown[base] += b
            if base == "dynamic-update-slice":
                # in-place update: traffic = the update slice (read+write),
                # not the full buffer (XLA aliases the big operand)
                upd = op.operands[1] if len(op.operands) > 1 else None
                b = 2 * _type_bytes(comp.symbols.get(upd, "")) if upd else 0
                t.bytes_accessed += b
                t.bytes_fused += b
            elif base == "dynamic-slice":
                t.bytes_accessed += 2 * _type_bytes(op.rtype)
                t.bytes_fused += 2 * _type_bytes(op.rtype)
            elif base not in _SKIP_TRAFFIC:
                b = _type_bytes(op.rtype)
                b += sum(_type_bytes(comp.symbols.get(o, ""))
                         for o in op.operands)
                if "dynamic-update-slice" in op.name or \
                        "dynamic_update_slice" in op.line:
                    # in-place accumulator fusion: the big buffer operand is
                    # aliased with the result; real traffic is the update
                    rbytes = _type_bytes(op.rtype)
                    alias = max((_type_bytes(comp.symbols.get(o, ""))
                                 for o in op.operands), default=0)
                    if alias and abs(alias - rbytes) <= rbytes * 0.01:
                        b -= alias + rbytes
                t.bytes_accessed += b
                if base not in _ELEMENTWISE:
                    t.bytes_fused += b
            if base == "while":
                bm = re.search(r"body=%?([\w\.\-]+)", op.line)
                cm = re.search(r"condition=%?([\w\.\-]+)", op.line)
                # XLA annotates statically-known trip counts directly
                km = re.search(r'known_trip_count[^0-9]*(\d+)', op.line)
                if km:
                    trips = int(km.group(1))
                elif cm and cm.group(1) in comps:
                    trips = trip_count(comps[cm.group(1)])
                else:
                    trips = 1
                if bm:
                    t.add(visit(bm.group(1)), trips)
            elif base in ("fusion", "call", "conditional", "custom-call",
                          "map", "reduce", "reduce-window", "scatter",
                          "select-and-scatter", "sort", "async-start"):
                for cname in re.findall(
                        r"(?:calls|to_apply|branch_computations=\{)"
                        r"=?%?([\w\.\-]+)", op.attrs):
                    sub = visit(cname)
                    # fusion interior traffic is on-chip: flops and
                    # collectives propagate, bytes do not
                    t.add(sub, 1.0, bytes_too=(base in
                                               ("call", "conditional")))
        memo[name] = t
        return t

    return visit(entry)
