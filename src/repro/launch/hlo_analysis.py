"""Trip-count-aware analysis of post-SPMD optimized HLO text.

Why this exists: XLA's built-in ``compiled.cost_analysis()`` visits each
while-loop body ONCE, so any program built around lax.scan
(scan-over-layers, flash-attention KV scan, microbatching) under-counts
FLOPs/bytes by the trip count, and it reports no per-collective
breakdown at all. This module re-derives:

  * flops            - 2*M*N*K for every dot, multiplied through nested
                       while trip counts (parsed from loop conditions)
  * mem_bytes        - HBM-traffic proxy: OUTPUT bytes of every
                       materializing top-level op (each buffer written
                       once), x 1.5 for read-back by consumers. pred-dtype
                       buffers (masks) and broadcast/iota outputs are
                       excluded — on TPU those fuse into consumers.
                       CPU-fusion granularity makes this an upper-bound
                       flavored estimate; it is CONSISTENT across
                       configurations, which is what §Perf optimization
                       deltas require.
  * collectives      - wire bytes per op type with ring-algorithm
                       multipliers: all-reduce 2(g-1)/g, all-gather /
                       reduce-scatter / all-to-all (g-1)/g, permute 1

All numbers are PER-DEVICE (the HLO is the per-device SPMD program).
Conditional branches are counted at the max over branches (upper bound).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")
_SKIP_MEM = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "while", "conditional", "call", "after-all", "partition-id",
             "replica-id", "reshape", "broadcast", "iota"}
MEM_READBACK = 1.5
_COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast",
                "all-reduce-start", "all-gather-start",
                "collective-permute-start"}


def shape_bytes(type_str: str) -> float:
    """Bytes of an HLO type expression (handles tuples)."""
    total = 0.0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def shape_dims(type_str: str) -> Optional[List[int]]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclass
class Op:
    name: str
    type_str: str
    opcode: str
    operands: List[str]
    attrs: str
    args: str = ""


@dataclass
class Computation:
    name: str
    ops: List[Op] = field(default_factory=list)
    types: Dict[str, str] = field(default_factory=dict)  # value name -> type


_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\((.*)\)\s*->.*\{\s*$")
_OP_RE = re.compile(
    r"^\s*(ROOT\s+)?%?([\w\.\-]+)\s*=\s*((?:\((?:[^()]|\([^()]*\))*\))|(?:[a-z]\w*\[[\d,]*\](?:\{[^}]*\})?))\s+([\w\-]+)\((.*)$"
)
_PARAM_RE = re.compile(r"%?([\w\.\-]+):\s*((?:\([^()]*\))|(?:[a-z]\w*\[[\d,]*\](?:\{[^}]*\})?))")


_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _logical_lines(text: str):
    """Join wrapped statements: HLO pretty-printing breaks long tuple types
    and operand lists across physical lines; a new statement starts only at
    '%name =', a computation header, ENTRY, or '}'."""
    out: List[str] = []
    for raw in text.splitlines():
        s = _COMMENT_RE.sub("", raw).rstrip()
        if not s.strip():
            continue
        st = s.strip()
        new_stmt = (st.startswith("%") or st.startswith("ROOT ")
                    or st.startswith("ENTRY ") or st.startswith("HloModule")
                    or st == "}")
        if new_stmt or not out:
            out.append(s)
        else:
            out[-1] += " " + st
    return out


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry = None
    for line in _logical_lines(text):
        if cur is None:
            m = _COMP_HDR.match(line.strip())
            if m:
                cur = Computation(m.group(2))
                comps[cur.name] = cur
                if m.group(1):
                    entry = cur.name
                for pm in _PARAM_RE.finditer(m.group(3)):
                    cur.types[pm.group(1)] = pm.group(2)
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        _, name, type_str, opcode, rest = m.groups()
        # operand names: %foo refs inside the first balanced paren group
        depth, i = 1, 0
        while i < len(rest) and depth > 0:
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
            i += 1
        arg_str, attrs = rest[: i - 1], rest[i:]
        operands = re.findall(r"%([\w\.\-]+)", arg_str)
        cur.ops.append(Op(name, type_str, opcode, operands, attrs, arg_str))
        cur.types[name] = type_str
    if entry:
        comps["__entry__"] = comps[entry]
    return comps


def _attr_comp(attrs: str, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w\.\-]+)", attrs)
    return m.group(1) if m else None


def _attr_comps(attrs: str, key: str) -> List[str]:
    m = re.search(key + r"=\{([^}]*)\}", attrs)
    if not m:
        return []
    return re.findall(r"%?([\w\.\-]+)", m.group(1))


def _dims_attr(attrs: str, key: str) -> List[int]:
    m = re.search(key + r"=\{([\d,]*)\}", attrs)
    if not m or not m.group(1):
        return []
    return [int(x) for x in m.group(1).split(",")]


def group_size(attrs: str, default: int) -> int:
    # iota format: replica_groups=[G,S]<=[N]  (last dim = group size)
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+", attrs)
    if m:
        return int(m.group(2))
    # explicit: replica_groups={{0,1,2},{3,4,5}}
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    return default


def trip_count_from_backend_config(attrs: str) -> Optional[int]:
    """XLA records loop trip counts: backend_config={"known_trip_count":
    {"n":"4"},...} — the authoritative source."""
    m = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', attrs)
    return int(m.group(1)) if m else None


def trip_count(comp: Computation) -> Tuple[int, bool]:
    """Fallback heuristic from a loop condition computation: the largest
    integer constant (jax scan/fori compare induction < constant)."""
    best = None
    for op in comp.ops:
        if op.opcode == "constant":
            m = re.search(r"^\s*(-?\d+)\s*$", op.args or "")
            if m:
                v = int(m.group(1))
                best = v if best is None else max(best, v)
        for m in re.finditer(r"constant\((-?\d+)\)", op.args + " " + op.attrs):
            v = int(m.group(1))
            best = v if best is None else max(best, v)
    if best is None or best <= 0:
        return 1, False
    return best, True


@dataclass
class Cost:
    flops: float = 0.0
    mem_bytes: float = 0.0
    coll_wire: float = 0.0
    coll_by_type: Dict[str, float] = field(default_factory=dict)
    mem_by_shape: Dict[str, float] = field(default_factory=dict)
    coll_count: int = 0
    dot_count: int = 0
    unknown_trips: int = 0

    def add(self, other: "Cost", mult: float = 1.0, with_mem: bool = True):
        self.flops += other.flops * mult
        if with_mem:
            self.mem_bytes += other.mem_bytes * mult
            for k, v in other.mem_by_shape.items():
                self.mem_by_shape[k] = self.mem_by_shape.get(k, 0.0) + v * mult
        self.coll_wire += other.coll_wire * mult
        for k, v in other.coll_by_type.items():
            self.coll_by_type[k] = self.coll_by_type.get(k, 0.0) + v * mult
        self.coll_count += int(other.coll_count * mult)
        self.dot_count += int(other.dot_count * mult)
        self.unknown_trips += other.unknown_trips


def _dot_flops(op: Op, comp: Computation) -> float:
    if len(op.operands) < 2:
        return 0.0
    lhs_t = comp.types.get(op.operands[0])
    rhs_t = comp.types.get(op.operands[1])
    if lhs_t is None or rhs_t is None:
        return 0.0
    lhs, rhs = shape_dims(lhs_t), shape_dims(rhs_t)
    if lhs is None or rhs is None:
        return 0.0
    lc = _dims_attr(op.attrs, "lhs_contracting_dims")
    lb = _dims_attr(op.attrs, "lhs_batch_dims")
    rc = _dims_attr(op.attrs, "rhs_contracting_dims")
    rb = _dims_attr(op.attrs, "rhs_batch_dims")
    import numpy as np
    pl = float(np.prod(lhs)) if lhs else 1.0
    contract = 1.0
    for d in rc:
        contract *= rhs[d] if d < len(rhs) else 1
    batch = 1.0
    for d in rb:
        batch *= rhs[d] if d < len(rhs) else 1
    pr = float(np.prod(rhs)) if rhs else 1.0
    n_free_rhs = pr / max(contract * batch, 1.0)
    return 2.0 * pl * n_free_rhs


_WIRE_MULT = {
    "all-reduce": lambda g: 2.0 * (g - 1) / g,
    "all-reduce-start": lambda g: 2.0 * (g - 1) / g,
    "all-gather": lambda g: (g - 1) / g,
    "all-gather-start": lambda g: (g - 1) / g,
    "reduce-scatter": lambda g: (g - 1) / g,
    "all-to-all": lambda g: (g - 1) / g,
    "collective-permute": lambda g: 1.0,
    "collective-permute-start": lambda g: 1.0,
    "collective-broadcast": lambda g: 1.0,
}


def analyze(text: str, n_devices: int) -> dict:
    comps = parse_hlo(text)
    memo: Dict[str, Cost] = {}

    def cost_of(name: str) -> Cost:
        if name in memo:
            return memo[name]
        memo[name] = Cost()  # cycle guard
        comp = comps.get(name)
        if comp is None:
            return memo[name]
        c = Cost()
        for op in comp.ops:
            oc = op.opcode
            if oc == "while":
                body = _attr_comp(op.attrs, "body")
                cond = _attr_comp(op.attrs, "condition")
                tc = trip_count_from_backend_config(op.attrs)
                known = tc is not None
                if not known and cond and cond in comps:
                    tc, known = trip_count(comps[cond])
                tc = tc or 1
                if body:
                    c.add(cost_of(body), mult=tc)
                if not known:
                    c.unknown_trips += 1
                continue
            if oc == "conditional":
                branches = _attr_comps(op.attrs, "branch_computations")
                if not branches:
                    t = _attr_comp(op.attrs, "true_computation")
                    f = _attr_comp(op.attrs, "false_computation")
                    branches = [b for b in (t, f) if b]
                if branches:
                    subs = [cost_of(b) for b in branches]
                    best = max(subs, key=lambda s: s.flops + s.mem_bytes)
                    c.add(best)
                continue
            if oc in ("call", "fusion", "map", "reduce", "reduce-window",
                      "scatter", "sort", "select-and-scatter"):
                sub = _attr_comp(op.attrs, "to_apply") or _attr_comp(
                    op.attrs, "calls")
                if sub:
                    # inner ops of a fusion don't touch HBM: flops only
                    c.add(cost_of(sub), with_mem=False)
            if oc == "dot":
                c.flops += _dot_flops(op, comp)
                c.dot_count += 1
            if oc in _COLLECTIVES:
                g = group_size(op.attrs, n_devices)
                in_bytes = sum(shape_bytes(comp.types.get(o, ""))
                               for o in op.operands)
                base = shape_bytes(op.type_str) if "gather" in oc else in_bytes
                wire = _WIRE_MULT.get(oc, lambda g: 1.0)(max(g, 1)) * base
                c.coll_wire += wire
                c.coll_by_type[oc.replace("-start", "")] = \
                    c.coll_by_type.get(oc.replace("-start", ""), 0.0) + wire
                c.coll_count += 1
            if oc not in _SKIP_MEM and not oc.endswith("-done"):
                if op.type_str.startswith("pred"):
                    continue  # masks fuse into consumers on TPU
                if oc == "dynamic-update-slice" and len(op.operands) >= 2:
                    # in-place cache write: traffic = the UPDATE slice, not
                    # the whole (layer-stacked) buffer the op returns
                    b = MEM_READBACK * shape_bytes(
                        comp.types.get(op.operands[1], ""))
                else:
                    b = MEM_READBACK * shape_bytes(op.type_str)
                c.mem_bytes += b
                m = _SHAPE_RE.search(op.type_str)
                key = m.group(0) if m else "?"
                c.mem_by_shape[key] = c.mem_by_shape.get(key, 0.0) + b
        memo[name] = c
        return c

    entry = cost_of("__entry__")
    top_shapes = dict(sorted(entry.mem_by_shape.items(),
                             key=lambda kv: -kv[1])[:32])
    return {
        "flops": entry.flops,
        "mem_bytes": entry.mem_bytes,
        "collective_wire_bytes": entry.coll_wire,
        "collective_by_type": entry.coll_by_type,
        "mem_by_shape_top": top_shapes,
        "collective_count": entry.coll_count,
        "dot_count": entry.dot_count,
        "unknown_trip_counts": entry.unknown_trips,
    }
