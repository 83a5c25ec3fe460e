"""Post-compile HLO analysis: trip-count-aware FLOP / traffic / collective
accounting + roofline terms.

XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip count,
so ``compiled.cost_analysis()`` badly undercounts scanned layer stacks (we
measured a 4-layer and a 32-layer phi3 reporting identical FLOPs).  This
module re-derives costs from the optimized HLO text instead:

  1. split the module into computations,
  2. build the call graph (while bodies/conditions weighted by the
     ``known_trip_count`` backend config, fusions/calls weight 1),
  3. propagate execution multipliers from ENTRY,
  4. cost every ``dot`` (2 x result_elems x contraction_elems), ``gather``
     and collective op, scaled by its computation's multiplier.

Collective "bytes" are the per-device result-shape bytes — the standard
proxy for link traffic (exact per-link factors like (n-1)/n are applied in
the roofline report, not here).
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^=]*?\)|[\w\[\],{}\s/*]+?)\s+"
    r"([\w\-]+)\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_ARGS_RE = re.compile(r"\(([^)]*)\)")


def raw_cost_analysis(compiled) -> dict:
    """XLA's own cost analysis of ``compiled`` (a dict; empty when the
    backend reports none)."""
    return compiled.cost_analysis() or {}


def _split_args(argstr: str):
    """Split an HLO operand list on top-level commas only — operand tokens
    carry inline shapes like ``f32[64,128]{1,0} %Arg_0.1`` whose dims also
    contain commas."""
    parts, depth, cur = [], 0, []
    for ch in argstr:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur).strip())
    return parts


def _operand_shape(tok: str, shapes: dict):
    """Shape string for one operand token: inline shape if present, else
    symbol-table lookup by name."""
    if "[" in tok:
        return tok
    nm = tok.lstrip("%").split(" ")[-1].lstrip("%")
    return shapes.get(nm)


def _parse_shape(s: str):
    """Return list of (dtype, dims) for every shape literal in s."""
    out = []
    for m in _SHAPE_RE.finditer(s):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        d = [int(x) for x in dims.split(",")] if dims else []
        out.append((dt, d))
    return out


def _shape_bytes(s: str) -> int:
    return sum(_DTYPE_BYTES[dt] * math.prod(d) if d else _DTYPE_BYTES[dt]
               for dt, d in _parse_shape(s))


@dataclass
class HloCost:
    flops: float = 0.0
    dot_bytes: float = 0.0              # dot/gather operand+result traffic
    collective_bytes: float = 0.0
    collective_by_kind: dict = field(default_factory=dict)
    collective_counts: dict = field(default_factory=dict)
    unscaled_collective_bytes: float = 0.0
    # dot_bytes minus S^2 attention intermediates (score/prob slabs inside
    # the KV-block scan).  On TPU those live in VMEM inside the Pallas flash
    # kernel (kernels/flash_attention.py) and never touch HBM; the XLA scan
    # path materializes them only because this container can't lower Pallas.
    dot_bytes_flash: float = 0.0


def _score_like(shape_str: str, mult: float) -> bool:
    """Attention-score-shaped tensor in a high-trip scan body: rank>=3 with
    both trailing dims >= 512 (S x block_k slabs), seen >= 64 times."""
    if mult < 64:
        return False
    for _, dims in _parse_shape(shape_str):
        if len(dims) >= 3 and len(dims) >= 2 and min(dims[-2:]) >= 512 \
                and math.prod(dims) >= (1 << 23):
            return True
    return False


def analyze_hlo(txt: str) -> HloCost:
    # ---- split into computations ----
    # computation headers start at column 0 and end with "{";
    # instruction lines are indented.
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for line in txt.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{") \
                and "(" in line:
            name = line.split("(")[0].strip()
            if name.startswith("ENTRY"):
                name = name[len("ENTRY"):].strip()
                cur = name.lstrip("%")
                entry = cur
            else:
                cur = name.lstrip("%")
            comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    if entry is None:       # fall back: first computation
        entry = next(iter(comps))

    # ---- symbol table: op name -> result shape string ----
    shapes: dict[str, str] = {}
    for lines in comps.values():
        for line in lines:
            m = _DEF_RE.match(line)
            if m:
                shapes[m.group(1)] = m.group(2)

    # ---- call graph with weights ----
    edges: dict[str, list[tuple[str, float]]] = defaultdict(list)
    for cname, lines in comps.items():
        for line in lines:
            trip = 1.0
            tm = _TRIP_RE.search(line)
            if tm:
                trip = float(tm.group(1))
            bm = _BODY_RE.search(line)
            if bm and " while(" in line:
                edges[cname].append((bm.group(1), trip))
                cm = _COND_RE.search(line)
                if cm:
                    edges[cname].append((cm.group(1), trip))
            for cm in _CALLS_RE.finditer(line):
                edges[cname].append((cm.group(1), 1.0))

    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    # propagate in topological-ish order (HLO call graphs are acyclic);
    # iterate to fixpoint (small graphs)
    for _ in range(len(comps) + 2):
        changed = False
        for src, outs in edges.items():
            if mult[src] == 0:
                continue
            acc: dict[str, float] = defaultdict(float)
            for dst, w in outs:
                acc[dst] += mult[src] * w
            for dst, v in acc.items():
                if abs(mult[dst] - v) > 1e-9 and v > mult[dst]:
                    mult[dst] = v
                    changed = True
        if not changed:
            break

    # ---- cost every op, scaled ----
    cost = HloCost()
    for cname, lines in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        for line in lines:
            dm = _DEF_RE.match(line)
            if not dm:
                continue
            name, result_shape, op = dm.groups()
            if op == "dot":
                res = _parse_shape(result_shape)
                if not res:
                    continue
                res_elems = math.prod(res[0][1]) if res[0][1] else 1
                cm = _CONTRACT_RE.search(line)
                contract_elems = 1
                args = _ARGS_RE.search(line[line.index("dot("):])
                operands = _split_args(args.group(1)) if args else []
                lhs_shape = (_operand_shape(operands[0], shapes)
                             if operands else None)
                if cm and lhs_shape:
                    lhs = _parse_shape(lhs_shape)
                    if lhs:
                        dims = lhs[0][1]
                        for di in (int(x) for x in cm.group(1).split(",")
                                   if x):
                            if di < len(dims):
                                contract_elems *= dims[di]
                cost.flops += m * 2.0 * res_elems * contract_elems
                operand_bytes = 0
                flash_operand_bytes = 0
                for a in operands:
                    shp = _operand_shape(a, shapes)
                    if shp is not None:
                        b = _shape_bytes(shp)
                        operand_bytes += b
                        if not _score_like(shp, m):
                            flash_operand_bytes += b
                rb = _shape_bytes(result_shape)
                cost.dot_bytes += m * (rb + operand_bytes)
                cost.dot_bytes_flash += m * (
                    (0 if _score_like(result_shape, m) else rb)
                    + flash_operand_bytes)
            elif op in ("gather", "dynamic-slice"):
                cost.dot_bytes += m * _shape_bytes(result_shape)
                cost.dot_bytes_flash += m * _shape_bytes(result_shape)
            elif op.rstrip("-start").rstrip("-done") in COLLECTIVE_OPS \
                    or any(op == c or op == c + "-start"
                           for c in COLLECTIVE_OPS):
                if op.endswith("-done"):
                    continue
                kind = op.replace("-start", "")
                b = _shape_bytes(result_shape)
                cost.collective_bytes += m * b
                cost.unscaled_collective_bytes += b
                cost.collective_by_kind[kind] = \
                    cost.collective_by_kind.get(kind, 0.0) + m * b
                cost.collective_counts[kind] = \
                    cost.collective_counts.get(kind, 0) + 1
    return cost


@dataclass
class Roofline:
    """Three-term roofline (seconds) for one step on the full mesh."""
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float          # whole-step, all devices
    hlo_bytes: float
    collective_bytes: float   # per-device
    model_flops: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def step_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def roofline_terms(*, per_device_flops: float, per_device_bytes: float,
                   per_device_collective_bytes: float, n_chips: int,
                   model_flops: float, peak_flops: float = 197e12,
                   hbm_bw: float = 819e9, ici_bw: float = 50e9,
                   ici_links: int = 4) -> Roofline:
    """All inputs are per-device (the compiled module is the per-device
    program).  model_flops is the global 6ND number for the step."""
    return Roofline(
        compute_s=per_device_flops / peak_flops,
        memory_s=per_device_bytes / hbm_bw,
        collective_s=per_device_collective_bytes / (ici_links * ici_bw),
        hlo_flops=per_device_flops * n_chips,
        hlo_bytes=per_device_bytes * n_chips,
        collective_bytes=per_device_collective_bytes,
        model_flops=model_flops)
