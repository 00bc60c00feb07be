"""HLO parsing + roofline terms (§Roofline of EXPERIMENTS.md).

XLA's ``cost_analysis()`` visits while-loop bodies ONCE (verified in
tests), so a scanned L-layer transformer under-reports FLOPs/bytes by
~L x, and it has no per-collective or per-link breakdown at all.  This
module therefore derives all three roofline terms from the optimized
HLO text itself:

  1. computations are split and a *trip multiplier* is propagated from
     ENTRY through while loops (lax.scan bound = the s32 constant in the
     loop condition);
  2. collective wire bytes are computed per op from its RESULT type and
     replica groups (ring-algorithm volumes), multiplied by the trip
     multiplier, and split ICI vs DCN by whether the group crosses pods;
  3. FLOPs are recomputed from dot ops (2 x prod(result) x contracted
     size via a per-computation symbol table) x multiplier; bytes from
     top-level memory-moving ops (fusion/dot/copy/slice/collective);
  4. each instruction's ``op_name`` metadata (``op_names``) names the
     ``jax.named_scope`` it came from (``core/scopes.py``), so each
     collective's ``wire_bytes_per_chip`` -- the bytes one chip sends
     per step -- falls to the phase that issued it.

Conventions (documented in EXPERIMENTS.md §Roofline):
  * all-gather:       (g-1)/g * result_bytes per chip
  * all-reduce:       2*(g-1)/g * result_bytes per chip
  * reduce-scatter:   (g-1)   * result_bytes per chip (= (g-1)/g * input);
    also a fusion calling ``all-reduce-scatter``, the TPU compiler's
    form of a reduce-scatter whose operand it pads
  * all-to-all:       (g-1)/g * result_bytes per chip
  * collective-permute: result_bytes per chip
  * a flat collective spanning P pods is attributed (P-1)/P of its bytes
    to DCN (the minimum that must cross); explicit pod-axis collectives
    (group size == P) are 100% DCN.

Hardware constants: 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s ICI/link
(single-link conservative budget), 6.25 GB/s/chip DCN (assumption,
documented).
"""

from __future__ import annotations

import dataclasses
import re
from collections import deque

import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
DCN_BW = 6.25e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1, "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\(")
# a computation's header; the signature is left out where the module is
# printed before optimisation
_COMP_HDR_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\)\s*->\s*.*)?\{$")
_PARAM_RE = re.compile(r"%?([\w.\-]+):\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\][^,)]*))")
_GROUPS_RE = re.compile(r"replica_groups=\{(.*?)\}\}?")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(.*?)\}\}?")
_CONST_RE = re.compile(r"%?[\w.\-]+ = s32\[\] constant\((\d+)\)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_WHILE_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_MEM_OPS = {"fusion", "dot", "convolution", "copy", "dynamic-slice",
            "dynamic-update-slice", "transpose", "reduce", "broadcast",
            "concatenate", "slice", "pad", "select-and-scatter", "scatter",
            "gather", "iota", "convert", "sort", "custom-call"} | set(_COLLECTIVES)


def _type_bytes(typestr: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(typestr):
        n = 1
        if m.group(2):
            for d in m.group(2).split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(m.group(1), 4)
    return total


def _last_shape_bytes(typestr: str) -> int:
    """Bytes of the last array of an async start's tuple (its result;
    the scalar contexts a collective-permute-start appends are left
    out where it has an array of rank 1 or more)."""
    ms = list(_SHAPE_RE.finditer(typestr))
    if not ms:
        return 0
    ms = [m for m in ms if m.group(2)] or ms
    m = ms[-1]
    n = 1
    if m.group(2):
        for d in m.group(2).split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(m.group(1), 4)


def _shape_dims(typestr: str) -> list[list[int]]:
    out = []
    for m in _SHAPE_RE.finditer(typestr):
        out.append([int(d) for d in m.group(2).split(",")] if m.group(2) else [])
    return out


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    lines: list[str]
    types: dict[str, str]        # op name -> result type string


def _split_computations(hlo_text: str) -> tuple[dict[str, Computation], str]:
    comps: dict[str, Computation] = {}
    entry = ""
    cur: Computation | None = None
    for raw in hlo_text.splitlines():
        st = raw.strip()
        m = _COMP_HDR_RE.match(st)
        if m and st.endswith("{"):
            cur = Computation(m.group(2), bool(m.group(1)), [], {})
            comps[cur.name] = cur
            if m.group(1):
                entry = cur.name
            # parameters declared in the header carry their types
            if "(" in st:
                hdr_params = st[st.index("(") + 1:]
                for pm in _PARAM_RE.finditer(hdr_params):
                    cur.types[pm.group(1)] = pm.group(2)
            continue
        if st == "}":
            cur = None
            continue
        if cur is None:
            continue
        cur.lines.append(st)
        dm = _DEF_RE.match(st)
        if dm:
            cur.types[dm.group(1)] = dm.group(2)
    return comps, entry


def _trip_count(comps: dict[str, Computation], cond_name: str) -> int:
    """lax.scan loop bound: the max s32 constant in the condition comp
    (or the tiny comps it calls)."""
    best = 1
    seen = set()
    stack = [cond_name]
    while stack:
        nm = stack.pop()
        if nm in seen or nm not in comps:
            continue
        seen.add(nm)
        for ln in comps[nm].lines:
            for cm in _CONST_RE.finditer(ln):
                best = max(best, int(cm.group(1)))
            for cm in _CALLS_RE.finditer(ln):
                stack.append(cm.group(1))
    return best


def _multipliers(comps: dict[str, Computation], entry: str) -> dict[str, int]:
    mult = {entry: 1}
    edges: dict[str, list[tuple[str, int]]] = {}
    for name, comp in comps.items():
        out: list[tuple[str, int]] = []
        for ln in comp.lines:
            if "while(" in ln:
                bm = _WHILE_BODY_RE.search(ln)
                cm = _WHILE_COND_RE.search(ln)
                if bm and cm:
                    trips = _trip_count(comps, cm.group(1))
                    out.append((bm.group(1), trips))
                    out.append((cm.group(1), trips))
                    continue
            for cm in _CALLS_RE.finditer(ln):
                out.append((cm.group(1), 1))
        edges[name] = out
    q = deque([entry])
    while q:
        cur = q.popleft()
        for child, k in edges.get(cur, []):
            m = mult[cur] * k
            if mult.get(child, 0) < m:
                mult[child] = m
                q.append(child)
    return mult


def _fused_comps(comps: dict[str, Computation]) -> set[str]:
    """Computations called via fusion/to_apply — their internals do not
    touch HBM; accounted at the call site."""
    fused = set()
    for comp in comps.values():
        for ln in comp.lines:
            if " fusion(" in ln or ln.startswith("fusion("):
                for cm in _CALLS_RE.finditer(ln):
                    fused.add(cm.group(1))
            elif "to_apply=" in ln:
                for cm in re.finditer(r"to_apply=%?([\w.\-]+)", ln):
                    fused.add(cm.group(1))
    return fused


def _fused_reduce_scatter(line: str, comps: dict[str, Computation]):
    """The all-reduce line of a fusion the TPU compiler made of a
    reduce-scatter it pads (it calls ``all-reduce-scatter``: an
    all-reduce and a dynamic-slice, run as one reduce-scatter), else
    None."""
    cm = _CALLS_RE.search(line)
    if cm is None or not cm.group(1).startswith("all-reduce-scatter"):
        return None
    body = comps.get(cm.group(1))
    return next((ln for ln in body.lines if " all-reduce(" in ln),
                None) if body else None


def _parse_groups(line: str, n_devices: int) -> list[list[int]]:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        reshape_dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(reshape_dims))).reshape(reshape_dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.transpose(perm)
        return ids.reshape(g, s).tolist()
    m = _GROUPS_RE.search(line)
    if m:
        groups = []
        for grp in re.findall(r"\{([\d,\s]*)\}", "{" + m.group(1) + "}"):
            if grp.strip():
                groups.append([int(x) for x in grp.replace(" ", "").split(",")])
        if groups:
            return groups
    return [list(range(n_devices))]


def _parse_pairs(line: str) -> list[tuple[int, int]]:
    m = _PAIRS_RE.search(line)
    if not m:
        return []
    return [tuple(int(v) for v in p.split(","))
            for p in re.findall(r"\{(\d+,\d+)\}", "{" + m.group(1) + "}")]


def _op_name(line: str) -> str:
    m = _OP_NAME_RE.search(line)
    return m.group(1) if m else ""


def _args_end(line: str, start: int) -> int:
    """Index of the parenthesis closing the one at ``start``."""
    depth = 0
    for i, ch in enumerate(line[start:], start):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(line) - 1


@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    type: str                    # result type
    operands: list[str]          # instructions of the same computation
    op_name: str                 # "" where the metadata has none
    line: str


def instructions(hlo_text: str) -> dict[str, Instruction]:
    """Every instruction of the module, by name.  Instruction names are
    unique in a module, and the device trace names its ops by them."""
    comps, _ = _split_computations(hlo_text)
    out = {}
    for comp in comps.values():
        for ln in comp.lines:
            dm = _DEF_RE.match(ln)
            if not dm:
                continue
            name, rtype, opcode = dm.groups()
            start = dm.end() - 1
            inner = ln[start + 1:_args_end(ln, start)]
            operands = [t for t in re.findall(r"[\w.\-]+", inner)
                        if t in comp.types and t != name]
            out[name] = Instruction(name, opcode, rtype, operands,
                                    _op_name(ln), ln)
    return out


def op_names(hlo_text: str) -> dict[str, str]:
    """{instruction: the ``op_name`` of its metadata}."""
    return {n: i.op_name for n, i in instructions(hlo_text).items()}


def is_collective(opcode: str) -> bool:
    return opcode.replace("-start", "") in _COLLECTIVES


def collective_key(ins: Instruction, table: dict[str, Instruction]):
    """What a compiler rewrite of a collective keeps: its replica groups
    and the shape of its first operand (a reduce-scatter rewritten as an
    all-reduce and a dynamic-slice keeps both)."""
    groups = tuple(tuple(g) for g in _parse_groups(ins.line, 0))
    src = table.get(ins.operands[0]) if ins.operands else None
    shape = _SHAPE_RE.findall(src.type) if src else []
    return groups, tuple(shape[:1])


@dataclasses.dataclass
class CollectiveOp:
    name: str                    # the HLO instruction
    op_name: str                 # its metadata's op_name (named scopes)
    kind: str
    result_bytes: int
    group_size: int
    crosses_pods: bool
    pods_spanned: int
    trip_mult: int
    wire_bytes_per_chip: float
    dcn_bytes: float
    ici_bytes: float
    line: str


@dataclasses.dataclass
class HloCosts:
    flops_per_chip: float        # loop-corrected dot flops
    bytes_per_chip: float        # loop-corrected HBM-traffic estimate
    xla_flops: float             # raw cost_analysis value (loop-undercounted)
    xla_bytes: float
    collectives: list["CollectiveOp"]


def analyze_module(hlo_text: str, n_devices: int, pod_size: int,
                   xla_flops: float = 0.0, xla_bytes: float = 0.0) -> HloCosts:
    comps, entry = _split_computations(hlo_text)
    mults = _multipliers(comps, entry) if entry else {}
    fused = _fused_comps(comps)

    colls: list[CollectiveOp] = []
    flops = 0.0
    bytes_ = 0.0

    for name, comp in comps.items():
        k_mult = mults.get(name, 0)
        if k_mult == 0 or name in fused:
            continue
        for ln in comp.lines:
            dm = _DEF_RE.match(ln)
            if not dm:
                continue
            opname, rtype, opkind = dm.groups()
            base_kind = opkind.replace("-start", "")
            group_line = ln
            if opkind == "fusion":
                inner = _fused_reduce_scatter(ln, comps)
                if inner is not None:
                    base_kind, group_line = "reduce-scatter", inner
            if base_kind in _COLLECTIVES and not opkind.endswith("-done"):
                rb = (_last_shape_bytes(rtype) if opkind.endswith("-start")
                      else _type_bytes(rtype))
                if base_kind == "collective-permute":
                    pairs = _parse_pairs(ln)
                    crosses = any(s // pod_size != t // pod_size
                                  for s, t in pairs)
                    wire = float(rb) * k_mult
                    colls.append(CollectiveOp(
                        opname, _op_name(ln), base_kind, rb, 2, crosses,
                        2 if crosses else 1,
                        k_mult, wire, wire if crosses else 0.0,
                        0.0 if crosses else wire, ln[:160]))
                else:
                    groups = _parse_groups(group_line, n_devices)
                    g = max(len(grp) for grp in groups)
                    pods = max(len({d // pod_size for d in grp})
                               for grp in groups)
                    crosses = pods > 1
                    if base_kind == "all-gather":
                        wire = (g - 1) / g * rb
                    elif base_kind == "all-reduce":
                        wire = 2 * (g - 1) / g * rb
                    elif base_kind == "reduce-scatter":
                        wire = (g - 1) * rb
                    else:  # all-to-all
                        wire = (g - 1) / g * rb
                    wire *= k_mult
                    if crosses:
                        dcn = wire * (pods - 1) / pods if g > pods else wire
                        ici = wire - dcn
                    else:
                        dcn, ici = 0.0, wire
                    colls.append(CollectiveOp(opname, _op_name(ln), base_kind,
                                              rb, g, crosses, pods, k_mult,
                                              wire, dcn, ici, ln[:160]))
                bytes_ += 2.0 * rb * k_mult
                continue

            if opkind == "dot":
                # flops = 2 * prod(result) * contracted size (via lhs type)
                res_dims = _shape_dims(rtype)
                res_elems = float(np.prod(res_dims[0])) if res_dims else 0.0
                lhs_name = re.search(r"\(\s*%?([\w.\-]+)", ln)
                csize = 1.0
                cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ln)
                if lhs_name and cm and lhs_name.group(1) in comp.types:
                    ldims = _shape_dims(comp.types[lhs_name.group(1)])
                    if ldims and cm.group(1):
                        for d in cm.group(1).split(","):
                            di = int(d)
                            if di < len(ldims[0]):
                                csize *= ldims[0][di]
                flops += 2.0 * res_elems * csize * k_mult
                bytes_ += _op_bytes(ln, rtype, comp) * k_mult
            elif opkind in _MEM_OPS:
                bytes_ += _op_bytes(ln, rtype, comp) * k_mult

    return HloCosts(flops, bytes_, xla_flops, xla_bytes, colls)


def _op_bytes(line: str, rtype: str, comp: Computation) -> float:
    """operands + result bytes, resolving operand types via the symbol
    table (unknown operands contribute 0).  dynamic-(update-)slice is
    in-place inside XLA loops: only the slice moves, not the buffer."""
    dm = _DEF_RE.match(line)
    if dm and dm.group(3) == "dynamic-slice":
        return 2.0 * _type_bytes(rtype)
    if dm and dm.group(3) == "dynamic-update-slice":
        ops = re.findall(r"%([\w.\-]+)", line[line.index("("):])
        if len(ops) >= 2 and ops[1] in comp.types:
            return 2.0 * _type_bytes(comp.types[ops[1]])
        return 0.0
    total = float(_type_bytes(rtype))
    start = line.index("(")
    inner = line[start + 1:_args_end(line, start)]
    for m in re.finditer(r"%([\w.\-]+)", inner):
        t = comp.types.get(m.group(1))
        if t:
            total += _type_bytes(t)
    return total


# ---------------------------------------------------------------------------
# Roofline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Roofline:
    flops_per_chip: float
    bytes_per_chip: float
    ici_bytes: float
    dcn_bytes: float
    compute_s: float
    memory_s: float
    ici_s: float
    dcn_s: float
    collective_s: float          # max(ici, dcn): overlapped budget
    collective_seq_s: float      # ici + dcn: serialized budget
    bottleneck: str
    step_s: float                # max of the three terms
    model_flops: float
    useful_flops_ratio: float
    roofline_fraction: float     # ideal model-flops time / step time

    def to_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(costs: HloCosts, n_chips: int,
                   model_flops_total: float) -> Roofline:
    ici = sum(o.ici_bytes for o in costs.collectives)
    dcn = sum(o.dcn_bytes for o in costs.collectives)
    compute_s = costs.flops_per_chip / PEAK_FLOPS
    memory_s = costs.bytes_per_chip / HBM_BW
    ici_s = ici / ICI_BW
    dcn_s = dcn / DCN_BW
    coll_s = max(ici_s, dcn_s)
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    step = max(terms.values())
    model_per_chip = model_flops_total / max(1, n_chips)
    useful = (model_per_chip / costs.flops_per_chip
              if costs.flops_per_chip else 0.0)
    ideal_s = model_per_chip / PEAK_FLOPS
    frac = ideal_s / step if step > 0 else 0.0
    return Roofline(costs.flops_per_chip, costs.bytes_per_chip, ici, dcn,
                    compute_s, memory_s, ici_s, dcn_s, coll_s, ici_s + dcn_s,
                    bottleneck, step, model_flops_total, useful, frac)


def summarize_ops(coll_ops: list[CollectiveOp]) -> dict:
    by_kind: dict[str, dict] = {}
    for o in coll_ops:
        d = by_kind.setdefault(o.kind, {"count": 0, "wire_bytes": 0.0,
                                        "dcn_bytes": 0.0, "ici_bytes": 0.0})
        d["count"] += 1
        d["wire_bytes"] += o.wire_bytes_per_chip
        d["dcn_bytes"] += o.dcn_bytes
        d["ici_bytes"] += o.ici_bytes
    return by_kind
