"""The named scope of each instruction of a compiled step: the benchmark's
own reading of the program's spans.

The program wraps the work of its training step in ``jax.named_scope``
(``repro/core/scopes.py``); JAX writes the scope path into the
``op_name`` metadata of every HLO instruction the work lowers to, and
the compiler keeps it on the fusions it forms.  The paths read

    jit(step)/jvp(forward)/...                          forward
    jit(step)/transpose(jvp(forward))/...               backward
    .../checkpoint/rematted_computation/...             remat recompute
    jit(step)/shard_map/sync/IntraReduceScatter/...     a schedule-IR step

and the device trace names its ops by instruction.  ``labels`` gives
each instruction of a compiled module its scope path: ``forward``,
``backward`` or ``backward/recompute``, ``optimizer``, ``step_metrics``,
``sync/<IR step>``.  It is a copy of the program's
``scopes.phase_map`` and the HLO parsing it uses
(``launch/hlo_analysis.py``), kept here so that what the per-layer
metrics read does not change with the program; only the names of the
schedule IR's steps are taken from the program, as the span names they
are.
"""

from __future__ import annotations

import dataclasses
import re

FORWARD = "forward"
BACKWARD = "backward"
OPTIMIZER = "optimizer"
STEP_METRICS = "step_metrics"
SYNC = "sync"
OTHER = "other"
RECOMPUTE = "rematted_computation"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
# instructions that do no work of a phase: they neither take one nor
# lend theirs (a weight read by the forward and by the optimizer; a zero
# constant the compiler shares between phases)
_SOURCES = ("parameter", "constant")
_INNER = re.compile(r"^(?:[\w.\-]+\()*([\w.\-]+)\)*$")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\(")
# a computation's header; the signature is left out where the module is
# printed before optimisation
_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\)\s*->\s*.*)?\{$")
_PARAM = re.compile(r"%?([\w.\-]+):\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\][^,)]*))")
_GROUPS = re.compile(r"replica_groups=\{(.*?)\}\}?")
_GROUPS_IOTA = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')


def ir_steps() -> frozenset:
    """The names of the program's schedule-IR steps: the executor scopes
    the sync's work by them."""
    from repro.core import schedule

    return frozenset(c.__name__ for c in schedule.Step.__subclasses__())


# ------------------------------------------------------------ HLO text --

@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    type: str                    # result type
    operands: list               # instructions of the same computation
    op_name: str                 # "" where the metadata has none
    line: str


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


def instructions(hlo_text: str) -> dict:
    """Every instruction of the module, by name (unique in a module)."""
    comps: list = []             # [(lines, {name: type})]
    cur = None
    for raw in hlo_text.splitlines():
        st = raw.strip()
        m = _HEADER.match(st)
        if m and st.endswith("{"):
            cur = ([], {})
            comps.append(cur)
            # parameters declared in the header carry their types
            if "(" in st:
                for pm in _PARAM.finditer(st[st.index("(") + 1:]):
                    cur[1][pm.group(1)] = pm.group(2)
            continue
        if st == "}":
            cur = None
            continue
        if cur is None:
            continue
        cur[0].append(st)
        dm = _DEF.match(st)
        if dm:
            cur[1][dm.group(1)] = dm.group(2)
    out = {}
    for lines, types in comps:
        for ln in lines:
            dm = _DEF.match(ln)
            if not dm:
                continue
            name, rtype, opcode = dm.groups()
            start = dm.end() - 1
            inner = ln[start + 1:_args_end(ln, start)]
            operands = [t for t in re.findall(r"[\w.\-]+", inner)
                        if t in types and t != name]
            om = _OP_NAME.search(ln)
            out[name] = Instruction(name, opcode, rtype, operands,
                                    om.group(1) if om else "", ln)
    return out


def is_collective(opcode: str) -> bool:
    return opcode.replace("-start", "") in COLLECTIVES


def _groups(line: str) -> list:
    m = _GROUPS_IOTA.search(line)
    if m:
        import numpy as np

        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        return ids.reshape(g, s).tolist()
    m = _GROUPS.search(line)
    if m:
        groups = [[int(x) for x in grp.replace(" ", "").split(",")]
                  for grp in re.findall(r"\{([\d,\s]*)\}", "{" + m.group(1) + "}")
                  if grp.strip()]
        if groups:
            return groups
    return [[]]


def collective_key(ins: Instruction, table: dict):
    """What a compiler rewrite of a collective keeps: its replica groups
    and the shape of its first operand (a reduce-scatter rewritten as an
    all-reduce and a dynamic-slice keeps both)."""
    groups = tuple(tuple(g) for g in _groups(ins.line))
    src = table.get(ins.operands[0]) if ins.operands else None
    shape = _SHAPE.findall(src.type) if src else []
    return groups, tuple(shape[:1])


# --------------------------------------------------------------- scopes --

def is_recompute(op_name: str) -> bool:
    return RECOMPUTE in op_name


def phase_of(op_name: str, steps: frozenset) -> str:
    """``forward``, ``backward`` (the remat recompute included),
    ``optimizer``, ``step_metrics``, ``sync/<IR step>`` (the innermost
    of ``steps`` under ``sync``), ``sync/other`` or ``other``."""
    parts = []
    for comp in op_name.split("/"):
        m = _INNER.match(comp)
        parts.append((comp, m.group(1) if m else comp))
    names = [n for _, n in parts]
    for comp, name in parts:
        if name == FORWARD:
            if comp.startswith("transpose(") or is_recompute(op_name):
                return BACKWARD
            return FORWARD
    if SYNC in names:
        inner = [n for n in names[names.index(SYNC) + 1:] if n in steps]
        return f"{SYNC}/{inner[-1] if inner else OTHER}"
    for name in (OPTIMIZER, STEP_METRICS):
        if name in names:
            return name
    return OTHER


def phase_map(compiled: str, lowered: str | None, steps: frozenset) -> dict:
    """{instruction: phase} of a compiled module's HLO text.

    The compiler makes some instructions that carry no scope: layout
    copies, loop-invariant casts hoisted out of a scan, and, on the
    TPU, a 1-D reduce-scatter rewritten as an all-reduce and a
    dynamic-slice.  A collective outside every scope takes the phase of
    the collectives of ``lowered`` (the module before optimisation,
    printed with its metadata) that have its replica groups and operand
    shape, where they agree on one.  Any other instruction outside every
    scope takes the phase its users agree on (it was made for them: a
    layout copy, a hoisted cast, an accumulator's zeros), else the one
    its operands agree on; one that reads a collective's result looks at
    its operands first, as the slice of a rewritten reduce-scatter
    belongs to it.  Parameters and constants neither take nor lend a
    phase.  What stays ``other`` has neither."""
    table = instructions(compiled)
    phase = {n: phase_of(i.op_name, steps) for n, i in table.items()}
    if lowered:
        before = instructions(lowered)
        keys: dict = {}
        for i in before.values():
            if is_collective(i.opcode):
                keys.setdefault(collective_key(i, before), set()).add(
                    phase_of(i.op_name, steps))
        for n, i in table.items():
            if phase[n] == OTHER and is_collective(i.opcode):
                got = keys.get(collective_key(i, table), set())
                if len(got) == 1:
                    phase[n] = next(iter(got))
    users: dict = {}
    for n, i in table.items():
        for o in i.operands:
            users.setdefault(o, []).append(n)

    def sides(n):
        near = (users.get(n, ()), table[n].operands)
        after = any(is_collective(table[m].opcode) for m in near[1])
        return near[::-1] if after else near

    def agreed(names):
        got = {phase[m] for m in names
               if table[m].opcode not in _SOURCES} - {OTHER}
        return got.pop() if len(got) == 1 else None

    pending = [n for n, i in table.items()
               if phase[n] == OTHER and i.opcode not in _SOURCES]
    # the preferred side settles first, so the order of the text does
    # not decide which side an instruction is read from
    for depth in (1, 2):
        changed = True
        while changed:
            changed = False
            for n in pending:
                if phase[n] != OTHER:
                    continue
                for side in sides(n)[:depth]:
                    got = agreed(side)
                    if got:
                        phase[n] = got
                        changed = True
                        break
    return phase


def labels(compiled: str, lowered: str | None) -> dict:
    """{instruction: scope path} of a compiled step, with the remat
    recompute as ``backward/recompute``; the instructions of no scope
    left out."""
    table = instructions(compiled)
    out = {}
    for name, phase in phase_map(compiled, lowered, ir_steps()).items():
        if phase == BACKWARD and is_recompute(table[name].op_name):
            phase += "/recompute"
        if phase != OTHER:
            out[name] = phase
    return out
