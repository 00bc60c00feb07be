"""Named scopes of the training step, and the phase an HLO op belongs to.

The step wraps its work in ``jax.named_scope``; JAX writes the scope
path into the ``op_name`` metadata of every HLO instruction the work
lowers to, and the compiler keeps it on the fusions it forms.  On a
``value_and_grad`` step the paths read

    jit(step)/jvp(forward)/...                          forward
    jit(step)/transpose(jvp(forward))/...               backward
    .../checkpoint/rematted_computation/...             remat recompute
    jit(step)/shard_map/sync/IntraReduceScatter/...     a schedule-IR step

The schedule-IR executor names each executed step by its IR class
(``IntraReduceScatter``, ``C2CRed``, ...; ``Pack``/``Unpack`` for the
packed buffer), so the vocabulary of the sync is the IR's own.
``phase_of`` maps an ``op_name`` back to one phase, and ``phase_map``
gives every instruction of a compiled module its phase, for a reduction
of the device trace, which names its ops by instruction.
"""

from __future__ import annotations

import re

import jax

from . import schedule as schedule_ir

FORWARD = "forward"
BACKWARD = "backward"
OPTIMIZER = "optimizer"
STEP_METRICS = "step_metrics"
SYNC = "sync"
OTHER = "other"
# the int8 codec's stages, nested under the C2C step that runs them
ENCODE = "encode"
DECODE = "decode"
RECOMPUTE = "rematted_computation"

# every class of the schedule IR; the executor scopes its work by these
IR_STEPS = frozenset(c.__name__ for c in schedule_ir.Step.__subclasses__())

# instructions that do no work of a phase: they neither take one nor
# lend theirs (a weight read by the forward and by the optimizer; a zero
# constant the compiler shares between phases)
_SOURCES = ("parameter", "constant")
_INNER = re.compile(r"^(?:[\w.\-]+\()*([\w.\-]+)\)*$")


def _parts(op_name: str) -> list[tuple[str, str]]:
    """(component, the scope name inside its transform wrappers)."""
    out = []
    for comp in op_name.split("/"):
        m = _INNER.match(comp)
        out.append((comp, m.group(1) if m else comp))
    return out


def scoped(step_cls):
    """The named scope of a schedule-IR step, named by its class."""
    return jax.named_scope(step_cls.__name__)


def is_recompute(op_name: str) -> bool:
    return RECOMPUTE in op_name


def phase_of(op_name: str) -> str:
    """``forward``, ``backward`` (the remat recompute included),
    ``optimizer``, ``step_metrics``, ``sync/<IR step>`` (the innermost
    IR scope under ``sync``), ``sync/other`` or ``other``."""
    parts = _parts(op_name)
    names = [n for _, n in parts]
    for comp, name in parts:
        if name == FORWARD:
            if comp.startswith("transpose(") or is_recompute(op_name):
                return BACKWARD
            return FORWARD
    if SYNC in names:
        inner = [n for n in names[names.index(SYNC) + 1:] if n in IR_STEPS]
        return f"{SYNC}/{inner[-1] if inner else OTHER}"
    for name in (OPTIMIZER, STEP_METRICS):
        if name in names:
            return name
    return OTHER


def phase_map(compiled: str, lowered: str | None = None) -> dict[str, str]:
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
    from repro.launch import hlo_analysis as ha

    table = ha.instructions(compiled)
    phase = {n: phase_of(i.op_name) for n, i in table.items()}
    if lowered:
        before = ha.instructions(lowered)
        keys: dict = {}
        for i in before.values():
            if ha.is_collective(i.opcode):
                keys.setdefault(ha.collective_key(i, before), set()).add(
                    phase_of(i.op_name))
        for n, i in table.items():
            if phase[n] == OTHER and ha.is_collective(i.opcode):
                got = keys.get(ha.collective_key(i, table), set())
                if len(got) == 1:
                    phase[n] = next(iter(got))
    users: dict = {}
    for n, i in table.items():
        for o in i.operands:
            users.setdefault(o, []).append(n)

    def sides(n):
        near = (users.get(n, ()), table[n].operands)
        after = any(ha.is_collective(table[m].opcode) for m in near[1])
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
