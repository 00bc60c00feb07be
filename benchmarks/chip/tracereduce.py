"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: for each
device plane (``/device:TPU:<n>``) the events of its ``XLA Ops`` line
(each an HLO instruction, named by its HLO text) and of its ``Async XLA
Ops`` line (an asynchronous op from its start to its done), and the
host spans whose names the caller gives.  ``reduce`` clips all of it to
one host span (the traced window) and gives, averaged over devices:

* ``busy_s``: the union of the intervals in which an op ran (a control
  flow op such as ``while`` only wraps the ops of its body and is not
  counted itself);
* ``idle_pct``: 100 x (1 - busy / window);
* ``collective_s_per_step``: the union of the collective ops' intervals
  (all-reduce, reduce-scatter, all-gather, collective-permute,
  all-to-all; synchronous, or asynchronous from start to done), per step;
* ``exposed_s_per_step``: the part of that union in which no other op
  (control flow aside) ran on the device, per step;
* ``device_ops``: the ten instructions that took most time, with their
  seconds in the window per device;
* ``idle_gaps``: the ten longest gaps between ops on the first device,
  each named by the host span that overlaps it most (or ``none``).
"""

from __future__ import annotations

import dataclasses
import re

COLLECTIVE = re.compile(r"^(all-reduce|reduce-scatter|all-gather|"
                        r"collective-permute|all-to-all|all-reduce-scatter)"
                        r"(-start|-done)?$")
CONTROL = ("while", "conditional", "call")
_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*.*?\s(?P<op>[a-z][a-z0-9-]*)\(")


@dataclasses.dataclass
class Op:
    start: float       # ns
    end: float
    name: str          # HLO instruction name
    opcode: str


@dataclasses.dataclass
class Trace:
    ops: dict          # device -> [Op] of the XLA Ops line
    async_ops: dict    # device -> [Op] of the Async XLA Ops line
    spans: list        # [(name, start ns, end ns)] of the host


def parse_hlo(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an event named by its HLO text."""
    m = _HLO.match(text)
    if m is None:
        return text.split(" ")[0].lstrip("%"), ""
    return m.group("name"), m.group("op")


def is_collective(op: Op) -> bool:
    base = re.sub(r"\.\d+$", "", op.name)
    return bool(COLLECTIVE.match(op.opcode) or COLLECTIVE.match(base))


def load(path: str, span_names) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, async_ops, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                out = ops if line.name == "XLA Ops" else async_ops
                evs = out.setdefault(plane.name, [])
                for e in line.events:
                    name, op = parse_hlo(e.name)
                    evs.append(Op(e.start_ns, e.start_ns + e.duration_ns,
                                  name, op))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Trace(ops, async_ops, spans)


# ------------------------------------------------------- interval sets --

def union(intervals) -> list:
    """Sorted, disjoint cover of [(start, end)]."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """a minus b, both disjoint and sorted."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged, lo: float, hi: float) -> list:
    """The complement of ``merged`` within [lo, hi]."""
    return subtract([(lo, hi)], merged)


# ------------------------------------------------------------- reduce --

def reduce(trace: Trace, window_span: str, steps: int) -> dict | None:
    """The numbers above, or None when the trace holds no device op or
    no window span."""
    win = [(s, e) for n, s, e in trace.spans if n == window_span]
    devices = sorted(d for d in trace.ops if trace.ops[d])
    if not win or not devices:
        return None
    lo, hi = win[0]
    window = hi - lo
    busy, coll, exposed = [], [], []
    by_op: dict = {}
    for d in devices:
        ops = [o for o in trace.ops[d] if o.opcode not in CONTROL]
        busy.append(measure(clip(union((o.start, o.end) for o in ops),
                                 lo, hi)))
        c = union([(o.start, o.end) for o in ops + trace.async_ops.get(d, [])
                   if is_collective(o)])
        c = clip(c, lo, hi)
        other = clip(union((o.start, o.end) for o in ops
                           if not is_collective(o)), lo, hi)
        coll.append(measure(c))
        exposed.append(measure(subtract(c, other)))
        for o in ops:
            t = min(o.end, hi) - max(o.start, lo)
            if t > 0:
                key = f"{o.name} ({o.opcode})"
                by_op[key] = by_op.get(key, 0.0) + t
    n = len(devices)
    first = union((o.start, o.end) for o in trace.ops[devices[0]]
                  if o.opcode not in CONTROL)
    idle = sorted(gaps(clip(first, lo, hi), lo, hi),
                  key=lambda g: g[1] - g[0], reverse=True)[:10]
    host = [(name, s, e) for name, s, e in trace.spans if name != window_span]

    def cover(g) -> str:
        best, best_t = "none", 0.0
        for name, s, e in host:
            t = min(e, g[1]) - max(s, g[0])
            if t > best_t:
                best, best_t = name, t
        return best

    ns = 1e-9
    return {
        "busy_s": sum(busy) / n * ns, "window_s": window * ns,
        "idle_pct": 100.0 * (1.0 - sum(busy) / n / window),
        "collective_s_per_step": sum(coll) / n / steps * ns,
        "exposed_s_per_step": sum(exposed) / n / steps * ns,
        "has_collectives": any(coll),
        "device_ops": [[k, v / n * ns] for k, v in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[cover(g), (g[1] - g[0]) * ns] for g in idle],
    }
