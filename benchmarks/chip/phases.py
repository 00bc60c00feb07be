#!/usr/bin/env python3
"""Device time of one cell's training step by phase, from a trace.

    python3 benchmarks/chip/phases.py --workload <cell> --seed <n>

Builds the cell's step as a run does (``harness.Program``), but compiles
it afresh, past JAX's persistent cache; drives a few steps, then traces
``trace_steps`` steps (the traffic file's) and gives every device op
the phase of its HLO instruction: the program's named
scopes (``repro.core.scopes``) as ``phase_map`` reads them from the
compiled step, with the remat recompute as ``backward/recompute``.  It
prints the ``[scopes]`` line (device ms a step per phase, a phase's time
also counting for its parent, the unscoped rest and the top ops with
their phases) on standard error and one JSON object on standard output:
the same per phase, the bytes a chip sends per step in the collectives
under ``sync`` (ring volumes, ``launch/hlo_analysis.py``), the union of
those collectives' intervals and the two's ratio in GB/s.

A diagnostic beside the benchmark: ``run.py`` neither calls nor needs it.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402
import tracereduce  # noqa: E402

WINDOW = "traced_window"


class Scoped(harness.Program):
    """The cell's program, with the lowered step kept: the module before
    optimisation names the scope of each collective that the compiler
    rewrites without its metadata."""

    def __init__(self, cell, devices):
        import jax.stages

        compile_ = jax.stages.Lowered.compile
        lowered = []

        def keep(self_, *a, **kw):
            lowered.append(self_)
            return compile_(self_, *a, **kw)

        jax.stages.Lowered.compile = keep
        try:
            super().__init__(cell, devices)
        finally:
            jax.stages.Lowered.compile = compile_
        self.lowered = lowered[-1]


def labels_of(prog: Scoped) -> tuple[dict, float]:
    """({instruction: phase} of the compiled step, the instructions of
    no phase left out; the bytes a chip sends per step in the
    collectives under ``sync``)."""
    from repro.core import scopes
    from repro.launch import hlo_analysis

    text = prog.compiled.as_text()
    pmap = scopes.phase_map(
        text, prog.lowered.as_text(dialect="hlo", debug_info=True))
    table = hlo_analysis.instructions(text)
    labels = {}
    for name, phase in pmap.items():
        if phase == scopes.BACKWARD and scopes.is_recompute(
                table[name].op_name):
            phase += "/recompute"
        if phase != scopes.OTHER:
            labels[name] = phase
    mesh = prog.cell.traffic["mesh"]
    costs = hlo_analysis.analyze_module(text, prog.cell.chips,
                                        mesh["data"] * mesh["model"])
    wire = sum(c.wire_bytes_per_chip for c in costs.collectives
               if labels.get(c.name, "").startswith("sync/"))
    return labels, wire


def by_phase(trace: tracereduce.Trace, labels: dict, steps: int) -> dict:
    """Seconds a step, averaged over the devices, of the union of the
    intervals of each phase's ops (synchronous and asynchronous; control
    flow aside; ``a/b`` counting for ``a`` too), of the ops of no phase,
    and of the collectives under ``sync``, in the traced window."""
    lo, hi = [(s, e) for n, s, e in trace.spans if n == WINDOW][0]
    devices = sorted(d for d in trace.ops if trace.ops[d])

    def seconds(intervals):
        return tracereduce.measure(tracereduce.clip(
            tracereduce.union(intervals), lo, hi))

    phases: dict = {}
    unscoped = sync = 0.0
    for d in devices:
        ops = [o for o in trace.ops[d] + trace.async_ops.get(d, [])
               if o.opcode not in tracereduce.CONTROL]
        spans: dict = {}
        for o in ops:
            parts = labels.get(o.name, "").split("/")
            for k in range(1, len(parts) + 1):
                spans.setdefault("/".join(parts[:k]), []).append(
                    (o.start, o.end))
        for key, iv in spans.items():
            if key:
                phases[key] = phases.get(key, 0.0) + seconds(iv)
            else:
                unscoped += seconds(iv)
        sync += seconds((o.start, o.end) for o in ops
                        if labels.get(o.name, "").startswith("sync/")
                        and tracereduce.is_collective(o))
    per = 1e-9 / len(devices) / steps
    return {"phase_s_per_step": {k: v * per
                                 for k, v in sorted(phases.items())},
            "unscoped_s_per_step": unscoped * per,
            "sync_collective_s_per_step": sync * per}


def traced(prog: Scoped, state, feed: harness.Feed, steps: int):
    """Trace ``steps`` steps of the closed loop; returns the state and the
    loaded trace (None when the profiler wrote none)."""
    import jax

    tdir = tempfile.mkdtemp(prefix="chip-phases-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                state, _ = harness.loop(prog.step, state, feed, steps=steps,
                                        annotate=True)
        finally:
            jax.profiler.stop_trace()
        files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
        if not files:
            return state, None
        return state, tracereduce.load(str(files[-1]),
                                       harness.SPANS + (WINDOW,))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def scopes_text(out: dict, top: list, labels: dict) -> str:
    ms = ", ".join(f"{k} {1e3 * v:.3f}"
                   for k, v in out["phase_s_per_step"].items())
    ops = "; ".join(f"{k} {1e3 * v:.2f} ms {labels.get(k.split(' (')[0], '-')}"
                    for k, v in top)
    return (f"[scopes] ms a step: {ms}; unscoped "
            f"{1e3 * out['unscoped_s_per_step']:.3f}; sync wire "
            f"{out['sync_wire_bytes_per_step']:.6g} B in "
            f"{1e3 * out['sync_collective_s_per_step']:.3f} ms; top ops "
            f"(ms in the window): {ops}")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731

    cell = harness.load_cell(harness.REPO, args.workload)
    devices = harness.check_devices(cell.chips)
    # the persistent cache keys a step without its metadata: a step
    # cached from a program with other scopes, or none, would come back
    # with their op names
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    prog = Scoped(cell, devices)
    t0 = time.perf_counter()
    labels, wire = labels_of(prog)
    map_s = time.perf_counter() - t0
    steps = cell.traffic["trace_steps"]
    feed = harness.Feed(cell, args.seed, prog.batch_sharding)
    try:
        state, _ = harness.loop(prog.step, prog.state(args.seed), feed,
                                steps=3)
        state, trace = traced(prog, state, feed, steps)
        del state
    finally:
        feed.close()
    reduced = trace and tracereduce.reduce(trace, WINDOW, steps)
    if reduced is None:
        log("phases.py: the trace holds no device op")
        return 1
    out = by_phase(trace, labels, steps)
    out["sync_wire_bytes_per_step"] = wire
    out["sync_bus_gbps"] = (wire / out["sync_collective_s_per_step"] / 1e9
                            if out["sync_collective_s_per_step"] else None)
    out.update(busy_s_per_step=reduced["busy_s"] / steps,
               collective_s_per_step=reduced["collective_s_per_step"],
               map_s=map_s, workload=cell.name,
               device={"kind": devices[0].device_kind, "count": len(devices)})
    log(f"[scopes] map of {len(labels)} instructions in {map_s:.2f} s")
    log(scopes_text(out, reduced["device_ops"], labels))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
