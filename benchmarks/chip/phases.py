#!/usr/bin/env python3
"""Device time of one cell's training step by phase, from a trace.

    python3 benchmarks/chip/phases.py --workload <cell> --seed <n>

Builds the cell's step as a run does (``harness.Program``), but compiles
it afresh, past JAX's persistent cache; drives a few steps, then traces
``trace_steps`` steps (the traffic file's) as a traced run does
(``harness.traced_segment``) and gives every device op the named scope
of its HLO instruction (``Program.labels``, reduced by
``tracereduce.by_phase``, as a traced run gives its readers).  It
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
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402
import tracereduce  # noqa: E402


def sync_wire_bytes(prog: harness.Program, labels: dict) -> float:
    """The bytes a chip sends per step in the collectives under ``sync``."""
    from repro.launch import hlo_analysis

    mesh = prog.cell.traffic["mesh"]
    costs = hlo_analysis.analyze_module(prog.compiled.as_text(),
                                        prog.cell.chips,
                                        mesh["data"] * mesh["model"])
    return sum(c.wire_bytes_per_chip for c in costs.collectives
               if labels.get(c.name, "").startswith("sync/"))


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
    prog = harness.Program(cell, devices)
    t0 = time.perf_counter()
    labels = prog.labels()
    wire = sync_wire_bytes(prog, labels)
    map_s = time.perf_counter() - t0
    steps = cell.traffic["trace_steps"]
    feed = harness.Feed(cell, args.seed, prog.batch_sharding)
    try:
        state, _ = harness.loop(prog.step, prog.state(args.seed), feed,
                                steps=3)
        state, trace, reduced = harness.traced_segment(prog, state, feed,
                                                       steps)
        del state
    finally:
        feed.close()
    if reduced is None:
        log("phases.py: the trace holds no device op")
        return 1
    out = tracereduce.by_phase(trace, labels, harness.WINDOW, steps)
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
