#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, at the cell's own size.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 11,12,13 --control-seeds 21,22,23

For each of ``--seeds``, the program's first three steps through the
same path a run takes (``harness.Program.first_steps``) against the
reference: the lower readings.  For each of ``--control-seeds``, the
control (the program's own model in the precision below the
configuration's, ``LOWER`` of the configuration's reference module) and
the planted faults the cell can have (half of the batch left out; on
several chips, the gradient exchange left out), each against the
reference: the upper readings;
and the reference put in the program's place in mixed precision
(computed in that lower precision, parameters and Adam in float32), a
path the program does not have.  A step that returns its
state unchanged reads 1 on ``update_norm_gap`` by construction.  One
JSON line per reading on standard output; the benchmark's runs never run
this.  It needs the chip, as a run does.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import compare  # noqa: E402
import harness  # noqa: E402
import tokens  # noqa: E402

def batches(cell, seed):
    t = cell.traffic
    return [tokens.batch(seed, s, cell.rows, t["seq_len"],
                         cell.config["vocab_size"], t["generator"]["zipf_a"])
            for s in range(harness.FIRST_STEPS)]


def program_readings(prog, cell, seed):
    feed = harness.Feed(cell, seed, prog.batch_sharding)
    try:
        state, readings = prog.first_steps(prog.state(seed), feed,
                                           cell.traffic["optimizer"])
        del state
    finally:
        feed.close()
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--dump", default="",
                    help="also write every reading, leaf by leaf, to this "
                         "file as JSON lines")
    args = ap.parse_args(argv)
    dump = open(args.dump, "a") if args.dump else None
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]

    cell = harness.load_cell(harness.REPO, args.workload)
    devices = harness.check_devices(cell.chips)
    harness.enable_compile_cache(harness.REPO)
    opt = cell.traffic["optimizer"]

    def emit(kind, seed, mine, theirs, t0):
        line = {"cell": cell.name, "kind": kind, "seed": seed,
                "seconds": time.perf_counter() - t0,
                **compare.numbers(mine, theirs)}
        print(json.dumps(line), flush=True)
        if dump:
            dump.write(json.dumps(dict(line, readings=mine,
                                       reference=theirs)) + "\n")
            dump.flush()

    reference = harness.resolve(cell.home, cell.config).reference
    ref = reference.Reference(cell.config, opt, devices)
    prog = harness.Program(cell, devices)
    for seed in seeds:
        t0 = time.perf_counter()
        mine = program_readings(prog, cell, seed)
        theirs = ref.run(seed, batches(cell, seed))
        emit("program", seed, mine, theirs, t0)
    del prog
    low = reference.LOWER[cell.config["dtype"]]
    planted = [(f"mixed_{low}", {"compute": low}),
               ("half_batch", {"fault": "half_batch"})]
    if cell.chips > 1:
        planted.append(("no_exchange", {"fault": "no_exchange"}))
    others = [(kind, reference.Reference(cell.config, opt, devices, **kw))
              for kind, kw in planted]
    refs = {}
    for seed in cseeds:
        theirs = refs[seed] = ref.run(seed, batches(cell, seed))
        for kind, other in others:
            t0 = time.perf_counter()
            emit(kind, seed, other.run(seed, batches(cell, seed)), theirs,
                 t0)
    del others
    # the control: the program's own model one precision below
    low_cell = dataclasses.replace(cell, config=dict(cell.config, dtype=low))
    prog = harness.Program(low_cell, devices)
    for seed in cseeds:
        t0 = time.perf_counter()
        emit("control", seed, program_readings(prog, low_cell, seed),
             refs[seed], t0)
    if dump:
        dump.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
