"""The step's phases from a trace (``tracereduce.by_phase`` over
``harness.Program.labels``, read by traced runs and ``phases.py``): the
reduction by phase on a synthetic trace, and, on 4 virtual CPU devices
in a child process, every op of the compiled (pod, data) = (2, 2) hier
step carrying the phase, and every sync collective the schedule-IR step,
that issued it."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

import harness
import phases
import tracereduce as tr

HERE = pathlib.Path(__file__).resolve().parent


def op(start, end, name, opcode):
    return tr.Op(start, end, name, opcode)


def synthetic():
    # window [0, 100] ns, 2 steps; device 1 ran ops of no known phase
    d0 = [op(0, 10, "fusion.1", "fusion"),
          op(10, 20, "all-reduce.3", "all-reduce"),
          op(15, 25, "fusion.2", "fusion"),
          op(30, 40, "fusion.4", "fusion"),
          op(90, 120, "fusion.5", "fusion"),       # past the window
          op(0, 40, "while.9", "while")]           # control flow
    a0 = [op(40, 60, "all-gather-start.1", "all-gather-start")]
    d1 = [op(0, 50, "fusion.1", "fusion"),
          op(20, 30, "reduce-scatter.4", "reduce-scatter")]
    return tr.Trace({"/device:TPU:0": d0, "/device:TPU:1": d1},
                    {"/device:TPU:0": a0}, [("traced_window", 0, 100)])


LABELS = {"fusion.1": "forward", "all-reduce.3": "sync/C2CRed",
          "fusion.2": "sync/Pack", "all-gather-start.1": "sync/IntraAllGather",
          "fusion.4": "backward/recompute", "fusion.5": "optimizer",
          "while.9": "backward"}


def test_by_phase_known_intervals():
    out = tr.by_phase(synthetic(), LABELS, "traced_window", steps=2)
    ns = 1e-9 / 2 / 2          # over 2 devices and 2 steps
    p = out["phase_s_per_step"]
    assert p["forward"] == pytest.approx((10 + 50) * ns)
    assert p["sync/C2CRed"] == pytest.approx(10 * ns)
    assert p["sync/Pack"] == pytest.approx(10 * ns)
    # the asynchronous all-gather from its start to its done
    assert p["sync/IntraAllGather"] == pytest.approx(20 * ns)
    # a phase counts for its parent too, as a union: [10,25] + [40,60]
    assert p["sync"] == pytest.approx(35 * ns)
    # the recompute is part of the backward; the while is no op of it
    assert p["backward"] == p["backward/recompute"] == pytest.approx(10 * ns)
    # clipped to the window
    assert p["optimizer"] == pytest.approx(10 * ns)
    assert out["unscoped_s_per_step"] == pytest.approx(10 * ns)
    # the collectives under sync: the all-reduce and the all-gather
    assert out["sync_collective_s_per_step"] == pytest.approx(30 * ns)


def test_no_chip_no_run():
    with pytest.raises(harness.NoChip):
        phases.main(["--workload", "olmo-1b-fp32.seq2k-b2.1chip",
                     "--seed", "1"])


CHILD = r"""
import json, pathlib, sys
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import jax
jax.config.update("jax_num_cpu_devices", 4)
import chipbench_tiny as ct, harness, phases
from repro.core import scopes
from repro.core.schedule import build_schedule
from repro.launch import hlo_analysis as ha

root = ct.make_root(pathlib.Path(sys.argv[1]), "qwen", 4, dtype="float32")
cell = harness.load_cell(root, "tiny.tiny")
prog = harness.Program(cell, jax.devices()[:4])
text = prog.compiled.as_text()
pmap = scopes.phase_map(text, prog.lowered.as_text(dialect="hlo",
                                                   debug_info=True))
labels = prog.labels()
wire = phases.sync_wire_bytes(prog, labels)
# the benchmark's copy of the scope reading against the program's own
table = ha.instructions(text)
program_labels = {}
for n, ph in pmap.items():
    if ph == scopes.BACKWARD and scopes.is_recompute(table[n].op_name):
        ph += "/recompute"
    if ph != scopes.OTHER:
        program_labels[n] = ph
comps, entry = ha._split_computations(text)
fused = ha._fused_comps(comps)
top = [ha._DEF_RE.match(ln).group(1) for c in comps.values()
       if c.name not in fused for ln in c.lines if ha._DEF_RE.match(ln)]
ret = table[[ln for ln in comps[entry].lines
             if ln.startswith("ROOT")][0].split()[1].lstrip("%")]
scalar = lambda n: table[n].type.split("{")[0].endswith("[]")
out = {"schedule": [type(s).__name__ for s in
                    build_schedule("all_reduce", "hier").steps],
       "collectives": {n: [pmap[n], scopes.phase_of(i.op_name)]
                       for n, i in table.items()
                       if ha.is_collective(i.opcode)},
       "dus": sorted({pmap[n] for n, i in table.items()
                      if i.opcode == "dynamic-update-slice"
                      and pmap[n].startswith("sync")}),
       "dots": sorted({pmap[n] for n, i in table.items()
                       if i.opcode in ("dot", "convolution")}),
       "updates": sorted({pmap[n] for n in ret.operands if not scalar(n)}),
       "other": sorted(f"{n} {table[n].opcode}" for n in top
                       if pmap[n] == "other" and not scalar(n)
                       and table[n].opcode not in ("parameter", "constant",
                                                   "tuple")),
       "labels": sorted(set(labels.values())), "wire": wire,
       "same_as_program": labels == program_labels, "n_labels": len(labels)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", CHILD, str(tmp_path_factory.mktemp("four")),
         str(HERE), str(HERE.parents[1] / "src")],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_each_collective_carries_its_schedule_step(four):
    # the compiled step's collectives: the model's tensor-parallel psums
    # (a model axis of 1) in forward and backward, the reported scalars'
    # pmean, and one sync collective for each executed step of the hier
    # schedule, each named by that step
    steps = {f"sync/{n}" for n in four["schedule"]}
    sync = {ph for ph, _ in four["collectives"].values()
            if ph.startswith("sync")}
    assert sync == {"sync/IntraReduceScatter", "sync/C2CRed",
                    "sync/IntraAllGather"}
    assert sync <= steps
    for n, (ph, raw) in four["collectives"].items():
        # on the CPU every collective keeps its own metadata
        assert ph == raw, n
        assert ph in steps | {"forward", "backward", "step_metrics"}, n


def test_every_op_falls_in_its_phase(four):
    assert four["dus"] == ["sync/Pack"]
    assert four["dots"] == ["backward", "forward"]
    assert four["updates"] == ["optimizer"]
    assert four["other"] == []


def test_labels_and_wire_bytes(four):
    assert {"forward", "backward", "backward/recompute", "optimizer",
            "step_metrics", "sync/Pack", "sync/IntraReduceScatter",
            "sync/C2CRed", "sync/IntraAllGather"} <= set(four["labels"])
    assert "other" not in four["labels"]
    # the benchmark's own reading of the scopes gives every instruction
    # the scope the program's phase_map gives it
    assert four["same_as_program"] and four["n_labels"] > 100
    # the packed gradient's RS, pod all-reduce and AG send bytes
    assert four["wire"] > 0
