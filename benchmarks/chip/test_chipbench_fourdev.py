"""The four-chip path on 4 virtual CPU devices, in a child process: the
program's synced gradient on a (pod, data) = (2, 2) mesh equals the
reference's over the same rows, a sound run is correct, and one with
the gradient exchange left out is not."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent

CHILD = r"""
import json, pathlib, sys
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import jax
jax.config.update("jax_num_cpu_devices", 4)
import jax.numpy as jnp
import numpy as np
import chipbench_tiny as ct, harness, reference, tokens

out = {}
root = ct.make_root(pathlib.Path(sys.argv[1]) / "a", "qwen", 4, dtype="float32")
cell = harness.load_cell(root, "tiny.tiny")
devs = jax.devices()[:4]
prog = harness.Program(cell, devs)
out["mesh"] = dict(prog.mesh.shape)
feed = harness.Feed(cell, 11, prog.batch_sharding)
params, opt = prog.state(11)
params, opt, m = prog.step(params, opt, feed.get())
feed.close()
b1 = cell.traffic["optimizer"]["b1"]
synced = [np.asarray(x) / (1 - b1) for x in jax.tree.leaves(opt.mu)]
ref = reference.Reference(cell.config, cell.traffic["optimizer"], devs)
b = tokens.batch(11, 0, cell.rows, cell.traffic["seq_len"],
                 cell.config["vocab_size"], 1.3)
loss, g = ref._grads(ref._init(reference.seed_key(11)),
                     jax.device_put(b["tokens"], ref.rows),
                     jax.device_put(b["labels"], ref.rows))
g = [np.asarray(x) for x in jax.tree.leaves(g)]
norm = float(np.sqrt(sum(float(np.sum(x * x)) for x in g)))
clip = min(1.0, cell.traffic["optimizer"]["grad_clip"] / (norm + 1e-9))
out["loss"] = [float(m["loss"]), float(loss)]
out["grad_err"] = max(float(np.max(np.abs(a - c * clip)) / np.max(np.abs(c * clip)))
                      for a, c in zip(synced, g))
out["sound"] = ct.run(root, seed=12)["correct"]
ct.plant("no_exchange", setattr)
res = ct.run(ct.make_root(pathlib.Path(sys.argv[1]) / "b", "qwen", 4,
                          dtype="float32"), seed=12)
out["no_exchange"] = res["correct"]
out["no_exchange_margin"] = max(c["value"] / c["limit"]
                                for c in res["checks"].values())
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


def test_synced_gradient_matches_reference(four):
    assert four["mesh"] == {"pod": 2, "data": 2, "model": 1}
    assert four["loss"][0] == pytest.approx(four["loss"][1], rel=1e-5)
    assert four["grad_err"] < 1e-4


def test_four_chip_run_correct_and_exchange_fault_caught(four):
    assert four["sound"]
    assert not four["no_exchange"]
    assert four["no_exchange_margin"] > 3
