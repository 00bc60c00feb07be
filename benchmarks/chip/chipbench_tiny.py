"""Tiny cells for the benchmark's CPU tests, and the faults the tests
plant in the program underneath a run.

``make_root`` writes a benchmark root holding one cell, ``tiny.tiny``:
the published configuration's shape kinds (olmo-1b's non-parametric
LayerNorm and MHA, or qwen2.5-3b's RMSNorm, GQA and QKV bias) at widths
a test can hold, the traffic mix of the real cells at 2 x 64 tokens a
chip, and the limits the tests hold it to; the benchmark's reference
and FLOP modules beside them, as in a checkout.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]

# The tiny float32 cells' readings on the CPU, where the program's float32
# matmuls are exact to rounding: sound runs over 12 seeds (olmo-like,
# qwen-like, qwen-like on 4 devices) read loss_gap <= 1.5e-6,
# grad_norm_gap <= 1.2e-6, update_norm_gap <= 1.7e-5; the control (the
# program's bfloat16 model) over 3 seeds reads loss_gap >= 5.3e-4,
# grad_norm_gap >= 1.3e-3, update_norm_gap >= 0.86; half the batch left
# out reads loss_gap >= 9e-3 and the exchange left out grad_norm_gap >= 0.16.
TINY_LIMITS = {"loss_gap": 2e-4, "grad_norm_gap": 1e-4,
               "update_norm_gap": 1e-4}


def make_root(dst: pathlib.Path, kind: str = "olmo", chips: int = 1,
              dtype: str = "float32", seq: int = 64,
              config: dict | None = None,
              traffic: dict | None = None) -> pathlib.Path:
    """``config``, ``traffic``: keys added to (or replacing those of) the
    tiny configuration and traffic mix."""
    home = dst / "benchmarks" / "chip"
    for d in ("configs", "traffic", "limits"):
        (home / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(HERE / "metrics", home / "metrics", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for f in ("reference.py", "flops.py"):
        shutil.copy(HERE / f, home / f)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    src = "olmo-1b-fp32" if kind == "olmo" else "qwen2.5-3b-fp32"
    cfg = json.loads((HERE / "configs" / f"{src}.json").read_text())
    cfg.update(name="tiny", num_hidden_layers=2, hidden_size=64,
               num_attention_heads=4,
               num_key_value_heads=4 if kind == "olmo" else 2,
               intermediate_size=128, vocab_size=256, dtype=dtype)
    cfg.update(config or {})
    (home / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((HERE / "traffic" / "seq2k-b2.1chip.json").read_text())
    mix.update(seq_len=seq, seqs_per_chip=2, trace_steps=2)
    mix["mesh"] = ({"pod": 2, "data": 2, "model": 1} if chips == 4
                   else {"pod": 1, "data": 1, "model": 1})
    mix.update(traffic or {})
    (home / "traffic" / "tiny.json").write_text(json.dumps(mix))
    (home / "limits" / "tiny.tiny.json").write_text(json.dumps(TINY_LIMITS))
    bench["configs"] = [{"name": "tiny", "source": "x",
                         "file": "benchmarks/chip/configs/tiny.json",
                         "reduced": [], "why": "x"}]
    bench["workloads"] = [{"name": "tiny.tiny", "config": "tiny",
                           "traffic": "tiny", "chips": chips, "why": "x"}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.tiny"]
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def plant(fault: str, patch) -> None:
    """Break the timed path underneath a run.  ``patch(obj, name, value)``
    sets an attribute for the test's duration (pytest's
    ``monkeypatch.setattr``, or plain ``setattr`` in a child process)."""
    import jax
    import jax.numpy as jnp

    import harness

    if fault == "unchanged":
        # the step returns the state it was given
        class Frozen(harness.Program):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                real = self.step

                def step(p, o, b):
                    keep = jax.tree.map(jnp.copy, (p, o))
                    _, _, m = real(p, o, b)
                    return keep[0], keep[1], m

                self.step = step

        patch(harness, "Program", Frozen)
    elif fault == "half_batch":
        # the loss and gradient of the first half of the tokens only
        from repro.train import loss as loss_lib
        real = loss_lib.sharded_xent

        def half(logits, labels, rt, vocab_size, z_loss=0.0):
            B, S = labels.shape
            keep = (jnp.arange(B * S) < (B * S) // 2).reshape(B, S)
            return real(logits, jnp.where(keep, labels, -1), rt, vocab_size,
                        z_loss)

        patch(loss_lib, "sharded_xent", half)
    elif fault == "grad_doubled":
        # the loss as it was, its gradient twice what it is: with the
        # norm clipped the update barely moves, the gradient does
        from repro.train import loss as loss_lib
        real = loss_lib.sharded_xent

        def doubled(*a, **kw):
            l, metrics = real(*a, **kw)
            return 2.0 * l - jax.lax.stop_gradient(l), metrics

        patch(loss_lib, "sharded_xent", doubled)
    elif fault == "no_exchange":
        # the gradient sync across chips left out
        from repro.core import collectives
        patch(collectives, "tree_hier_psum", lambda g, *a, **kw: g)
    elif fault == "control":
        # the program's own model one precision below the configuration's
        class Lower(harness.Program):
            def __init__(self, cell, devices):
                reference = harness.resolve(cell.home, cell.config).reference
                low = reference.LOWER[cell.config["dtype"]]
                super().__init__(dataclasses.replace(
                    cell, config=dict(cell.config, dtype=low)), devices)

        patch(harness, "Program", Lower)
    else:
        raise ValueError(fault)


def run(root: pathlib.Path, seed: int, seconds: float = 0.5,
        trace: bool = False) -> dict:
    """One run of ``tiny.tiny`` past the harness's look for a chip."""
    import time

    import jax

    import harness

    cell = harness.load_cell(root, "tiny.tiny")
    return harness.run(cell, seed, seconds, trace,
                       jax.devices()[:cell.chips], time.perf_counter(),
                       log=lambda m: None)
