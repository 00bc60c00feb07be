"""A run of a tiny cell past the harness's look for a chip: sound, it
is correct; with the timed path broken underneath, or with the control
(the program's model one precision below) in its place, ``correct``
comes out false.  A configuration's own reference and FLOP modules are
the ones a run uses."""

import pytest

import chipbench_tiny as ct
import harness


@pytest.mark.parametrize("kind", ["olmo", "qwen"])
def test_sound_run_is_correct(tmp_path, kind):
    res = ct.run(ct.make_root(tmp_path, kind, dtype="float32"), seed=2**33 + 5)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"tokens_per_s", "step_ms_p90",
                                   "peak_hbm_gib", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "control",
                                   "grad_doubled"])
def test_fault_is_not_correct(tmp_path, monkeypatch, fault):
    ct.plant(fault, monkeypatch.setattr)
    res = ct.run(ct.make_root(tmp_path, "qwen", dtype="float32"), seed=9)
    assert not res["correct"], res["checks"]
    # each fault fails some number by a margin, not by a hair
    assert max(c["value"] / c["limit"] for c in res["checks"].values()) > 3


def test_traced_run_reads_host_spans(tmp_path, monkeypatch):
    # on the CPU the trace has no device plane: the trace's metrics are
    # left out, the host spans and the model FLOPs are read
    monkeypatch.setattr(harness.peaks_lib, "peaks",
                        lambda kind: {"bf16_flops_per_s": 1e12})
    res = ct.run(ct.make_root(tmp_path, "olmo", dtype="float32"), seed=4,
                 trace=True)
    assert res["correct"]
    assert set(res["metrics"]) == {"dispatch_ms", "batch_wait_ms", "mfu"}
    assert "breakdown" not in res and "busy_s" not in res["device"]


FAULTY_REFERENCE = """
import reference as _plain
from reference import *  # noqa: F401,F403


class Reference(_plain.Reference):
    def __init__(self, cfg, opt, devices, fault=None, compute="float32"):
        super().__init__(cfg, opt, devices, fault="half_batch",
                         compute=compute)
"""


def test_named_reference_module_is_used(tmp_path):
    # a sound program against a reference that leaves half the batch
    # out: only the named module, not reference.py, can fail the run
    root = ct.make_root(tmp_path, "qwen", dtype="float32",
                        config={"reference": "faulty_reference"})
    (root / "benchmarks" / "chip" / "faulty_reference.py").write_text(
        FAULTY_REFERENCE)
    res = ct.run(root, seed=9)
    assert not res["correct"], res["checks"]
    assert max(c["value"] / c["limit"] for c in res["checks"].values()) > 3


def test_named_flops_module_sets_mfu(tmp_path, monkeypatch):
    root = ct.make_root(tmp_path, "olmo", dtype="float32",
                        config={"flops": "fixed_flops"})
    (root / "benchmarks" / "chip" / "fixed_flops.py").write_text(
        "def train_flops_per_token(cfg, seq_len):\n    return 2.5e9\n")
    monkeypatch.setattr(harness.peaks_lib, "peaks",
                        lambda kind: {"bf16_flops_per_s": 1e12})
    seen = []
    real = harness.metric_context

    def spy(*a, **kw):
        seen.append(real(*a, **kw))
        return seen[-1]

    monkeypatch.setattr(harness, "metric_context", spy)
    res = ct.run(root, seed=6, trace=True)
    assert res["correct"]
    ctx = seen[0]
    assert ctx["flops_per_token"] == 2.5e9
    assert ctx["config"]["flops"] == "fixed_flops"
    assert ctx["traffic"]["seq_len"] == 64 and ctx["phases"] is None
    assert res["metrics"]["mfu"]["value"] == pytest.approx(
        100.0 * 2.5e9 * ctx["window"]["tokens_per_s"] / 1e12)


def test_only_traced_runs_label_the_step(tmp_path, monkeypatch):
    """An untraced run labels nothing; a traced one labels the compiled
    step's instructions by scope, reduces the trace it took by them and
    hands each scope's seconds a step to the readers."""
    root = ct.make_root(tmp_path, "olmo", dtype="float32")

    def refuse(self):
        raise AssertionError("an untraced run labelled the step")

    monkeypatch.setattr(harness.Program, "labels", refuse)
    assert ct.run(root, seed=8)["correct"]
    monkeypatch.undo()

    # the CPU's trace holds no device op: stand in for its reduction
    fake = {"busy_s": 1.0, "window_s": 1.0, "idle_pct": 0.0,
            "collective_s_per_step": 0.0, "exposed_s_per_step": 0.0,
            "has_collectives": False, "device_ops": [], "idle_gaps": []}
    monkeypatch.setattr(harness.tracereduce, "reduce", lambda *a: fake)
    got = []

    def by_phase(trace, labels, window, steps):
        got.append(labels)
        return {"phase_s_per_step": {"forward": 0.003, "backward": 0.006,
                                     "optimizer": 0.002,
                                     "sync/Pack": 0.001}}

    monkeypatch.setattr(harness.tracereduce, "by_phase", by_phase)
    monkeypatch.setattr(harness.peaks_lib, "peaks",
                        lambda kind: {"bf16_flops_per_s": 1e12})
    res = ct.run(ct.make_root(tmp_path / "traced", "olmo", dtype="float32"),
                 seed=8, trace=True)
    assert {"forward", "backward", "backward/recompute", "optimizer",
            "sync/Pack"} <= set(got[0].values())
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["forward_ms"] == pytest.approx(3.0)
    assert m["backward_ms"] == pytest.approx(6.0)
    assert m["optimizer_ms"] == pytest.approx(2.0)
    assert m["sync_pack_ms"] == pytest.approx(1.0)
