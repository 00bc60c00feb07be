"""A run of a tiny cell past the harness's look for a chip: sound, it
is correct; with the timed path broken underneath, or with the control
(the program's model one precision below) in its place, ``correct``
comes out false."""

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
