"""Multi-device validation: each mdscripts/ file runs in a subprocess
with 8 virtual CPU devices (shared runner: tests/_mdrun.py)."""

import pytest

from _mdrun import run_mdscript as _run


@pytest.fixture(scope="module")
def collectives_out():
    return _run("check_collectives.py")


def test_hetccl_collectives_8dev(collectives_out):
    """c2c primitives + every hierarchical collective vs flat natives."""
    assert "hier_psum[hier_pipelined" in collectives_out


W = ",w=6144"


@pytest.mark.parametrize("case", [
    f"hier_psum[{m}{W}]" for m in (
        "hier,k=1,codec=None", "hier_pipelined,k=3,codec=None",
        "hier,k=1,codec=bf16", "hier_pipelined,k=2,codec=bf16",
        "hier_border_rs,k=1,codec=None")] + [
    f"hier_psum_scatter->all_gather[{m}{W}]" for m in ("hier", "flat")])
def test_row_view_callers_8dev(collectives_out, case):
    """Every caller of ``hom_reduce_scatter`` on a buffer it scatters
    as rows (intra RS, chunk loop, border-RS pod leg, ZeRO-1 and its
    Flat branch) equals the native psum."""
    assert f"OK {case}\n" in collectives_out


@pytest.mark.parametrize("case", [
    f"{c},{dt}" for dt in ("float32", "bfloat16")
    for c in ("qualifying", "not-multiple", "axis-size-1")])
def test_reduce_scatter_values_8dev(collectives_out, case):
    """The row view gives the 1-D ``psum_scatter``'s shard, exactly,
    for a length of whole rows, one that is not, and an axis of size 1."""
    assert f"OK hom_reduce_scatter[{case}]\n" in collectives_out


@pytest.mark.parametrize("case", ["qualifying-rows", "not-multiple",
                                  "axis-size-1"])
def test_reduce_scatter_lowering_8dev(collectives_out, case):
    """A qualifying buffer reaches the collective as (rows, 128); any
    other, and an axis of size 1, lower as the plain 1-D scatter."""
    assert f"OK lowering[{case}]\n" in collectives_out


@pytest.mark.slow
def test_train_comm_modes_8dev():
    """flat/hier/pipelined/zero1/fsdp(+int8) reproduce the single-device
    trajectory for dense, SSD and MoE archs."""
    _run("check_train_modes.py", timeout=1500)


def test_donated_sharded_step_8dev():
    """Two donated sharded steps of the dense smoke model on the
    (2,2,2) mesh match the single-device losses and grad norm."""
    out = _run("check_donated_step.py")
    assert "OK donated hier+int8 matches single device" in out


def test_hlo_analysis_8dev():
    _run("check_hlo_analysis.py")


def test_pipeline_pp_over_pod_8dev():
    """GPipe over the pod axis: loss AND grads equal the single-device
    reference; the stage handoff lowers to a DCN collective-permute."""
    _run("check_pipeline_pp.py")


@pytest.mark.slow
def test_elastic_restart_8dev():
    """Pod-failure recovery: mesh -> single-device -> mesh checkpoint
    resume reproduces the uninterrupted loss trajectory.  End-to-end
    training x3 runs — slow tier."""
    _run("check_elastic.py")


@pytest.mark.slow
def test_chaos_guard_8dev():
    """Chaos engine + collective guard: all five seeded fault classes
    (hang, transient, NaN payload, bit-flip, degraded link) detected
    within their deadlines, attributed to the right link/rank, and the
    committed trajectory recovers bit-for-bit vs the fault-free
    reference; zero false positives on the guarded fault-free matrix."""
    out = _run("check_chaos.py", timeout=1500)
    assert "0 guard events" in out
    assert "bit-for-bit vs the fault-free reference" in out
    assert '"false_positives": 0' in out


@pytest.mark.slow
def test_elastic_replan_8dev():
    """Live elastic re-planning: kill a pod (and confirm a straggler
    shrink), re-plan with PlanCache invalidation, slot-map remap of the
    ZeRO-1 master (packing.pack poisoned -> no re-flatten), resume
    bit-for-bit vs a from-scratch survivor-topology run."""
    out = _run("check_elastic_replan.py", timeout=1500)
    assert "bit-for-bit resume" in out
