"""Phases of the step from HLO op names (core/scopes.py) and the HLO
instruction table they are read from (launch/hlo_analysis.py)."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import scopes
from repro.launch import hlo_analysis as ha

J = "jit(step_body)/shard_map"


@pytest.mark.parametrize("op_name,phase", [
    (f"{J}/jvp(forward)/while/body/dot_general", "forward"),
    (f"{J}/jvp(forward)/psum", "forward"),
    (f"{J}/transpose(jvp(forward))/while/body/dot_general", "backward"),
    (f"{J}/transpose(jvp(forward))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", "backward"),
    (f"{J}/jvp(forward)/checkpoint/rematted_computation/exp", "backward"),
    (f"{J}/sync/IntraReduceScatter/reduce_scatter",
     "sync/IntraReduceScatter"),
    (f"{J}/sync/C2CRed/psum", "sync/C2CRed"),
    (f"{J}/sync/Pack/dynamic_update_slice", "sync/Pack"),
    (f"{J}/sync/Unpack/slice", "sync/Unpack"),
    # nested IR scopes: the innermost wins, the codec stays its step's
    (f"{J}/sync/ChunkLoop/while/body/C2CRed/encode/round", "sync/C2CRed"),
    (f"{J}/sync/ChunkLoop/while/body/dynamic_update_slice",
     "sync/ChunkLoop"),
    (f"{J}/sync/reshape", "sync/other"),
    (f"{J}/optimizer/sqrt", "optimizer"),
    ("step_metrics/add", "step_metrics"),
    (f"{J}/broadcast_in_dim", "other"),
    ("params['layers']['mlp']['w_down']", "other"),
    ("", "other"),
])
def test_phase_of(op_name, phase):
    assert scopes.phase_of(op_name) == phase


def test_recompute_is_backward():
    rem = f"{J}/transpose(jvp(forward))/checkpoint/rematted_computation/dot"
    assert scopes.is_recompute(rem)
    assert not scopes.is_recompute(f"{J}/transpose(jvp(forward))/dot")


def test_ir_steps_are_the_schedule_classes():
    from repro.core import schedule
    assert {"IntraReduceScatter", "C2CRed", "C2CCpy", "IntraAllGather",
            "IntraBcast", "ChunkLoop", "Pack", "Unpack", "Flat"} <= \
        scopes.IR_STEPS
    assert all(hasattr(schedule, n) for n in scopes.IR_STEPS)


# An optimised module after the TPU compiler rewrote a 1-D reduce-scatter
# as an all-reduce and a dynamic-slice with no metadata, and the same
# module before optimisation (printed with its metadata, no signatures).
LONG = "x" * 200
OPTIMISED = f"""HloModule jit_step, is_scheduled=true

%region_0 (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)
}}

ENTRY %main (param.1: f32[8], param.2: f32[4,4]) -> (f32[8], f32[4,4]) {{
  %param.1 = f32[8]{{0}} parameter(0)
  %param.2 = f32[4,4]{{1,0}} parameter(1)
  %pack.1 = f32[8]{{0}} fusion(f32[8]{{0}} %param.1), kind=kLoop, calls=%region_0, backend_config={{"{LONG}":1}}, metadata={{op_name="{J}/sync/Pack/dynamic_update_slice"}}
  %all-reduce.1 = f32[8]{{0}} all-reduce(f32[8]{{0}} %pack.1), channel_id=2, replica_groups={{{{0,1}},{{2,3}}}}, use_global_device_ids=true, to_apply=%region_0, backend_config={{"{LONG}":1}}
  %dynamic-slice.1 = f32[4]{{0}} dynamic-slice(f32[8]{{0}} %all-reduce.1, s32[] %c), dynamic_slice_sizes={{4}}
  %psum.2 = f32[4]{{0}} all-reduce(f32[4]{{0}} %dynamic-slice.1), channel_id=1, replica_groups={{{{0,2}},{{1,3}}}}, use_global_device_ids=true, to_apply=%region_0, backend_config={{"{LONG}":1}}, metadata={{op_name="{J}/sync/C2CRed/psum"}}
  %copy.7 = f32[4,4]{{0,1}} copy(f32[4,4]{{1,0}} %param.2)
  %dot.3 = f32[4,4]{{1,0}} dot(f32[4,4]{{0,1}} %copy.7, f32[4,4]{{1,0}} %param.2), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{J}/jvp(forward)/dot_general"}}
  ROOT %tuple.1 = (f32[4]{{0}}, f32[4,4]{{1,0}}) tuple(%psum.2, %dot.3)
}}
"""
LOWERED = f"""HloModule jit_step

region_0.1 {{
  a = f32[] parameter(0)
  b = f32[] parameter(1)
  ROOT add.1 = f32[] add(a, b)
}}

ENTRY main.2 {{
  Arg_0.1 = f32[8]{{0}} parameter(0)
  dynamic_update_slice.4 = f32[8]{{0}} dynamic-update-slice(Arg_0.1, Arg_0.1, c), metadata={{op_name="{J}/sync/Pack/dynamic_update_slice"}}
  reduce_scatter.5 = f32[4]{{0}} reduce-scatter(dynamic_update_slice.4), channel_id=1, replica_groups={{{{0,1}},{{2,3}}}}, use_global_device_ids=true, dimensions={{0}}, to_apply=region_0.1, metadata={{op_name="{J}/sync/IntraReduceScatter/reduce_scatter"}}
  ROOT psum.5 = f32[4]{{0}} all-reduce(reduce_scatter.5), channel_id=1, replica_groups={{{{0,2}},{{1,3}}}}, use_global_device_ids=true, to_apply=region_0.1, metadata={{op_name="{J}/sync/C2CRed/psum"}}
}}
"""


def test_op_names_reads_past_long_lines():
    names = ha.op_names(OPTIMISED)
    assert names["psum.2"] == f"{J}/sync/C2CRed/psum"
    assert names["all-reduce.1"] == ""
    assert names["dot.3"] == f"{J}/jvp(forward)/dot_general"
    # the lowered module's headers carry no signature
    low = ha.op_names(LOWERED)
    assert low["reduce_scatter.5"] == \
        f"{J}/sync/IntraReduceScatter/reduce_scatter"
    table = ha.instructions(OPTIMISED)
    assert table["dynamic-slice.1"].operands == ["all-reduce.1"]
    assert table["dot.3"].operands == ["copy.7", "param.2"]


def test_collective_names_and_op_names():
    costs = ha.analyze_module(OPTIMISED, 4, pod_size=2)
    by = {c.name: c for c in costs.collectives}
    assert set(by) == {"all-reduce.1", "psum.2"}
    # the op_name sits past the 160 characters the line keeps
    assert len(by["psum.2"].line) == 160
    assert by["psum.2"].op_name == f"{J}/sync/C2CRed/psum"
    assert by["all-reduce.1"].op_name == ""
    assert by["psum.2"].wire_bytes_per_chip == 2 * (2 - 1) / 2 * 16


# The TPU compiler's form of a reduce-scatter whose operand it pads: a
# fusion calling ``all-reduce-scatter`` (an all-reduce and a slice run
# as one reduce-scatter), then a collective-permute that mends the
# shifted shard, whose start tuple ends in scalar contexts.
PADDED_RS = f"""HloModule jit_step, is_scheduled=true

%region_0 (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)
}}

%all-reduce-scatter (input: f32[30,128]) -> f32[16,128] {{
  %input = f32[30,128]{{1,0}} parameter(0)
  %constant.1 = f32[] constant(0)
  %pad.1 = f32[32,128]{{1,0}} pad(%input, %constant.1), padding=0_2x0_0
  %all-reduce.2 = f32[32,128]{{1,0}} all-reduce(%pad.1), channel_id=3, replica_groups={{{{0,1}},{{2,3}}}}, use_global_device_ids=true, to_apply=%region_0
  %c = u32[] constant(0)
  ROOT %dynamic-slice.3 = f32[16,128]{{1,0}} dynamic-slice(%all-reduce.2, %c, %c), dynamic_slice_sizes={{16,128}}
}}

ENTRY %main (param.1: f32[30,128]) -> f32[16,128] {{
  %param.1 = f32[30,128]{{1,0}} parameter(0)
  %fusion.17 = f32[16,128]{{1,0}} fusion(%param.1), kind=kCustom, calls=%all-reduce-scatter, metadata={{op_name="{J}/sync/IntraReduceScatter/reduce_scatter"}}
  %slice.5 = f32[1,128]{{1,0}} slice(%fusion.17), slice={{[15:16], [0:128]}}
  %collective-permute-start = (f32[1,128]{{1,0}}, f32[1,128]{{1,0}}, u32[], u32[]) collective-permute-start(%slice.5), channel_id=4, source_target_pairs={{{{0,1}},{{2,3}}}}
  %collective-permute-done = f32[1,128]{{1,0}} collective-permute-done(%collective-permute-start)
  ROOT %concatenate.1 = f32[16,128]{{1,0}} concatenate(%collective-permute-done, %fusion.17), dimensions={{0}}
}}
"""


def test_padded_reduce_scatter_is_a_reduce_scatter():
    costs = ha.analyze_module(PADDED_RS, 4, pod_size=2)
    by = {c.name: c for c in costs.collectives}
    # the fusion's all-reduce is not counted apart from it
    assert set(by) == {"fusion.17", "collective-permute-start"}
    rs = by["fusion.17"]
    assert (rs.kind, rs.group_size) == ("reduce-scatter", 2)
    assert rs.wire_bytes_per_chip == (2 - 1) * 16 * 128 * 4
    assert rs.op_name == f"{J}/sync/IntraReduceScatter/reduce_scatter"
    # the permute's bytes are its f32[1,128], not the scalar context
    assert by["collective-permute-start"].wire_bytes_per_chip == 128 * 4


def test_phase_map_places_what_the_compiler_made():
    pm = scopes.phase_map(OPTIMISED, LOWERED)
    # the rewritten reduce-scatter: its all-reduce by the lowered
    # collective of the same groups and operand, its slice after it
    assert pm["all-reduce.1"] == "sync/IntraReduceScatter"
    assert pm["dynamic-slice.1"] == "sync/IntraReduceScatter"
    assert pm["psum.2"] == "sync/C2CRed"
    # a layout copy takes its user's phase; parameters take none
    assert pm["copy.7"] == "forward"
    assert pm["param.2"] == "other"
    # without the lowered module the all-reduce only has its neighbours
    assert scopes.phase_map(OPTIMISED)["all-reduce.1"] == "sync/Pack"


def test_value_and_grad_splits_forward_backward_recompute():
    def loss(w, x):
        with jax.named_scope(scopes.FORWARD):
            h = jax.checkpoint(lambda w, x: jnp.tanh(x @ w))(w, x)
            return jnp.sum(h @ w)

    def step(w, x):
        g = jax.grad(loss)(w, x)
        with jax.named_scope(scopes.OPTIMIZER):
            return w - 0.1 * g

    w = jnp.ones((16, 16))
    text = jax.jit(step).lower(w, w).compile().as_text()
    pm = scopes.phase_map(text)
    table = ha.instructions(text)
    assert {pm[n] for n, i in table.items() if i.opcode == "dot"} == {
        "forward", "backward"}
    # the tanh runs twice: in the forward, and recomputed in the backward
    tanh = {pm[n]: scopes.is_recompute(i.op_name)
            for n, i in table.items() if i.opcode == "tanh"}
    assert tanh == {"forward": False, "backward": True}
    assert {pm[n] for n, i in table.items() if i.opcode == "subtract"
            and "/optimizer/" in i.op_name} == {"optimizer"}
