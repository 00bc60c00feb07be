"""The trace reduction on a synthetic trace with known intervals."""

import pytest

import tracereduce as tr


def op(start, end, name, opcode):
    return tr.Op(start, end, name, opcode)


def synthetic():
    # device 0, window [0, 100] ns, 2 steps
    d0 = [op(0, 10, "fusion.1", "fusion"),
          op(10, 20, "all-reduce.3", "all-reduce"),
          op(15, 25, "fusion.2", "fusion"),
          op(30, 40, "fusion.1", "fusion"),
          op(0, 40, "while.9", "while")]          # control flow: not an op
    a0 = [op(40, 60, "all-gather-start.1", "all-gather-start")]
    # device 1: busy [0, 50], a collective [20, 30] wholly under compute
    d1 = [op(0, 50, "fusion.1", "fusion"),
          op(20, 30, "reduce-scatter.4", "reduce-scatter")]
    spans = [("traced_window", 0, 100), ("dispatch", 24, 31),
             ("batch_wait", 45, 95), ("wait_metrics", 95, 100)]
    return tr.Trace({"/device:TPU:0": d0, "/device:TPU:1": d1},
                    {"/device:TPU:0": a0}, spans)


def test_interval_algebra():
    assert tr.union([(5, 8), (0, 2), (1, 3), (8, 9), (4, 4)]) == [
        (0, 3), (5, 9)]
    assert tr.measure([(0, 3), (5, 9)]) == 7
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) == [
        (0, 2), (4, 8), (22, 25), (26, 30)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.gaps([(10, 20)], 0, 30) == [(0, 10), (20, 30)]


def test_reduce_known_intervals():
    r = tr.reduce(synthetic(), "traced_window", steps=2)
    ns = 1e-9
    # busy: dev0 [0,25] + [30,40] = 35 (the while is not an op);
    # dev1 [0,50] = 50
    assert r["busy_s"] == pytest.approx(42.5 * ns)
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["idle_pct"] == pytest.approx(57.5)
    # collectives: dev0 [10,20] + async [40,60] = 30; dev1 [20,30] = 10
    assert r["collective_s_per_step"] == pytest.approx((30 + 10) / 2 / 2 * ns)
    # exposed: dev0 [10,15] + [40,60] = 25 (the while does not hide it);
    # dev1 0
    assert r["exposed_s_per_step"] == pytest.approx((25 + 0) / 2 / 2 * ns)
    assert r["has_collectives"]
    ops = dict(r["device_ops"])
    assert ops["fusion.1 (fusion)"] == pytest.approx((20 + 50) / 2 * ns)
    assert "while.9 (while)" not in ops
    # idle gaps on the first device: [40,100] under batch_wait, [25,30]
    # under dispatch
    assert r["idle_gaps"][0] == ["batch_wait", pytest.approx(60 * ns)]
    assert r["idle_gaps"][1] == ["dispatch", pytest.approx(5 * ns)]


def test_reduce_finds_nothing():
    t = synthetic()
    assert tr.reduce(tr.Trace({}, {}, t.spans), "traced_window", 2) is None
    assert tr.reduce(tr.Trace(t.ops, {}, []), "traced_window", 2) is None
    one = tr.Trace({"/device:TPU:0": [op(0, 5, "fusion.1", "fusion")]}, {},
                   [("traced_window", 0, 10)])
    assert tr.reduce(one, "traced_window", 1)["has_collectives"] is False


@pytest.mark.parametrize("text,name,opcode", [
    ("%tanh_reduce_fusion = (bf16[4096]{0:T(1024)(128)(2,1)}, "
     "bf16[4096,4096]{1,0:T(8,128)(2,1)}) fusion(bf16[4096,4096]"
     "{1,0:T(8,128)(2,1)S(1)} %copy-done, bf16[4096,4096] %b.1), "
     "kind=kOutput", "tanh_reduce_fusion", "fusion"),
    ("%copy-done = bf16[4096,4096]{1,0:T(8,128)(2,1)S(1)} copy-done((bf16"
     "[4096,4096]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) %copy-start)",
     "copy-done", "copy-done"),
    ("%all-reduce-start.2 = f32[1024]{0} all-reduce-start(f32[1024]{0} "
     "%p), replica_groups={{0,1}}", "all-reduce-start.2",
     "all-reduce-start"),
])
def test_parse_hlo(text, name, opcode):
    assert tr.parse_hlo(text) == (name, opcode)
    assert tr.is_collective(tr.Op(0, 1, name, opcode)) == (
        "all-reduce" in name)


def test_load_cpu_trace_has_no_device(tmp_path):
    # a trace taken on the CPU has no TPU plane: the reduction finds
    # nothing to read rather than a zero
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("traced_window"):
        jax.block_until_ready(jax.jit(lambda x: x @ x)(jnp.ones((64, 64))))
    jax.profiler.stop_trace()
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    t = tr.load(path, ("traced_window",))
    assert [n for n, _, _ in t.spans] == ["traced_window"]
    assert tr.reduce(t, "traced_window", 1) is None
