"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the virtual device count before ANY other import — jax locks
the device count on first init.  Pinned to the CPU: the 512 devices are
virtual host devices, and the run must never claim an accelerator.
"""

import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, cell_applicable, get_config, get_shape  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_axis_sizes, runtime_for_mesh  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.serve.serve_step import _axes_for_batch, cache_specs  # noqa: E402
from repro.train import TrainConfig, make_train_step  # noqa: E402


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (weak-type
    correct, shardable, no device allocation)."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        out = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
               "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.n_enc_layers:
            out["enc"] = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model),
                                              jnp.float32)
        return out
    if shape.kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.n_enc_layers:
            out["enc"] = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model),
                                              jnp.float32)
        return out
    return {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32)}


def model_flops_for(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS: 6·N·D for training, 2·N·D for inference (N = active
    params, D = tokens processed)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # one token per request


def moe_a2a_bytes(cfg: ModelConfig, shape: ShapeConfig | None,
                  n_chips: int) -> int:
    """Per-rank All2All payload of one MoE layer's dispatch (and
    combine): the capacity-padded expert buckets each rank ships —
    tokens×hidden×dtype (Table 2).  Tokens are sliced 1/world on the ep
    path, padded by the capacity factor; 4 B/elem matches the f32
    gradient-volume convention of ``auto_plan``."""
    from repro.models import moe as moe_lib

    tokens = (shape.global_batch * shape.seq_len
              if shape is not None and shape.kind == "train" else 4096)
    t_loc = max(1, tokens // max(1, n_chips))
    cap = moe_lib._capacity(t_loc, cfg.top_k, cfg.n_experts, 1.25)
    return max(1, cfg.n_experts * cap * cfg.d_model * 4)


def auto_plan(arch: str, *, multi_pod: bool, comm_mode: str = "hier",
              allow_int8: bool = False, shape_name: str | None = None,
              skew: str = "none", packed: bool = True,
              border_scarce: bool = False,
              plan_cache_path: str | None = None):
    """--plan auto: run the cost-model planner for this cell's
    production topology and gradient volume; returns
    (CommPlan, chosen Candidate, a2a CommPlan | None, cache stats dict).

    Planning goes through a ``core.plan_cache.PlanCache``: the
    process-wide default, or — with ``plan_cache_path`` — a disk-backed
    one, which is what lets hillclimb's dryrun *subprocesses* share
    plans across iterations (same topology fingerprint + knobs → one
    cached search).  The returned stats dict (hits/misses/entries)
    lands in the result JSON for the hillclimb report to aggregate.

    The ZeRO-1 gradient sync rides reduce_scatter (no end AllGather in
    the synced step), so its plan is priced on that collective.  Lossy
    int8 wire compression must be opted into explicitly (mirrors
    train.py) — otherwise the auto schedule could "beat" hand configs
    by adopting a codec the baselines were not allowed to use.
    try_balanced is off: a balanced-subgroup topology is advisory (the
    jax mesh cannot subdivide pods), so executable plans price the
    mesh as it will actually run.

    With a training ``shape_name`` the gradient volume is split into
    readiness-ordered layer buckets and the plan is priced against the
    backward-compute timeline (``backward_compute_s``), so it optimizes
    *exposed* comm time and may recommend ``hier_overlap``
    (``plan.recommended_mode()``); without a shape the single-bucket
    sequential plan of earlier revisions is returned unchanged.

    ``skew='auto'`` (training shapes only) runs the joint skew + comm
    optimizer (core/skew.py; DESIGN.md §10) instead of a bare comm
    plan: the returned plan carries the uneven microbatch split, the
    per-cluster compute times, and the per-pod gradient weights the
    lowered step executes (``CommPlan.cluster_weights``).

    MoE architectures additionally get an **All2All plan**: the
    per-MoE-layer dispatch volume (``moe_a2a_bytes``) is planned as one
    bucket per MoE layer over the same topology, enumerating the a2a
    schedule family (flat / flat_a2a / hier_a2a) — its
    ``recommended_mode()`` is what ``models/moe.py`` runs
    (``Runtime.moe_a2a_mode``).  ``border_scarce`` swaps the production
    topology for ``topology.tpu_multipod_scarce`` (one scale-up domain
    per pod, few DCN uplinks) — the regime where ``hier_a2a`` wins.
    """
    from repro.core import cost_model, overlap, planner, topology
    from repro.core import skew as skew_lib
    from repro.launch.mesh import PRODUCTION_MULTI_SHAPE

    n_pods, _, tp_size = PRODUCTION_MULTI_SHAPE
    if not multi_pod:
        n_pods = 1
    chips_per_pod = (
        PRODUCTION_MULTI_SHAPE[1] * PRODUCTION_MULTI_SHAPE[2])
    topo = (topology.tpu_multipod_scarce(n_pods, chips_per_pod)
            if border_scarce else
            topology.tpu_multipod(n_pods, chips_per_pod))
    cfg = get_config(arch)
    grad_bytes = max(1, cfg.param_count() * 4 // tp_size)
    pc = (planner.PlanCache(path=plan_cache_path) if plan_cache_path
          else planner.default_plan_cache())
    plan_kw = dict(
        cache=pc,
        coll="reduce_scatter" if comm_mode == "hier_zero1" else "all_reduce",
        pod_axis="pod" if multi_pod else None, intra_axis="data",
        compressions=(None, "bf16", "int8") if allow_int8 else (None, "bf16"),
        flat_mechanism="native", try_balanced=False,
        # candidates are priced for the data path that will execute:
        # Pack/Unpack steps when packed (DESIGN.md §11), legacy re-pads
        # free when --no-packed — so the A/B axis compares the same
        # plan under both executors.  The leaf-count estimate (embed +
        # final norm + lm_head + ~12 tensors per layer: qkvo, mlp,
        # norms) arms the planner's per-leaf fallback; lower_cell reads
        # plan.data_path and drops Pack/Unpack when packing loses.
        packed=packed,
        n_leaves=4 + 12 * max(1, cfg.n_layers))
    # structural modes (fsdp / hier_zero1) execute a monolithic sync, so
    # their plan must be priced at that granularity
    sizes, backward_s, train_shape = [grad_bytes], None, None
    if shape_name is not None:
        shape = get_shape(shape_name)
        if shape.kind == "train":
            train_shape = shape
            if comm_mode not in ("fsdp", "hier_zero1"):
                backward_s = cost_model.backward_compute_time(
                    topo, model_flops_for(cfg, shape))
                sizes = overlap.bucket_sizes_for_volume(grad_bytes,
                                                        cfg.n_layers)
    sim_cache: dict = {}
    skew_split = skew_comp = None
    if skew == "auto" and train_shape is not None:
        sp = skew_lib.optimize(
            topo, model_flops_for(cfg, train_shape), sizes,
            total_microbatches=max(topo.n_clusters,
                                   train_shape.global_batch),
            # structural modes execute one monolithic sequential sync —
            # no backward window to hide behind, so score sequentially
            backward_frac=(0.0 if comm_mode in ("fsdp", "hier_zero1")
                           else 2.0 / 3.0),
            _sim_cache=sim_cache, **plan_kw)
        skew_split, skew_comp = sp.split, sp.compute_s
        plan = sp.plan
    else:
        plan = planner.plan(topo, sizes, backward_compute_s=backward_s,
                            _sim_cache=sim_cache, **plan_kw)
    if plan.overlap is not None and plan.recommended_mode() != "hier_overlap":
        # overlap doesn't win -> execution is one monolithic collective;
        # re-plan at that granularity so config_for resolves a schedule
        # tuned for the payload that actually crosses the wire
        plan = planner.plan(topo, [grad_bytes], skew=skew_split,
                            skew_compute_s=skew_comp,
                            _sim_cache=sim_cache, **plan_kw)
    big = max(plan.buckets, key=lambda b: b.nbytes)
    a2a_plan = None
    if cfg.n_experts:
        a2a_bytes = moe_a2a_bytes(cfg, train_shape,
                                  n_pods * chips_per_pod)
        a2a_plan = planner.plan(
            topo, [a2a_bytes] * max(1, cfg.n_layers),
            coll="all_to_all",
            pod_axis="pod" if multi_pod else None, intra_axis="data",
            compressions=(None, "bf16"), flat_mechanism="native",
            try_balanced=False, cache=pc, _sim_cache=sim_cache)
    return plan, big.candidate, a2a_plan, pc.stats()


def _dryrun_topology(multi_pod: bool, border_scarce: bool):
    from repro.core import topology
    from repro.launch.mesh import PRODUCTION_MULTI_SHAPE

    n_pods = PRODUCTION_MULTI_SHAPE[0] if multi_pod else 1
    chips_per_pod = PRODUCTION_MULTI_SHAPE[1] * PRODUCTION_MULTI_SHAPE[2]
    return (topology.tpu_multipod_scarce(n_pods, chips_per_pod)
            if border_scarce else
            topology.tpu_multipod(n_pods, chips_per_pod))


def guard_section(plan, *, mode: str, chunks: int,
                  compression: str | None, n_chips: int):
    """--guard: the collective guard's pre-launch view of this cell —
    the schedule digest every rank must agree on (desync detector) and
    the comm deadline the guard would arm from the cost model's
    prediction.  A dry run lowers one process, so all ranks digest
    identically; the chaos harness perturbs one digest to prove the
    detector fires."""
    from repro.core.schedule import STRUCTURAL_MODES, build_schedule
    from repro.runtime import guard as guard_lib

    if plan is not None:
        digest = guard_lib.schedule_digest(plan)
        predicted = plan.predicted_step_s
    else:
        sched = build_schedule("all_reduce",
                               STRUCTURAL_MODES.get(mode, mode),
                               chunks, compression)
        digest = guard_lib.schedule_digest(sched)
        predicted = None
    gcfg = guard_lib.GuardConfig()
    ok, _, outliers = guard_lib.digest_agreement(
        {r: digest for r in range(max(1, n_chips))})
    return {"schedule_digest": digest, "ranks": int(max(1, n_chips)),
            "agreement": bool(ok), "outliers": list(outliers),
            "deadline_margin": gcfg.deadline_margin,
            "deadline_s": (None if predicted is None else
                           max(gcfg.min_deadline_s,
                               gcfg.deadline_margin * predicted))}


def chaos_section(seed: int, arch: str, *, multi_pod: bool,
                  border_scarce: bool, plan, mode: str, chunks: int,
                  compression: str | None, n_steps: int = 32):
    """--chaos: the seeded fault plan this cell would face, plus the
    degraded-fabric pricing — the gradient sync simulated on the
    nominal topology vs. on the fault plan's worst active link
    degradation (``simulate_schedule(link_scale=...)``), which is the
    slowdown the guard's link-health EWMA must detect and the elastic
    re-plan must price around."""
    from repro.configs import get_config
    from repro.core.schedule import STRUCTURAL_MODES, build_schedule
    from repro.core.transport_sim import simulate_schedule
    from repro.launch.mesh import PRODUCTION_MULTI_SHAPE
    from repro.runtime.faults import FaultPlan

    topo = _dryrun_topology(multi_pod, border_scarce)
    fplan = FaultPlan.generate(seed, n_steps,
                               n_clusters=topo.n_clusters,
                               n_ranks=topo.n_ranks)
    if plan is not None:
        b = max(plan.buckets, key=lambda x: x.nbytes)
        sched_mode, nch, comp = (b.candidate.mode, b.candidate.n_chunks,
                                 b.candidate.compression)
        nbytes = b.nbytes
    else:
        sched_mode, nch, comp = STRUCTURAL_MODES.get(mode, mode), chunks, \
            compression
        nbytes = max(1, get_config(arch).param_count() * 4
                     // PRODUCTION_MULTI_SHAPE[2])
    sched = build_schedule("all_reduce", sched_mode, nch, comp)
    # worst concurrent degradation over the plan's timeline
    worst: dict[int, float] = {}
    for e in fplan.events:
        if e.kind == "degraded_link":
            for ci, s in fplan.link_scale(e.step).items():
                worst[ci] = min(worst.get(ci, 1.0), s)
    nominal_s = simulate_schedule(sched, topo, nbytes, level="cluster")
    degraded_s = (simulate_schedule(sched, topo, nbytes, level="cluster",
                                    link_scale=worst)
                  if worst else nominal_s)
    return {"seed": int(seed), "n_steps": int(n_steps),
            "events": fplan.summary()["events"],
            "schedule": {"mode": sched_mode, "n_chunks": nch,
                         "compression": comp, "nbytes": int(nbytes)},
            "degraded_links": {str(ci): round(1.0 / s, 3)
                               for ci, s in sorted(worst.items())},
            "nominal_sync_s": nominal_s,
            "degraded_sync_s": degraded_s,
            "slowdown": (degraded_s / nominal_s if nominal_s > 0
                         else None)}


def elastic_replan_report(arch: str, *, multi_pod: bool,
                          comm_mode: str = "hier",
                          border_scarce: bool = False,
                          plan_cache_path: str | None = None):
    """--elastic: simulate a topology loss against this cell's
    production topology and run the detect -> re-plan transition
    (``runtime.elastic.ElasticController``).  Multi-pod cells lose
    their last pod (``drop_cluster``); single-pod cells confirm a
    persistent straggler and evict half the hosts
    (``shrink_cluster``).  Returns the ``ReplanReport`` — the result
    JSON carries it under ``"replan"`` with the plan-cache
    invalidation observable in ``"plan_cache"`` stats."""
    from repro.core import planner, topology
    from repro.launch.mesh import PRODUCTION_MULTI_SHAPE
    from repro.runtime.elastic import ElasticConfig, ElasticController

    n_pods, _, tp_size = PRODUCTION_MULTI_SHAPE
    if not multi_pod:
        n_pods = 1
    chips_per_pod = PRODUCTION_MULTI_SHAPE[1] * PRODUCTION_MULTI_SHAPE[2]
    topo = (topology.tpu_multipod_scarce(n_pods, chips_per_pod)
            if border_scarce else
            topology.tpu_multipod(n_pods, chips_per_pod))
    cfg = get_config(arch)
    grad_bytes = max(1, cfg.param_count() * 4 // tp_size)
    pc = (planner.PlanCache(path=plan_cache_path) if plan_cache_path
          else planner.default_plan_cache())
    plan_kw = dict(
        coll="reduce_scatter" if comm_mode == "hier_zero1" else "all_reduce",
        pod_axis="pod" if multi_pod else None, intra_axis="data",
        compressions=(None, "bf16"), flat_mechanism="native",
        try_balanced=False)
    # make sure the doomed fingerprint has a cache line to invalidate
    planner.plan(topo, [grad_bytes], cache=pc, **plan_kw)
    ctl = ElasticController(
        topo, [grad_bytes], plan_cache=pc,
        config=ElasticConfig(
            on_straggler=lambda t: t.shrink_cluster(
                0, max(1, t.clusters[0].n_nodes // 2))),
        plan_kw=plan_kw)
    if topo.n_clusters > 1:
        rep = ctl.report_pod_failure(0, topo.n_clusters - 1)
    else:
        rep = None
        for s in range(ctl.cfg.straggler_patience):
            rep = ctl.observe_step(s, slow=True)
        assert rep is not None
    # a dry run lowers but never steps, so nothing is resharded
    return ctl.resumed(rep.step_detected, remap_path="none (dry run)")


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               comm_mode: str = "fsdp", sp: bool = False,
               use_pallas: bool = False, n_chunks: int = 4,
               compression: str | None = None,
               capacity_factor: float = 1.25,
               remat_policy: str = "none", plan=None,
               packed: bool = True, moe_a2a_mode: str = "flat"):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = mesh_axis_sizes(mesh)
    n_chips = int(jnp.prod(jnp.asarray(list(sizes.values()))))
    pod_size = n_chips // sizes.get("pod", 1)

    is_train = shape.kind == "train"
    fsdp = is_train and comm_mode == "fsdp"
    rt = runtime_for_mesh(mesh, fsdp=fsdp, sp=sp, use_pallas=use_pallas,
                          remat_policy=remat_policy,
                          moe_capacity_factor=capacity_factor,
                          moe_a2a_mode=moe_a2a_mode,
                          # skew-aware per-cluster expert capacity rides
                          # the same weights as the gradient sync
                          moe_cluster_weights=(plan.cluster_weights
                                               if plan is not None else None))
    model = Model(cfg, rt)
    if fsdp:
        model = model.with_fsdp(sizes["data"])

    pshape = jax.eval_shape(model.init, jax.random.key(0))
    model.prepare(pshape)
    ins = input_specs(cfg, shape)

    t0 = time.time()
    if is_train:
        tcfg = TrainConfig(comm_mode=comm_mode, n_chunks=n_chunks,
                           dcn_compression=compression, plan=plan,
                           packed=packed,
                           # the fsdp sync path reads tcfg.cluster_weights
                           # directly, so the plan's weights must be
                           # mirrored here for the lowered HLO to run
                           # the weighted reduction
                           cluster_weights=(plan.cluster_weights
                                            if plan is not None else None))
        build, _ = make_train_step(model, tcfg, mesh=mesh, donate=False)
        step, _ = build(pshape)
        if tcfg.comm_mode == "hier_zero1":
            from repro.runtime import elastic as elastic_lib
            from repro.train import optimizer as opt_lib
            # the flat master is built from LOCAL (TP-sharded) leaves per
            # model column, scattered over data: global dim = local shard
            # x (data x model).  The master layout is the packed
            # per-wire-dtype one (collectives._zero1_layout), so the
            # padded size comes from the same planner the step executes
            # (host-side twin: elastic.zero1_master_layout, shared with
            # the elastic remap path).
            isize, tpsize = sizes["data"], sizes.get("model", 1)
            specs = model.param_specs(pshape)
            layout = elastic_lib.zero1_master_layout(pshape, specs, sizes,
                                                     intra_axis="data")
            padded_local = layout.padded_total
            shard_n = padded_local // isize
            gdim = shard_n * isize * tpsize
            shard = jax.ShapeDtypeStruct((gdim,), jnp.float32)
            opt_shape = opt_lib.ZeroState(shard, shard, shard,
                                          jax.ShapeDtypeStruct((), jnp.int32))
        else:
            from repro.train import optimizer as opt_lib
            opt_shape = jax.eval_shape(opt_lib.adam_init, pshape)
        lowered = step.lower(pshape, opt_shape, ins)
    else:
        from repro.serve.serve_step import make_serve_steps
        prefill, decode, caches_shape = make_serve_steps(
            model, mesh, shape.global_batch, shape.seq_len)
        if shape.kind == "prefill":
            args = (pshape, ins["tokens"]) + ((ins["enc"],) if "enc" in ins else ())
            lowered = prefill.lower(*args)
        else:
            lowered = decode.lower(pshape, ins["token"], caches_shape)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    hlo = compiled.as_text()
    costs = hlo_analysis.analyze_module(
        hlo, n_chips, pod_size,
        xla_flops=float(ca.get("flops", 0.0)),
        xla_bytes=float(ca.get("bytes accessed", 0.0)))
    mflops = model_flops_for(cfg, shape)
    roof = hlo_analysis.roofline_terms(costs, n_chips, mflops)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "comm_mode": comm_mode, "sp": sp, "status": "ok",
        "remat_policy": remat_policy, "compression": compression,
        "capacity_factor": capacity_factor, "use_pallas": use_pallas,
        "n_chunks": n_chunks,
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            # memory_analysis reports PER-DEVICE byte counts
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_per_device_gib": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
                / 2**30, 3),
        },
        "xla_cost": {"flops": float(ca.get("flops", 0.0)),
                     "bytes": float(ca.get("bytes accessed", 0.0))},
        "roofline": roof.to_dict(),
        "collectives": hlo_analysis.summarize_ops(costs.collectives),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    if cfg.n_experts:
        result["moe_a2a_mode"] = moe_a2a_mode
    if plan is not None:
        result["plan"] = plan.summary()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--mode", default=None,
                    choices=["flat", "hier", "hier_pipelined",
                             "hier_border_rs", "hier_overlap",
                             "hier_zero1", "fsdp"])
    ap.add_argument("--plan", default="manual", choices=["manual", "auto"],
                    help="auto: core.planner picks mode/chunks/compression "
                         "from the cost model instead of the --mode flags")
    ap.add_argument("--skew", default="none", choices=["none", "auto"],
                    help="auto (requires --plan auto, train shapes): "
                         "core.skew jointly optimizes the uneven per-pod "
                         "batch split with the comm plan; the lowered step "
                         "runs the weighted gradient sync (DESIGN.md §10)")
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--compression", default=None,
                    choices=[None, "bf16", "int8"])
    ap.add_argument("--capacity-factor", type=float, default=1.25)
    ap.add_argument("--remat-policy", default="none",
                    choices=["none", "save_collectives"])
    ap.add_argument("--no-packed", action="store_true",
                    help="disable the zero-copy packed gradient data "
                         "path (legacy per-step re-flatten; A/B axis)")
    ap.add_argument("--border-scarce", action="store_true",
                    help="price --plan auto against the border-scarce "
                         "multipod topology (one scale-up domain per "
                         "pod, few DCN uplinks) instead of the "
                         "every-chip-a-border-rank default")
    ap.add_argument("--plan-cache", default=None, metavar="PATH",
                    help="disk-backed plan cache shared across dryrun "
                         "processes (hillclimb passes one file so "
                         "repeated --plan auto invocations hit instead "
                         "of re-searching); stats land in the result "
                         "JSON under 'plan_cache'")
    ap.add_argument("--elastic", action="store_true",
                    help="after lowering, simulate a topology loss "
                         "(multi-pod: drop the last pod; single: evict "
                         "half the hosts on a confirmed straggler) and "
                         "run the elastic re-plan; the transition's "
                         "ReplanReport lands in the result JSON under "
                         "'replan'")
    ap.add_argument("--guard", action="store_true",
                    help="emit the collective guard's pre-launch view "
                         "in the result JSON under 'guard': the "
                         "schedule digest every rank must agree on and "
                         "the comm deadline armed from the cost model's "
                         "prediction (runtime/guard.py)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="emit the seeded fault plan and the degraded-"
                         "fabric pricing (nominal vs worst injected "
                         "link degradation, simulate_schedule "
                         "link_scale) in the result JSON under 'chaos'; "
                         "implies --guard")
    ap.add_argument("--watchdog-max-bad-steps", type=int, default=3,
                    help="NaN watchdog knob (train.py executes it; the "
                         "dry run records it in the run header)")
    ap.add_argument("--watchdog-spike-factor", type=float, default=10.0,
                    help="NaN watchdog spike ratio (run header)")
    ap.add_argument("--watchdog-window", type=int, default=64,
                    help="NaN watchdog median window (run header)")
    ap.add_argument("--straggler-factor", type=float, default=3.0,
                    help="straggler monitor factor (run header)")
    ap.add_argument("--straggler-window", type=int, default=32,
                    help="straggler monitor median window (run header)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.skew == "auto" and args.plan != "auto":
        ap.error("--skew auto requires --plan auto")
    use_guard = args.guard or args.chaos is not None
    print(f"[run] watchdog(max_bad_steps={args.watchdog_max_bad_steps}, "
          f"spike_factor={args.watchdog_spike_factor:g}, "
          f"window={args.watchdog_window}) "
          f"straggler(factor={args.straggler_factor:g}, "
          f"window={args.straggler_window}) "
          f"guard={'on' if use_guard else 'off'} "
          f"chaos={args.chaos if args.chaos is not None else 'off'}",
          flush=True)
    mode, chunks, comp, plan = (args.mode or "fsdp", args.chunks,
                                args.compression, None)
    moe_a2a_mode = "flat"
    cache_stats = None
    try:
        if args.plan == "auto":
            plan, chosen, a2a_plan, cache_stats = auto_plan(
                args.arch, multi_pod=args.mesh == "multi",
                comm_mode=args.mode or "hier",
                allow_int8=args.compression == "int8",
                shape_name=args.shape, skew=args.skew,
                packed=not args.no_packed,
                border_scarce=args.border_scarce,
                plan_cache_path=args.plan_cache)
            print(f"[plan] cache: {cache_stats['hits']} hit(s), "
                  f"{cache_stats['misses']} miss(es)", flush=True)
            if a2a_plan is not None:
                moe_a2a_mode = a2a_plan.recommended_mode()
                print(f"[plan] MoE dispatch/combine All2All -> "
                      f"{moe_a2a_mode}", flush=True)
                print(a2a_plan.describe(), flush=True)
            # explicitly-flagged structural modes (fsdp / hier_zero1) keep
            # their optimizer wiring; the schedule comes from the plan,
            # resolved per bucket inside the collectives.  For the rest,
            # the plan may recommend the chained overlap executor when
            # exposed comm beats the sequential sync.
            if args.mode in ("fsdp", "hier_zero1"):
                mode = args.mode
            else:
                rec = plan.recommended_mode()
                if rec == "hier_overlap":
                    mode = "hier_overlap"
                else:
                    # per-bucket schedules resolve from the plan inside
                    # the collectives; "hier" is the generic wiring and
                    # "flat" the no-plan degenerate case
                    mode = chosen.mode if chosen.mode == "flat" else "hier"
            chunks, comp = chosen.n_chunks, chosen.compression
            # the human-readable table replaces reading the raw summary
            # dict out of the result JSON
            print(plan.describe(), flush=True)
        use_packed = not args.no_packed
        if plan is not None and plan.data_path == "per_leaf":
            # planner's per-leaf fallback (plan(packed=True, n_leaves=)):
            # the modeled pack overhead loses to syncing the leaves
            # individually, so lower the unpacked executor
            print("[plan] per-leaf data path (pack overhead loses; "
                  "lowering without Pack/Unpack)", flush=True)
            use_packed = False
        res = lower_cell(args.arch, args.shape, multi_pod=args.mesh == "multi",
                         comm_mode=mode, sp=args.sp,
                         use_pallas=args.pallas, n_chunks=chunks,
                         compression=comp,
                         capacity_factor=args.capacity_factor,
                         remat_policy=args.remat_policy, plan=plan,
                         packed=use_packed,
                         moe_a2a_mode=moe_a2a_mode)
        if args.elastic:
            rep = elastic_replan_report(
                args.arch, multi_pod=args.mesh == "multi", comm_mode=mode,
                border_scarce=args.border_scarce,
                plan_cache_path=args.plan_cache)
            res["replan"] = rep.summary()
            print(rep.describe(), flush=True)
        if use_guard:
            res["guard"] = guard_section(
                plan, mode=mode, chunks=chunks, compression=comp,
                n_chips=res.get("n_chips", 1))
            print(f"[guard] schedule digest "
                  f"{res['guard']['schedule_digest']} "
                  f"({res['guard']['ranks']} rank(s) agree)", flush=True)
        if args.chaos is not None:
            res["chaos"] = chaos_section(
                args.chaos, args.arch, multi_pod=args.mesh == "multi",
                border_scarce=args.border_scarce, plan=plan, mode=mode,
                chunks=chunks, compression=comp)
            ch = res["chaos"]
            print(f"[chaos] seed {args.chaos}: "
                  f"{len(ch['events'])} fault(s); sync "
                  f"{ch['nominal_sync_s'] * 1e3:.2f} ms nominal -> "
                  f"{ch['degraded_sync_s'] * 1e3:.2f} ms degraded "
                  f"(x{ch['slowdown']:.2f})", flush=True)
        if cache_stats is not None:
            res["plan_cache"] = cache_stats
    except Exception as e:  # noqa: BLE001
        res = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "comm_mode": mode, "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-3000:]}
    js = json.dumps(res, indent=1)
    print(js)
    if args.out:
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(js)
    if res["status"] == "error":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
