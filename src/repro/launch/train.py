"""End-to-end training driver: data pipeline -> shard_map train step ->
metrics, with checkpoint/restart, NaN rollback and straggler logging.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.train \
        --arch qwen2.5-3b --smoke --steps 100 --mesh test --mode hier

`--mesh test` is a (pod, data, model) mesh over the devices present:
(1,1,1) on one chip, (2,2,1) on four, and (2,2,2) over the 8 virtual
devices XLA's CPU backend provides when ``JAX_PLATFORMS=cpu``.  `--mesh
none` runs single-device; `--mesh production` is the real 2x16x16
target (512 virtual CPU devices, dry-run hardware).
"""

import argparse
import dataclasses
import os
import pathlib
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.data import DataConfig, Prefetcher
from repro.launch.mesh import (
    emulate_host_devices, make_local_mesh, make_production_mesh,
    runtime_for_mesh)
from repro.models import Model
from repro.parallel.sharding import Runtime
from repro.runtime import (
    CheckpointManager, NaNWatchdog, StragglerMonitor, WatchdogConfig)
from repro.train import TrainConfig, make_train_step
from repro.train.optimizer import OptConfig

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache for the drivers; never enabled
    at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    uses it and nothing is set here; otherwise the cache lives at the
    fixed ``<repo>/.jax_cache`` (the path is part of the cache key, so
    it never moves)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir",
                      str(REPO_ROOT / ".jax_cache"))


def init_training(model, tcfg, mesh, seed: int = 0):
    """make_train_step -> init -> (sharded) build -> ZeRO bootstrap: the
    library path every driver takes.  Returns ``(step_fn, builder,
    pshape, params, opt)``; ``builder`` rebuilds the sharded step for
    the same shapes (None without a mesh)."""
    builder_or_step, init = make_train_step(model, tcfg, mesh=mesh)
    params, opt = init(jax.random.key(seed))
    pshape = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
    if mesh is None:
        return builder_or_step, None, pshape, params, opt
    step_fn, boot = builder_or_step(pshape)
    if boot is not None:
        opt = boot(params)
    return step_fn, builder_or_step, pshape, params, opt


def data_config(cfg, global_batch: int, seq: int, seed: int = 0) -> DataConfig:
    return DataConfig(vocab_size=cfg.vocab_size, global_batch=global_batch,
                      seq_len=seq, seed=seed, enc_seq=cfg.enc_seq,
                      d_model=cfg.d_model if cfg.enc_seq else 0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "test", "production"])
    ap.add_argument("--mode", default="hier",
                    choices=["flat", "hier", "hier_pipelined",
                             "hier_border_rs", "hier_overlap",
                             "hier_zero1", "fsdp"])
    ap.add_argument("--plan", default="manual", choices=["manual", "auto"],
                    help="auto: let core.planner pick mode/chunks/compression "
                         "per gradient bucket from the cost model, replacing "
                         "the hand-picked --mode/--chunks flags")
    ap.add_argument("--skew", default="none", choices=["none", "auto"],
                    help="auto: core.skew derives the uneven per-pod batch "
                         "split from per-cluster tflops and runs the "
                         "weighted gradient sync (DESIGN.md §10); with "
                         "--plan auto the comm plan is jointly optimized "
                         "with the split")
    ap.add_argument("--compression", default=None, choices=["bf16", "int8"])
    ap.add_argument("--no-packed", action="store_true",
                    help="disable the zero-copy packed gradient data "
                         "path (legacy per-step re-flatten; A/B axis)")
    ap.add_argument("--plan-cache", default=None, metavar="PATH",
                    help="disk-backed plan cache (core.plan_cache): "
                         "repeated --plan auto launches on the same "
                         "topology/knobs reuse the cached search "
                         "instead of re-planning")
    ap.add_argument("--elastic", action="store_true",
                    help="enable the elastic re-planning controller "
                         "(runtime/elastic.py): on a pod failure the old "
                         "topology's plan-cache lines are invalidated, "
                         "the planner re-runs against the survivors, the "
                         "ZeRO-1 master is remapped online through the "
                         "packed slot map, and training resumes on the "
                         "survivor mesh; the transition's ReplanReport "
                         "is printed at resume.  Straggler verdicts are "
                         "fed to the controller too (host eviction is "
                         "the scheduler's call, so confirmed stragglers "
                         "are surfaced, not acted on)")
    ap.add_argument("--inject-pod-failure", type=int, default=None,
                    metavar="STEP",
                    help="with --elastic on a multi-pod mesh: report the "
                         "last pod as failed just before STEP executes "
                         "(emulated fault injection)")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--guard", action="store_true",
                    help="arm the collective guard (runtime/guard.py): "
                         "per-step comm deadline (cost-model prediction "
                         "x margin, floored by wall-clock calibration), "
                         "pre-launch schedule-digest agreement, payload "
                         "checksums, bounded retry on transient transfer "
                         "failures, and per-link bandwidth EWMAs whose "
                         "confirmed degraded verdicts escalate to the "
                         "elastic controller (re-plan needs --elastic)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="seeded chaos engine (runtime/faults.py): "
                         "inject one fault per class (degraded link, "
                         "transient transfer failure, rank hang, NaN "
                         "payload, bit flip) at deterministic steps; "
                         "implies --guard.  Requires a mesh (--mesh "
                         "test|production)")
    ap.add_argument("--watchdog-max-bad-steps", type=int, default=3,
                    help="NaN watchdog: consecutive non-finite/spiking "
                         "losses before rollback")
    ap.add_argument("--watchdog-spike-factor", type=float, default=10.0,
                    help="NaN watchdog: loss vs trailing median ratio "
                         "flagged as a spike")
    ap.add_argument("--watchdog-window", type=int, default=64,
                    help="NaN watchdog: trailing median window (steps)")
    ap.add_argument("--straggler-factor", type=float, default=3.0,
                    help="straggler monitor: step slower than factor x "
                         "trailing median is flagged")
    ap.add_argument("--straggler-window", type=int, default=32,
                    help="straggler monitor: trailing median window "
                         "(steps)")
    args = ap.parse_args(argv)
    if args.chaos is not None and args.mesh == "none":
        ap.error("--chaos requires a mesh (--mesh test|production)")
    if args.mesh != "none":
        # CPU emulation only (a no-op unless JAX_PLATFORMS=cpu)
        emulate_host_devices(8 if args.mesh == "test" else 512)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.mesh == "none":
        mesh = None
        rt = Runtime(use_pallas=args.pallas)
    else:
        mesh = (make_local_mesh() if args.mesh == "test"
                else make_production_mesh(multi_pod=True))
        rt = runtime_for_mesh(mesh, fsdp=args.mode == "fsdp",
                              use_pallas=args.pallas)
    model = Model(cfg, rt)
    if args.mode == "fsdp" and mesh is not None:
        model = model.with_fsdp(dict(zip(mesh.axis_names,
                                         mesh.devices.shape))["data"])

    plan = None
    plan_cache = None
    cluster_weights = None
    moe_a2a_mode = rt.moe_a2a_mode
    moe_weights = None
    if (args.plan == "auto" or args.skew == "auto") and mesh is not None:
        from repro.core import cost_model, overlap, planner, topology
        from repro.core import skew as skew_lib

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_pods = sizes.get("pod", 1)
        chips_per_pod = int(np.prod(list(mesh.devices.shape))) // n_pods
        topo = topology.tpu_multipod(max(1, n_pods), chips_per_pod)
        grad_bytes = max(1, cfg.param_count() * 4 // sizes.get("model", 1))
        allowed = (None, args.compression) if args.compression else (None, "bf16")
        plan_cache = (planner.PlanCache(path=args.plan_cache)
                      if args.plan_cache else planner.default_plan_cache())
        plan_kw = dict(
            cache=plan_cache,
            # the ZeRO-1 sync is a reduce_scatter (the end AllGather moves
            # to the param update); everything else rides all_reduce
            coll=("reduce_scatter" if args.mode == "hier_zero1"
                  else "all_reduce"),
            pod_axis="pod" if n_pods > 1 else None, intra_axis="data",
            compressions=allowed, flat_mechanism="native",
            # balanced subgroups are advisory (the mesh can't subdivide
            # pods) — executable plans price the mesh as it runs
            try_balanced=False,
            # the step executes the packed data path, so candidates are
            # priced with the Pack/Unpack steps (DESIGN.md §11); the
            # leaf count arms the per-leaf fallback — if the modeled
            # pack overhead loses to syncing the leaves individually,
            # plan.data_path comes back "per_leaf" and packed is
            # overridden below
            packed=not args.no_packed,
            n_leaves=len(jax.tree.leaves(
                jax.eval_shape(model.init, jax.random.key(0)))))
        # overlap axis: price the readiness-ordered layer buckets against
        # the backward-compute timeline so the plan optimizes exposed
        # rather than total comm time (core/overlap.py).  Structural
        # modes execute one monolithic sync, so they are priced at that
        # granularity directly.
        step_flops = (6.0 * cfg.active_param_count()
                      * args.global_batch * args.seq)
        backward_s = None
        bucket_sizes = [grad_bytes]
        if args.mode not in ("fsdp", "hier_zero1"):
            backward_s = cost_model.backward_compute_time(topo, step_flops)
            # same cap the executor uses (TrainConfig.bucket_cap_mb
            # defaults to this constant), so the priced layout matches
            # the executed one
            bucket_sizes = overlap.bucket_sizes_for_volume(
                grad_bytes, cfg.n_layers, overlap.DEFAULT_CAP_BYTES)
        sim_cache: dict = {}
        skew_split = skew_comp = None
        if args.skew == "auto":
            # joint skew + comm optimization (DESIGN.md §10): uneven
            # integer microbatch split, weighted gradient sync, and the
            # straggler objective.  tpu_multipod is homogeneous, so the
            # split degenerates to even (weights 1.0) — the wiring still
            # runs end to end for skewed topologies.
            sp = skew_lib.optimize(
                topo, step_flops, bucket_sizes,
                total_microbatches=max(topo.n_clusters, args.global_batch),
                # structural modes execute one monolithic sequential
                # sync — no backward window to hide behind
                backward_frac=(0.0 if args.mode in ("fsdp", "hier_zero1")
                               else 2.0 / 3.0),
                _sim_cache=sim_cache, **plan_kw)
            skew_split, skew_comp = sp.split, sp.compute_s
            cluster_weights = sp.split.weights
            print("[skew] " + sp.describe(), flush=True)
            if any(abs(w - 1.0) > 1e-9 for w in cluster_weights):
                # this single-host driver shards the batch evenly per
                # device (DataConfig below runs n_hosts=1); weighting
                # gradients of an *even* batch would bias the mean, so
                # the weighted sync only executes when the data layer
                # delivers the matching uneven shards
                # (DataConfig.host_shares on multi-host launches)
                print("[skew] data shards are even per device — keeping "
                      "the unweighted sync (the split above describes "
                      "the intended uneven assignment)", flush=True)
                cluster_weights = None
            if args.plan == "auto":
                plan = sp.plan
        if args.plan == "auto" and plan is None:
            plan = planner.plan(topo, bucket_sizes,
                                backward_compute_s=backward_s,
                                skew=skew_split, skew_compute_s=skew_comp,
                                _sim_cache=sim_cache, **plan_kw)
        if (plan is not None and plan.overlap is not None
                and plan.recommended_mode() != "hier_overlap"):
            # overlap doesn't win -> execution is one monolithic
            # collective; re-plan at that granularity so config_for
            # resolves a schedule tuned for the real payload
            plan = planner.plan(topo, [grad_bytes], skew=skew_split,
                                skew_compute_s=skew_comp,
                                _sim_cache=sim_cache, **plan_kw)
        if (plan is not None and cluster_weights is None
                and plan.cluster_weights is not None):
            # mirror the even-data guard above on the executed plan
            plan = dataclasses.replace(plan, cluster_weights=None)
        if plan is not None:
            b = max(plan.buckets, key=lambda x: x.nbytes)
            msg = (f"[plan] {plan.recommended_mode()} "
                   f"(biggest bucket: {b.candidate.mode} "
                   f"n_chunks={b.candidate.n_chunks} "
                   f"compression={b.candidate.compression}) "
                   f"predicted {plan.predicted_step_s*1e3:.2f} ms/sync total")
            if plan.overlap is not None:
                msg += (f", {plan.exposed_comm_s*1e3:.2f} ms exposed "
                        f"(backward "
                        f"{plan.overlap.backward_compute_s*1e3:.2f} ms)")
            print(msg + f" validated={plan.validated}", flush=True)
            print(plan.describe(), flush=True)
        if args.plan == "auto" and cfg.n_experts:
            # MoE dispatch/combine All2All: the ep payload is token
            # activations (E x capacity x d_model), not gradients, so it
            # gets its own plan over the a2a candidate family
            # (flat / flat_a2a / hier_a2a; DESIGN.md §12).  int8 is
            # excluded by the hier_a2a builder — activations have no
            # error-feedback step to absorb the quantization bias.
            from repro.models import moe as moe_lib

            tokens = max(1, args.global_batch * args.seq)
            t_loc = max(1, tokens // max(1, topo.n_ranks))
            cap = moe_lib._capacity(t_loc, cfg.top_k, cfg.n_experts,
                                    rt.moe_capacity_factor)
            a2a_bytes = max(1, cfg.n_experts * cap * cfg.d_model * 4)
            a2a_plan = planner.plan(
                topo, [a2a_bytes] * max(1, cfg.n_layers),
                coll="all_to_all",
                pod_axis="pod" if n_pods > 1 else None, intra_axis="data",
                compressions=(None, "bf16"), flat_mechanism="native",
                try_balanced=False, cache=plan_cache, _sim_cache=sim_cache)
            moe_a2a_mode = a2a_plan.recommended_mode()
            # skew split -> expert capacity: slow clusters host fewer
            # hot-expert slots.  Capacity allocation never weights
            # gradients, so the even-data guard above does not apply.
            if skew_split is not None:
                moe_weights = skew_split.weights
            print(f"[plan] MoE dispatch/combine All2All -> {moe_a2a_mode} "
                  f"({a2a_bytes / 2 ** 20:.1f} MiB/layer)", flush=True)
            print(a2a_plan.describe(), flush=True)
        st = plan_cache.stats()
        print(f"[plan] cache: {st['hits']} hit(s), {st['misses']} miss(es)",
              flush=True)

    if cfg.n_experts and (moe_a2a_mode != rt.moe_a2a_mode
                          or moe_weights != rt.moe_cluster_weights):
        # the Runtime is closed over by the model, so rebuild both with
        # the planned MoE a2a knobs before the train step traces
        rt = dataclasses.replace(
            rt, moe_a2a_mode=moe_a2a_mode,
            moe_cluster_weights=(tuple(moe_weights) if moe_weights
                                 else None))
        model = Model(cfg, rt)
        if args.mode == "fsdp" and mesh is not None:
            model = model.with_fsdp(dict(zip(mesh.axis_names,
                                             mesh.devices.shape))["data"])

    # optimizer structure (fsdp / zero1) is not a per-bucket knob; the plan
    # only replaces the schedule choice within the generic hier path.
    mode = args.mode
    if plan is not None and mode not in ("fsdp", "hier_zero1"):
        mode = ("hier_overlap"
                if plan.recommended_mode() == "hier_overlap" else "hier")
    use_packed = not args.no_packed
    if plan is not None and plan.data_path == "per_leaf":
        # planner's per-leaf fallback: pack overhead exceeds the wire
        # saving for this tree, so execute the unpacked tree sync
        print("[plan] per-leaf data path (pack overhead loses; "
              "packed disabled for this run)", flush=True)
        use_packed = False
    tcfg = TrainConfig(comm_mode=mode,
                       dcn_compression=args.compression, plan=plan,
                       cluster_weights=cluster_weights,
                       packed=use_packed,
                       opt=OptConfig(lr=args.lr, warmup_steps=20))
    step_fn, builder_or_step, pshape, params, opt = init_training(
        model, tcfg, mesh)
    dcfg = data_config(cfg, args.global_batch, args.seq)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        start, (params, opt), extra = ckpt.restore((params, opt))
        print(f"resumed from step {start}")

    watchdog = NaNWatchdog(WatchdogConfig(
        max_bad_steps=args.watchdog_max_bad_steps,
        loss_spike_factor=args.watchdog_spike_factor,
        window=args.watchdog_window))
    straggler = StragglerMonitor(factor=args.straggler_factor,
                                 window=args.straggler_window)
    use_guard = args.guard or args.chaos is not None
    print(f"[run] watchdog(max_bad_steps={args.watchdog_max_bad_steps}, "
          f"spike_factor={args.watchdog_spike_factor:g}, "
          f"window={args.watchdog_window}) "
          f"straggler(factor={args.straggler_factor:g}, "
          f"window={args.straggler_window}) "
          f"guard={'on' if use_guard else 'off'} "
          f"chaos={args.chaos if args.chaos is not None else 'off'}",
          flush=True)

    elastic_ctl = None
    if args.elastic and mesh is not None:
        from repro.core import planner as planner_lib
        from repro.core import topology as topology_lib
        from repro.runtime import elastic as elastic_lib

        e_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        e_pods = e_sizes.get("pod", 1)
        e_topo = topology_lib.tpu_multipod(
            max(1, e_pods),
            int(np.prod(list(mesh.devices.shape))) // max(1, e_pods))
        e_grad = max(1, cfg.param_count() * 4 // e_sizes.get("model", 1))
        e_cache = (plan_cache if plan_cache is not None
                   else planner_lib.default_plan_cache())
        e_kw = dict(
            coll=("reduce_scatter" if args.mode == "hier_zero1"
                  else "all_reduce"),
            pod_axis="pod" if e_pods > 1 else None, intra_axis="data",
            compressions=((None, args.compression) if args.compression
                          else (None, "bf16")),
            flat_mechanism="native", try_balanced=False)
        # make sure the running topology has a cache line — the line a
        # pod failure must invalidate
        planner_lib.plan(e_topo, [e_grad], cache=e_cache, **e_kw)
        elastic_ctl = elastic_lib.ElasticController(
            e_topo, [e_grad], plan_cache=e_cache, straggler=straggler,
            plan_kw=e_kw)

    guard = None
    injector = None
    g_topo = None
    g_n_ranks = 1
    g_grad = 1
    if use_guard:
        from repro.core import topology as topology_lib
        from repro.core.collectives import CommConfig
        from repro.runtime import faults as faults_lib
        from repro.runtime import guard as guard_lib

        g_sizes = (dict(zip(mesh.axis_names, mesh.devices.shape))
                   if mesh is not None else {})
        g_n_ranks = (int(np.prod(list(mesh.devices.shape)))
                     if mesh is not None else 1)
        g_pods = g_sizes.get("pod", 1)
        g_topo = topology_lib.tpu_multipod(
            max(1, g_pods), max(1, g_n_ranks // max(1, g_pods)))
        g_grad = max(1, cfg.param_count() * 4 // g_sizes.get("model", 1))
        guard = guard_lib.CollectiveGuard(
            guard_lib.GuardConfig(),
            predicted_step_s=(plan.predicted_step_s
                              if plan is not None else None),
            nominal_Bps={i: c.nic_Bps
                         for i, c in enumerate(g_topo.clusters)},
            expected_ranks=range(g_n_ranks),
            elastic=elastic_ctl)
        # pre-launch desync check: every rank digests the schedule it is
        # about to run (this single-process emulation computes one digest
        # for all ranks; a real deployment gathers them over the control
        # plane, and the chaos harness perturbs one to prove detection)
        dsrc = plan if plan is not None else CommConfig(
            mode=mode, pod_axis="pod" if g_pods > 1 else None,
            intra_axis="data", n_chunks=tcfg.n_chunks,
            compression=args.compression,
            cluster_weights=(tuple(cluster_weights)
                             if cluster_weights else None))
        digest = guard_lib.schedule_digest(dsrc)
        ev = guard.check_agreement(start,
                                   {r: digest for r in range(g_n_ranks)})
        print(f"[guard] schedule digest {digest} "
              + (f"DESYNC: {ev.detail}" if ev is not None
                 else f"({g_n_ranks} rank(s) agree)"), flush=True)
        if args.chaos is not None:
            fplan = faults_lib.FaultPlan.generate(
                args.chaos, args.steps, n_clusters=g_topo.n_clusters,
                n_ranks=g_n_ranks)
            injector = faults_lib.FaultInjector(fplan)
            print("\n".join(
                f"[chaos] seed {args.chaos}: {e.kind} @ step {e.step}"
                f" x{e.duration}"
                + (f" cluster={e.cluster}" if e.cluster is not None else "")
                + (f" rank={e.rank}" if e.rank is not None else "")
                for e in fplan.events), flush=True)

    def _pod_failover(at_step, mesh, model, tcfg, params, opt):
        """Kill the last pod: re-plan against the survivors, rebuild
        the step on the survivor mesh, and cross params + optimizer
        state online (ZeRO-1 master via the packed slot-map remap;
        checkpoint-restore fallback when the layouts are not
        remappable)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.runtime import elastic as elastic_lib

        old_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        rep = elastic_ctl.report_pod_failure(
            at_step, elastic_ctl.topo.n_clusters - 1)
        print(f"[elastic] {rep.trigger}: {rep.detail}; re-planned "
              f"{rep.old_fingerprint} -> {rep.new_fingerprint} in "
              f"{rep.replan_latency_s * 1e3:.1f} ms "
              f"({rep.invalidated_entries} cache line(s) invalidated)",
              flush=True)
        new_mesh = elastic_lib.survivor_mesh(mesh, "pod",
                                             old_sizes["pod"] - 1)
        new_sizes = dict(zip(new_mesh.axis_names, new_mesh.devices.shape))
        new_rt = runtime_for_mesh(new_mesh, fsdp=args.mode == "fsdp",
                                  use_pallas=args.pallas)
        new_model = Model(cfg, new_rt)
        if args.mode == "fsdp":
            new_model = new_model.with_fsdp(new_sizes["data"])
        new_tcfg = dataclasses.replace(
            tcfg, plan=elastic_ctl.plan if tcfg.plan is not None else None)
        build2, _ = make_train_step(new_model, new_tcfg, mesh=new_mesh)
        step2, boot2 = build2(pshape)
        specs_old = model.param_specs(pshape)
        specs_new = new_model.param_specs(pshape)
        p_shard = [NamedSharding(new_mesh, sp)
                   for sp in jax.tree.leaves(specs_new)]
        new_params = jax.tree.unflatten(
            jax.tree.structure(params),
            [jax.device_put(np.asarray(jax.device_get(x)), s)
             for x, s in zip(jax.tree.leaves(params), p_shard)])
        remap_path = "slot_map"
        rsh = NamedSharding(new_mesh, P())
        if args.mode == "hier_zero1":
            old_layout = elastic_lib.zero1_master_layout(
                pshape, specs_old, old_sizes)
            new_layout = elastic_lib.zero1_master_layout(
                pshape, specs_new, new_sizes)
            host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                opt)
            zspec = (P(("data", "model")) if "model" in new_sizes
                     else P("data"))
            zsh = NamedSharding(new_mesh, zspec)
            try:
                remapped = elastic_lib.remap_zero_state(
                    host, old_layout, new_layout,
                    old_world=old_sizes["data"],
                    new_world=new_sizes["data"],
                    n_columns=new_sizes.get("model", 1))
                new_opt = type(opt)(
                    jax.device_put(remapped.flat_param, zsh),
                    jax.device_put(remapped.mu, zsh),
                    jax.device_put(remapped.nu, zsh),
                    jax.device_put(np.asarray(remapped.step), rsh))
            except ValueError as e:
                # mesh shrank below the layout's divisibility (or the
                # leaf contents changed): restore with new shardings
                remap_path = "restore_fallback"
                print(f"[elastic] slot-map remap not applicable ({e}); "
                      "falling back to checkpoint restore", flush=True)
                new_opt = None
                if ckpt is not None and ckpt.latest_step() is not None:
                    try:
                        _, (new_params, new_opt), _ = ckpt.restore(
                            (new_params, boot2(new_params)),
                            shardings=(jax.tree.unflatten(
                                jax.tree.structure(params), p_shard),
                                type(opt)(zsh, zsh, zsh, rsh)))
                    except Exception as e2:  # noqa: BLE001
                        # the checkpointed master flat rode the OLD
                        # world's layout, so even the restore cannot
                        # reshape it onto the survivors
                        print(f"[elastic] restore not layout-"
                              f"compatible either ({e2})", flush=True)
                        new_opt = None
                if new_opt is None:
                    print("[elastic] re-bootstrapping the optimizer "
                          "from the resharded params (moments reset)",
                          flush=True)
                    new_opt = boot2(new_params)
        else:
            psh_tree = jax.tree.unflatten(jax.tree.structure(params),
                                          p_shard)
            osh_tree = type(opt)(psh_tree, psh_tree, rsh)
            new_opt = jax.tree.map(
                lambda x, s: jax.device_put(
                    np.asarray(jax.device_get(x)), s),
                opt, osh_tree)
        return new_mesh, new_model, new_tcfg, step2, new_params, new_opt, \
            remap_path

    pre = Prefetcher(dcfg, start_step=start)
    losses = []
    injected_failure = False
    elastic_remap_path = "slot_map"
    fresh_trace = True  # step 0 compiles; its wall time is not a hang
    try:
        t_start = time.time()
        step = start
        while step < args.steps:
            if (elastic_ctl is not None and not injected_failure
                    and args.inject_pod_failure is not None
                    and step >= args.inject_pod_failure
                    and elastic_ctl.topo.n_clusters > 1):
                injected_failure = True
                (mesh, model, tcfg, step_fn, params, opt,
                 elastic_remap_path) = _pod_failover(
                     step, mesh, model, tcfg, params, opt)
                fresh_trace = True
            sid, batch = pre.get(timeout=30.0)
            batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
            retraced, fresh_trace = fresh_trace, False
            chaos_hook = None
            stalled_s = 0.0
            if injector is not None:
                # a hung rank stalls past the guard's deadline (the
                # in-band emulation of a silent rank in one process)
                stalled_s = injector.stall(
                    step, guard.deadline_s or guard.cfg.min_deadline_s)
                chaos_hook = injector.corruption_hook(
                    step, axes=mesh.axis_names)
            straggler.start()
            timing = {}

            def _run(params=params, opt=opt, batch=batch, hook=chaos_hook):
                t0 = time.monotonic()
                if hook is not None:
                    # trace-time corruption: build and FIRST-call a fresh
                    # step under the hook (tracing happens at first call;
                    # the regular step_fn stays clean for the next step)
                    from repro.core import primitives
                    with primitives.inject_hook(hook):
                        f_step, _ = builder_or_step(pshape)
                        out = f_step(params, opt, batch)
                else:
                    out = step_fn(params, opt, batch)
                timing["dt"] = time.monotonic() - t0
                return out

            if guard is not None:
                thunk = (_run if injector is None
                         else injector.wrap_transfer(step, _run))
                new_params, new_opt, m = guard.retry(step, thunk)
            else:
                new_params, new_opt, m = _run()
            loss = float(m["loss"])
            slow = straggler.stop()
            if guard is not None:
                hung = (injector.hung_ranks(step)
                        if injector is not None else ())
                for r in range(g_n_ranks):
                    if r not in hung:
                        guard.heartbeat(step, r)
                if chaos_hook is None and not retraced:
                    # a retrace step's wall time is dominated by
                    # compilation, not the fabric — not a hang signal
                    gev = guard.observe_step_time(
                        step, timing.get("dt", 0.0) + stalled_s)
                    if gev is not None:
                        print(f"[guard] {gev.kind} @ step {step}: "
                              f"{gev.detail} ({gev.attribution})",
                              flush=True)
                # the reduced metrics ride along: with the finite gate a
                # NaN payload never reaches new_params — the synced
                # grad_norm is where it surfaces
                gev = guard.check_payload(
                    step, {"grad_norm": m["grad_norm"],
                           "loss": m["loss"], "params": new_params})
                if gev is not None:
                    print(f"[guard] {gev.kind} @ step {step}: "
                          f"{gev.detail}", flush=True)
                if g_topo is not None and g_topo.n_clusters > 1:
                    # emulated link-health feed: the nominal C2C time
                    # for this step's gradient payload (size varied so
                    # the alpha-beta fit is well-posed), inflated by any
                    # active degradation — exactly the observation a
                    # slow wire produces on a real fabric
                    nbytes = int(g_grad * (1.0 + 0.25 * (step % 4))) + 1
                    for ci, cl in enumerate(g_topo.clusters):
                        t_obs = nbytes / cl.nic_Bps
                        if injector is not None:
                            t_obs = injector.perturb_transfer_time(
                                step, ci, t_obs)
                        gev = guard.observe_transfer(step, ci, nbytes,
                                                     t_obs)
                        if gev is None:
                            continue
                        print(f"[guard] {gev.kind} @ step {step}: "
                              f"{gev.detail} ({gev.attribution})",
                              flush=True)
                        if gev.replan is not None:
                            # re-planned against the derated fabric:
                            # rebuild the step with the new plan on the
                            # unchanged mesh (no resharding needed)
                            if tcfg.plan is not None:
                                tcfg = dataclasses.replace(
                                    tcfg, plan=elastic_ctl.plan)
                                builder_or_step, _ = make_train_step(
                                    model, tcfg, mesh=mesh)
                                step_fn, _ = builder_or_step(pshape)
                                fresh_trace = True
                            elastic_remap_path = "none (same mesh)"
            if elastic_ctl is not None:
                # confirmed persistent stragglers are surfaced (host
                # eviction is the scheduler's call; on_straggler is
                # unset here, so the controller records but never acts)
                elastic_ctl.observe_step(step, slow=slow)
            verdict = watchdog.observe(loss)
            if verdict == "rollback" and ckpt and ckpt.latest_step() is not None:
                # the step donated the old (params, opt) buffers; the
                # returned ones are the live templates for the restore
                step, (params, opt), _ = ckpt.restore(
                    (new_params, new_opt))
                print(f"[health] non-finite/spiking loss -> rolled back to {step}")
                continue
            if verdict == "skip":
                # with the finite gate the returned buffers hold the
                # pre-update values on a poisoned step — adopting them
                # IS the skip (the old buffers were donated)
                params, opt = new_params, new_opt
                print(f"[health] step {step}: loss {loss} skipped")
                step += 1
                continue
            params, opt = new_params, new_opt
            if elastic_ctl is not None and elastic_ctl.state == "replanned":
                print(elastic_ctl.resumed(
                    step, remap_path=elastic_remap_path).describe(),
                    flush=True)
            losses.append(loss)
            if step % args.log_every == 0:
                dt = (time.time() - t_start) / max(1, len(losses))
                print(f"step {step:5d} loss {loss:8.4f} "
                      f"gnorm {float(m['grad_norm']):7.3f} "
                      f"{dt*1e3:7.1f} ms/step"
                      + (" [straggler]" if slow else ""), flush=True)
            if ckpt and step and step % args.ckpt_every == 0:
                ckpt.save_async(step, (params, opt))
            step += 1
        if ckpt:
            ckpt.save(step, (params, opt))
            ckpt.wait()
    finally:
        pre.close()
    if guard is not None:
        grep = guard.report()
        dl = grep["deadline_s"]
        print(f"[guard] deadline "
              f"{'unarmed' if dl is None else f'{dl:.3f}s'}; "
              f"events: {grep['counts'] or 'none'}", flush=True)
    if injector is not None:
        print(f"[chaos] {len(injector.injected)} injected action(s): "
              + (", ".join(sorted({i['kind'] for i in injector.injected}))
                 or "none"), flush=True)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}) "
          f"over {len(losses)} steps")
    return losses


if __name__ == "__main__":
    main()
