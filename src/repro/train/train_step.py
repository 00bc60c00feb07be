"""The distributed training step: explicit shard_map SPMD with the
HetCCL hierarchical collectives doing all data-parallel traffic.

Communication modes (``TrainConfig.comm_mode``) — the §Perf A/B axis:

  flat        replicated params; one flat psum over (pod, data) for the
              gradients (homogeneous-library emulation — the baseline).
  hier        paper-faithful AllReduceH: ReduceScatter(ICI) ->
              c2cRed(DCN) -> AllGather(ICI), bucketed (Alg. 1, Table 7).
  hier_pipelined
              hier with the C2C step chunked + software-pipelined
              against the intra steps (paper §4.3.2, Fig. 9).
  hier_border_rs
              §4.3 border-communicator schedule: the pod hop becomes a
              combining reduce-scatter + owned-shard redistribution over
              the cluster ring (proportional NIC split; no Fig. 8 bounce
              hop — wins on border-scarce clusters).
  hier_overlap
              AllReduceH per readiness-ordered gradient bucket
              (core/overlap.py): buckets chained in backward readiness
              order (lm_head first, layers in reverse, embeddings last)
              so XLA can schedule each bucket's C2C against the
              backward compute still producing later buckets
              (beyond-paper; the H2/HETHUB overlap axis).
  hier_zero1  hier breakdown fused with ZeRO-1: the reduce-scattered
              f32 shard feeds Adam directly; the end-AllGather doubles
              as the parameter reconstruction (beyond-paper).
  fsdp        parameters FSDP-sharded over `data`; autodiff's transpose
              of the per-layer all_gather performs the intra-pod
              reduce-scatter, and the only explicit sync left is the
              c2cRed psum over `pod` — the paper's breakdown realized
              structurally (beyond-paper; optional int8+EF compression
              on that DCN hop).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collectives as coll
from repro.core.collectives import CommConfig
from repro.core import compression
from repro.core import overlap as overlap_lib
from repro.core import scopes
from repro.core.schedule import STRUCTURAL_MODES, build_schedule
from repro.models.model import Model
from repro.parallel.sharding import Runtime, shard_map
from . import loss as loss_lib
from . import optimizer as opt_lib


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    # any registered schedule mode (flat|hier|hier_pipelined|
    # hier_border_rs|...) or a structural mode (hier_overlap|hier_zero1|
    # fsdp) wrapping one — see core.schedule.STRUCTURAL_MODES
    comm_mode: str = "hier"
    dcn_compression: str | None = None  # None|bf16|int8 (pod hop only)
    n_chunks: int = 4                 # pipelined mode
    # hier_overlap bucket size cap; defaults to the same constant the
    # planner-side bucket_sizes_for_volume uses, so a plan priced with
    # default caps describes the layout that actually executes
    bucket_cap_mb: int = overlap_lib.DEFAULT_CAP_BYTES >> 20
    # zero-copy packed gradient data path (core/packing.py, DESIGN.md
    # §11): one persistent trace-time layout, one pack + one unpack per
    # step, no per-bucket/per-chunk re-concatenation.  False keeps the
    # legacy per-step re-flatten (benchmarks A/B both).
    packed: bool = True
    # per-pod gradient weights for the skew-aware uneven batch split
    # (core/skew.py SkewSplit.weights: mean 1 over pods).  The weighted
    # sync keeps psum(w*g)/n_dp the exact global-batch mean gradient
    # when pod c holds weight*batch/n_pods of the samples.  None = even.
    cluster_weights: tuple[float, ...] | None = None
    # planner.CommPlan: when set, the collectives resolve mode/chunks/
    # compression per gradient bucket from the plan (--plan auto) and the
    # hand-picked fields above only steer the optimizer wiring
    # (hier_zero1/fsdp structure cannot be chosen per bucket).
    plan: Any = None
    # donation-safe bad-step handling: when the synced loss or grad norm
    # comes back non-finite (a NaN payload off the wire, a numerics
    # blowup), the update is gated to a no-op *inside* the compiled step
    # — the old values flow through into the donated output buffers, so
    # the driver's watchdog "skip" verdict can adopt them without
    # needing the (already-donated) previous state.  Healthy steps are
    # bit-identical: where(True, new, old) selects new exactly.
    finite_gate: bool = True
    opt: opt_lib.OptConfig = dataclasses.field(default_factory=opt_lib.OptConfig)
    aux_weight: float = 1e-2          # MoE load-balance loss weight
    z_loss: float = 0.0

    def comm_config(self, rt: Runtime):
        if self.plan is not None:
            return self.plan
        # structural modes (overlap chain / ZeRO-1 / fsdp) wrap the hier
        # schedule; every other comm_mode IS a schedule-builder mode —
        # build once eagerly so an unknown mode fails here with the
        # registry's error, not inside the jitted step
        mode = STRUCTURAL_MODES.get(self.comm_mode, self.comm_mode)
        build_schedule("all_reduce", mode, self.n_chunks,
                       self.dcn_compression)
        return CommConfig(mode=mode, pod_axis=rt.pod_axis,
                          intra_axis=rt.dp_axis or "data",
                          n_chunks=self.n_chunks,
                          compression=self.dcn_compression,
                          cluster_weights=self.cluster_weights)


def _spec_has(spec, name: str) -> bool:
    return any(s == name or (isinstance(s, tuple) and name in s)
               for s in (spec or ()))


def _global_grad_norm(grads, specs, rt: Runtime):
    """Global L2 norm respecting each leaf's sharding: each bucket of
    leaves gets one psum over exactly the axes it is sharded on."""
    buckets: dict[tuple, Any] = {}
    for g, s in zip(jax.tree.leaves(grads), jax.tree.leaves(specs)):
        axes = []
        if rt.tp_axis and _spec_has(s, "model"):
            axes.append(rt.tp_axis)
        if rt.fsdp_axis and _spec_has(s, "data"):
            axes.append(rt.fsdp_axis)
        key = tuple(axes)
        val = jnp.sum(g.astype(jnp.float32) ** 2)
        buckets[key] = buckets.get(key, 0.0) + val
    total = jnp.zeros((), jnp.float32)
    for axes, val in buckets.items():
        total = total + (lax.psum(val, axes) if axes else val)
    return jnp.sqrt(total)


def make_train_step(model: Model, tcfg: TrainConfig, mesh=None,
                    donate: bool = True):
    """Returns (step_fn, init_fn).

    Without a mesh both run single-device (smoke tests).  With a mesh,
    step_fn is jit(shard_map(...)) over the model's param specs.
    """
    rt = model.rt
    cfg = model.cfg
    ccfg = tcfg.comm_config(rt)
    dp_axes = rt.dp_axes

    def dp_size():
        if not dp_axes:
            return 1
        n = 1
        for ax in dp_axes:
            n = n * lax.psum(1, ax)
        return n

    def sync_grads(grads, specs):
        """The all-reduce gradient sync of every mode but hier_zero1."""
        if tcfg.comm_mode == "fsdp":
            # fsdp leaves arrive reduce-scattered over data (the
            # autodiff transpose of the per-layer all_gather = the
            # start homColl); the only explicit sync left is the
            # pod-axis c2cRed (+ optional int8/bf16 compression).
            def sync(g, s):
                if _spec_has(s, "data"):
                    if rt.pod_axis is None:
                        return g
                    w = None
                    if tcfg.cluster_weights is not None:
                        # the autodiff transpose already did the intra
                        # RS; the weight is constant within a pod, so
                        # scaling here is still the exact uneven-shard
                        # weighted reduction
                        w = jnp.asarray(tcfg.cluster_weights, jnp.float32)[
                            lax.axis_index(rt.pod_axis)]
                    if tcfg.dcn_compression:
                        # weight folds into the codec's scale vector
                        # (zero payload-sized HBM traffic)
                        return compression.compressed_psum(
                            g, rt.pod_axis, tcfg.dcn_compression, weight=w)
                    if w is not None:
                        g = g * w.astype(g.dtype)
                    return lax.psum(g, rt.pod_axis)
                return coll.hier_psum(g, ccfg) if dp_axes else g
            return jax.tree.map(sync, grads, specs)
        if tcfg.comm_mode == "hier_overlap" and dp_axes:
            # readiness-ordered bucket chain: XLA may overlap each
            # bucket's C2C with the backward ops still producing later
            # buckets (core/overlap.py)
            return overlap_lib.tree_hier_psum_overlap(
                grads, ccfg, cap_bytes=tcfg.bucket_cap_mb << 20,
                packed=tcfg.packed)
        if dp_axes:
            return coll.tree_hier_psum(grads, ccfg, packed=tcfg.packed)
        return grads

    # ---------------- the shard-local step body ---------------------------
    def step_body(params, opt_state, batch, specs):
        tokens, labels = batch["tokens"], batch["labels"]
        enc = batch.get("enc")

        def loss_fn(p):
            # inside value_and_grad, so that the backward reads
            # transpose(jvp(forward)) and the two stay apart in the HLO
            with jax.named_scope(scopes.FORWARD):
                logits, aux = model.apply_train(p, tokens, enc)
                l, metrics = loss_lib.sharded_xent(
                    logits, labels, rt, cfg.vocab_size, tcfg.z_loss)
            return l + tcfg.aux_weight * aux, (metrics, aux)

        (lval, (metrics, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)

        n_dp = dp_size()
        # ---- gradient synchronization: the paper's technique -------------
        if tcfg.comm_mode == "hier_zero1" and dp_axes:
            # AllReduceH with the end-AllGather fused into the parameter
            # reconstruction (ZeRO-1): RS(ICI) -> c2cRed(DCN) gives the
            # synced f32 shard that feeds Adam directly.
            with jax.named_scope(scopes.SYNC):
                shard, fmeta = coll.tree_hier_psum_scatter(grads, ccfg)
            # (the packed master layout groups leaves by wire dtype so
            # the sync and the reconstruction gather below run bf16
            # segments at 2 bytes/elem — collectives.FlatShardMeta)
            # grad norm on the scattered shard.  Replicated leaves
            # (norms/biases, <0.1% of params) appear once per TP column
            # and are over-counted x tp — documented approximation;
            # crucially identical on every device, so clipping stays
            # consistent.
            with jax.named_scope(scopes.OPTIMIZER):
                sq = jnp.sum(shard.astype(jnp.float32) ** 2)
                sq = lax.psum(sq, ccfg.intra_axis)
                if rt.tp_axis:
                    sq = lax.psum(sq, rt.tp_axis)
                gnorm = jnp.sqrt(sq) / n_dp
                clip = jnp.minimum(1.0, tcfg.opt.grad_clip / (gnorm + 1e-9))
                zstate = opt_lib.zero_update(shard, opt_state, tcfg.opt,
                                             clip / n_dp)
            with jax.named_scope(scopes.SYNC):
                new_params = coll.tree_hier_unscatter(zstate.flat_param,
                                                      fmeta, ccfg)
            new_opt = zstate
        else:
            with jax.named_scope(scopes.SYNC):
                grads = sync_grads(grads, specs)
            with jax.named_scope(scopes.OPTIMIZER):
                gnorm = _global_grad_norm(grads, specs, rt) / n_dp
                clip = jnp.minimum(1.0,
                                   tcfg.opt.grad_clip / (gnorm + 1e-9))
                new_params, new_opt = opt_lib.adam_update(
                    grads, opt_state, params, tcfg.opt, clip / n_dp)

        # gnorm is already the norm of the mean gradient (the synced sum
        # over n_dp replicas, divided once above)
        m = {"loss": lval, "grad_norm": gnorm, "aux": aux,
             "mean_logp": metrics["mean_logp"]}
        if dp_axes:
            with jax.named_scope(scopes.STEP_METRICS):
                m = {k: lax.pmean(v, dp_axes) for k, v in m.items()}
        if tcfg.finite_gate:
            # see TrainConfig.finite_gate: poisoned updates become
            # no-ops so donated buffers still carry the usable state.
            # The gate keys off the *reduced* scalars (a local-only NaN
            # would gate one shard and desync the others).
            with jax.named_scope(scopes.OPTIMIZER):
                ok = jnp.isfinite(m["loss"]) & jnp.isfinite(m["grad_norm"])
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new_params, params)
                new_opt = jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new_opt, opt_state)
        return new_params, new_opt, m

    # ---------------- init ------------------------------------------------
    def zero_bootstrap(params):
        """Build the ZeRO master shard from (local) params inside
        shard_map: pack per wire-dtype segment, slice this device's
        per-segment shard (the same persistent layout the scattered
        grad sync and the reconstruction gather use)."""
        shard, _ = coll.zero1_local_shard(params, ccfg)
        return opt_lib.zero_init_from_flatparam(shard)

    def init_fn(key):
        params = model.init(key)
        if tcfg.comm_mode == "hier_zero1" and dp_axes:
            return params, None  # bootstrap via make_zero_bootstrap
        return params, opt_lib.adam_init(params)

    donated = (0, 1) if donate else ()
    if mesh is None:
        def local_step(params, opt_state, batch):
            specs = jax.tree.map(lambda _: P(), params)
            return step_body(params, opt_state, batch, specs)

        return jax.jit(local_step, donate_argnums=donated), init_fn

    # ---------------- sharded wiring ---------------------------------------
    def named(spec_tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                            is_leaf=lambda s: isinstance(s, P))

    def state_specs(params_shape):
        specs = model.param_specs(params_shape)
        if tcfg.comm_mode == "hier_zero1":
            # the flat master varies across both data (scatter) and model
            # (TP shards flattened per column): 2D-shard its only dim.
            zspec = P((ccfg.intra_axis, "model") if rt.tp_axis else ccfg.intra_axis)
            return specs, opt_lib.ZeroState(zspec, zspec, zspec, P())
        return specs, opt_lib.AdamState(specs, specs, P())

    def sharded_init(key):
        """init_fn with every leaf created in place on its own shards
        (never the whole state on one device first)."""
        specs, opt_spec = state_specs(jax.eval_shape(model.init, key))
        if tcfg.comm_mode == "hier_zero1" and dp_axes:
            return jax.jit(model.init, out_shardings=named(specs))(key), None
        return jax.jit(init_fn, out_shardings=named((specs, opt_spec)))(key)

    def build(params_shape):
        model.prepare(params_shape)
        specs, opt_spec = state_specs(params_shape)
        batch_spec = {"tokens": P(dp_axes or None), "labels": P(dp_axes or None)}
        if cfg.n_enc_layers:
            batch_spec["enc"] = P(dp_axes or None)
        metric_spec = {"loss": P(), "grad_norm": P(), "aux": P(),
                       "mean_logp": P()}

        fn = shard_map(
            functools.partial(step_body, specs=specs),
            mesh=mesh,
            in_specs=(specs, opt_spec, batch_spec),
            out_specs=(specs, opt_spec, metric_spec),
            check_vma=False)
        # explicit in/out shardings pin each donated input to the output
        # leaf with its own spec: left to inference, the donation pairing
        # can match a sharded input with a same-shaped replicated output
        step = jax.jit(
            fn, in_shardings=named((specs, opt_spec, batch_spec)),
            out_shardings=named((specs, opt_spec, metric_spec)),
            donate_argnums=donated)

        boot = None
        if tcfg.comm_mode == "hier_zero1":
            boot = jax.jit(shard_map(
                zero_bootstrap, mesh=mesh, in_specs=(specs,),
                out_specs=opt_spec, check_vma=False))
        return step, boot

    return build, sharded_init
