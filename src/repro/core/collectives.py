"""Heterogeneous collectives: Algorithm 1 + Table 7 as JAX functions.

Every global collective is the 3-step hierarchical breakdown

    start homColl (intra-pod, ICI)  ->  C2C (pod axis, DCN)  ->  end homColl

exposed next to a ``flat`` single-collective baseline so the schedule
can be A/B'd with everything else fixed (the paper's Gloo/flat-NCCL
comparisons).  All functions run inside shard_map.

This module is the *execution interpreter* of the cluster-level
schedule IR (``core/schedule.py``, DESIGN.md §9): the public ``hier_*``
entry points build the schedule for their ``CommConfig.mode`` and run
it step by step via ``primitives.py`` (``execute``).  New modes are
added by registering a schedule builder — no decomposition lives here.

The pytree entry points pack leaves into one flat buffer per wire dtype
before communicating (gradient bucketing): one α per phase instead of
one per leaf, and clean, parseable HLO for the roofline analysis.  The
packed data path (``core/packing.py``, DESIGN.md §11) computes that
layout once at trace time with every downstream alignment baked in —
bf16 leaves stay 2 bytes on the wire, the chunk pipeline and the int8
block codec never re-pad, and the traced step carries exactly one pack
concatenate and one slice-only unpack.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import compression, packing, primitives, scopes
from . import schedule as schedule_ir


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """How cross-device reduction/gather traffic is scheduled.

    mode — any string with a registered schedule builder
    (``core.schedule``); shipped modes:
      * ``flat``  — single native collective over all data-parallel axes
                    (the homogeneous-library emulation; baseline).
      * ``hier``  — paper-faithful AllReduceH: ReduceScatter(intra) ->
                    c2cRed(pod) -> AllGather(intra).
      * ``hier_pipelined`` — hier with the C2C step chunked and software-
                    pipelined against the intra steps (paper §4.3.2).
      * ``hier_border_rs`` — §4.3 border-communicator variant: the pod
                    hop becomes a combining reduce-scatter + shard
                    redistribution over the cluster ring (no Fig. 8
                    bounce hop on border-scarce clusters).
    compression: optional codec for the pod (DCN) hop only — ``bf16`` or
      ``int8`` (error feedback handled by the caller); beyond-paper.
    cluster_weights: per-pod gradient weights for the skew-aware uneven
      batch split (``core.skew``; DESIGN.md §10), normalized to mean 1
      over pods — one entry per pod-axis index.  The combining entry
      points pre-scale the payload locally (schedule IR ``Scale`` step)
      so every reduction stays the intrinsic vendor collective; ``None``
      means the even split (no scaling, bit-identical to before).
    """

    mode: str = "hier"
    pod_axis: str | None = "pod"
    intra_axis: str = "data"
    n_chunks: int = 4
    compression: str | None = None
    cluster_weights: tuple[float, ...] | None = None

    @property
    def dp_axes(self) -> tuple[str, ...]:
        return ((self.pod_axis,) if self.pod_axis else ()) + (self.intra_axis,)


def resolve_config(cfg, nbytes: int) -> CommConfig:
    """Per-bucket planner support: every collective entry point accepts
    either a plain ``CommConfig`` (one schedule for everything) or any
    object with a ``config_for(nbytes) -> CommConfig`` method — in
    practice a ``planner.CommPlan`` — which picks the schedule by the
    bucket's local payload size.  Duck-typed so core.collectives never
    imports core.planner (which imports this module)."""
    fn = getattr(cfg, "config_for", None)
    return cfg if fn is None else fn(int(nbytes))


def _cluster_weight_scalar(cfg: CommConfig) -> jax.Array:
    """This device's per-cluster gradient weight as an f32 scalar
    (uneven-shard weighted reduction, DESIGN.md §10)."""
    w = jnp.asarray(cfg.cluster_weights, jnp.float32)
    if cfg.pod_axis is None:
        if w.shape[0] != 1:
            raise ValueError(
                f"cluster_weights has {w.shape[0]} entries but the config "
                "has no pod axis (single cluster)")
        return w[0]
    psize = primitives.axis_size(cfg.pod_axis)
    if w.shape[0] != psize:
        raise ValueError(
            f"cluster_weights has {w.shape[0]} entries but the "
            f"{cfg.pod_axis!r} axis has {psize} pods")
    return w[lax.axis_index(cfg.pod_axis)]


def _apply_cluster_weight(x: jax.Array, cfg: CommConfig) -> jax.Array:
    """Scale by this device's per-cluster gradient weight.  The weight
    is constant within a cluster, so one local multiply before the
    first combining step keeps every downstream reduction an intrinsic
    vendor collective.  The schedule interpreter defers this multiply
    to the C2C stage (shard-sized data, or folded into the wire codec —
    zero extra payload-sized HBM traffic); this full-payload form only
    runs on the flat / single-cluster fallbacks."""
    if cfg.cluster_weights is None:
        return x
    return x * _cluster_weight_scalar(cfg).astype(x.dtype)


def _pad_to(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    pad = (-x.size) % multiple
    if pad:
        x = jnp.concatenate([x.reshape(-1), jnp.zeros((pad,), x.dtype)])
    return x.reshape(-1), pad


# ---------------------------------------------------------------------------
# The execution interpreter of the schedule IR (DESIGN.md §9)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _ExecCtx:
    """Mutable walk state: the pending wire codec (set by Compress /
    cleared by Decompress), the pod-alignment padding the border
    exchange legs round-trip, and the deferred cluster weight (set by
    Scale, consumed by the first combining C2C step — applied to the
    shard-sized payload or folded into the codec's scale vector, never
    a full-payload pass)."""
    codec: str | None = None
    pod_pad: int = 0
    weight: jax.Array | None = None


def _wire_cast(buf: jax.Array, codec: str | None, fn) -> jax.Array:
    """Run collective ``fn`` with the payload cast to the wire codec.
    Only bf16 composes with native combining collectives; int8 rides
    its own ring (`compression.compressed_psum`)."""
    if codec == "bf16":
        return fn(buf.astype(jnp.bfloat16)).astype(buf.dtype)
    return fn(buf)


def _exec_step(step: schedule_ir.Step, buf: jax.Array, cfg: CommConfig,
               ctx: _ExecCtx) -> jax.Array:
    intra, pod = cfg.intra_axis, cfg.pod_axis
    if isinstance(step, schedule_ir.Scale):
        if cfg.cluster_weights is None:
            return buf
        if pod is None:
            # single cluster: no C2C stage to fold into — apply now
            return _apply_cluster_weight(buf, cfg)
        # defer to the combining C2C step: the weight is constant within
        # a cluster and the intra phases are linear, so w·RS(x) == RS(w·x)
        # — applying it on the 1/intra_size shard (or inside the codec's
        # scale vector) costs zero payload-sized HBM traffic
        ctx.weight = _cluster_weight_scalar(cfg)
        return buf
    if isinstance(step, (schedule_ir.Pack, schedule_ir.Unpack)):
        # performed at the pytree entry points (core/packing.py); the
        # array-level interpreter receives an already-packed buffer
        return buf
    if isinstance(step, schedule_ir.Compress):
        ctx.codec = step.codec
        return buf
    if isinstance(step, schedule_ir.Decompress):
        ctx.codec = None
        return buf
    if isinstance(step, schedule_ir.BorderGather):
        # Fig. 8 bounce: a modeling artifact of border-NIC landing; on
        # the all-border TPU mapping the native combining collective
        # absorbs it (model-only — priced and simulated, never run).
        return buf
    if isinstance(step, schedule_ir.IntraReduceScatter):
        if step.model_only:
            return buf
        buf = primitives.apply_inject(buf, "intra_rs")
        return primitives.hom_reduce_scatter(buf, intra)
    if isinstance(step, (schedule_ir.IntraAllGather, schedule_ir.IntraBcast)):
        if getattr(step, "model_only", False):
            return buf
        return primitives.hom_all_gather(buf, intra)
    if isinstance(step, schedule_ir.C2CRed):
        if pod is None:
            return buf
        buf = primitives.apply_inject(buf, "c2c")
        w, ctx.weight = ctx.weight, None
        if step.scatter:
            # border-communicator leg 1: combining reduce-scatter over
            # the cluster ring — each cluster ends owning 1/P of the
            # shard, reduced by its *native* collective (no bounce hop)
            psize = primitives.axis_size(pod)
            ctx.pod_pad = (-buf.size) % psize
            if ctx.pod_pad:
                buf = jnp.concatenate(
                    [buf, jnp.zeros((ctx.pod_pad,), buf.dtype)])
            if w is not None:
                buf = buf * w.astype(buf.dtype)
            return _wire_cast(buf, ctx.codec,
                              lambda b: primitives.hom_reduce_scatter(b, pod))
        if ctx.codec is not None:
            # weight folds into the codec's nb-sized scale vector
            return compression.compressed_psum(buf, pod, ctx.codec, weight=w)
        if w is not None:
            buf = buf * w.astype(buf.dtype)
        return primitives.c2c_red(buf, pod)
    if isinstance(step, schedule_ir.C2CCpy):
        if pod is None:
            return buf
        buf = primitives.apply_inject(buf, "c2c")
        if step.gather:
            # border-communicator leg 2: ring-redistribute the owned,
            # fully reduced shards (values already codec-rounded, so the
            # wire cast is lossless here)
            out = _wire_cast(buf, ctx.codec,
                             lambda b: primitives.hom_all_gather(b, pod))
            if ctx.pod_pad:
                out = out[:-ctx.pod_pad]
                ctx.pod_pad = 0
            return out
        # AllGatherH's raw-shard pod ring: stacks pods on a leading dim
        return primitives.c2c_cpy(buf, pod)
    if isinstance(step, schedule_ir.ChunkLoop):
        from . import pipelined  # local import to avoid cycle
        w, ctx.weight = ctx.weight, None
        return pipelined.execute_chunk_loop(step, buf, cfg, weight=w)
    if isinstance(step, schedule_ir.Flat):
        raise ValueError("Flat steps are handled by the entry points")
    if isinstance(step, (schedule_ir.IntraAll2All,
                         schedule_ir.BorderExchange)):
        # the flat-buffer interpreter has no split/concat dims; the
        # token-dimension walker in hier_all_to_all executes these
        raise ValueError("All2All steps are handled by hier_all_to_all")
    raise NotImplementedError(f"no executor for step {step!r}")


def _exec_steps(steps, buf: jax.Array, cfg: CommConfig) -> jax.Array:
    ctx = _ExecCtx()
    for step in steps:
        # steps that only change ctx emit no op under their scope
        with scopes.scoped(type(step)):
            buf = _exec_step(step, buf, cfg, ctx)
    return buf


def _flat_psum(x: jax.Array, cfg: CommConfig) -> jax.Array:
    """The one native all-reduce over every data-parallel axis."""
    with scopes.scoped(schedule_ir.Flat):
        return lax.psum(primitives.apply_inject(
            _apply_cluster_weight(x, cfg), "flat"), cfg.dp_axes)


# ---------------------------------------------------------------------------
# AllReduceH on one array
# ---------------------------------------------------------------------------

def hier_psum(x: jax.Array, cfg: CommConfig) -> jax.Array:
    """Global all-reduce over (pod, intra) axes: build the mode's
    schedule and execute it (hier: the Table-7 breakdown — DCN cost per
    chip 2·(x.nbytes/intra_size)·(P-1)/P, an intra_size× reduction
    versus the flat single all-reduce)."""
    cfg = resolve_config(cfg, x.nbytes)
    sched = schedule_ir.build_schedule("all_reduce", cfg.mode, cfg.n_chunks,
                                       cfg.compression)
    if cfg.cluster_weights is not None:
        sched = schedule_ir.with_cluster_scale(sched)
    if any(isinstance(s, schedule_ir.Flat) for s in sched.steps):
        return _flat_psum(x, cfg)
    if cfg.pod_axis is None and sched.pipelined:
        # Degenerate 1-cluster pipeline: there is no C2C phase to hide,
        # so the chunk loop would only add α costs.  Plain intra psum.
        return _flat_psum(x, cfg)
    isize = primitives.axis_size(cfg.intra_axis)
    flat, pad = _pad_to(x.astype(x.dtype), isize)
    out = _exec_steps(sched.steps, flat, cfg)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


def hier_psum_scatter(x: jax.Array, cfg: CommConfig) -> jax.Array:
    """ReduceScatterH over the intra axis + c2cRed over pods: returns the
    per-device 1/intra_size flat shard, globally summed.  This is the
    ZeRO-1 entry: the end-AllGather is deferred to the param update."""
    cfg = resolve_config(cfg, x.nbytes)
    intra = cfg.intra_axis
    isize = primitives.axis_size(intra)
    flat, _ = _pad_to(x, isize)
    sched = schedule_ir.build_schedule("reduce_scatter", cfg.mode,
                                       cfg.n_chunks, cfg.compression)
    if cfg.cluster_weights is not None:
        sched = schedule_ir.with_cluster_scale(sched)
    if any(isinstance(s, schedule_ir.Flat) for s in sched.steps):
        with scopes.scoped(schedule_ir.Flat):
            shard = primitives.hom_reduce_scatter(
                _apply_cluster_weight(flat, cfg), intra)
            if cfg.pod_axis is not None:
                shard = lax.psum(shard, cfg.pod_axis)
        return shard
    # the scattered sync is not chunk-pipelined (there is no end phase
    # to overlap): interpret a ChunkLoop body sequentially
    steps, _ = sched.unrolled()
    return _exec_steps(steps, flat, cfg)


def hier_all_gather_flat(shard: jax.Array, cfg: CommConfig,
                         orig_size: int) -> jax.Array:
    """Inverse of hier_psum_scatter: AllGather the flat shard over the
    intra axis and trim padding (the deferred end homColl)."""
    out = primitives.hom_all_gather(shard, cfg.intra_axis)
    return out[:orig_size]


# ---------------------------------------------------------------------------
# AllGatherH (Table 7 row 2): c2cCpy of raw shards, then intra Bcast.
# ---------------------------------------------------------------------------

def hier_all_gather(x: jax.Array, cfg: CommConfig, gather_dim: int = 0) -> jax.Array:
    """Gather shards over (pod, intra) via the mode's schedule — for the
    hier family: pod-ring the *raw* shard first (C2CCpy; one copy
    crosses DCN, Table-7-optimal), then the intra AllGather doubles as
    the end Bcast (IntraBcast)."""
    cfg = resolve_config(cfg, x.nbytes)
    sched = schedule_ir.build_schedule("all_gather", cfg.mode, cfg.n_chunks,
                                       cfg.compression)
    flat_sched = any(isinstance(s, schedule_ir.Flat) for s in sched.steps)
    if flat_sched or cfg.pod_axis is None:
        return primitives.hom_all_gather(x, cfg.dp_axes, gather_dim)
    g = gather_dim
    steps, _ = sched.unrolled()    # the gather path is not chunk-pipelined
    pods = x[None]
    for step in steps:
        with scopes.scoped(type(step)):
            if isinstance(step, schedule_ir.C2CCpy):
                pods = primitives.c2c_cpy(x, cfg.pod_axis)    # (P, *x), DCN
            elif isinstance(step, schedule_ir.IntraBcast):
                pods = lax.all_gather(pods, cfg.intra_axis, axis=0,
                                      tiled=False)            # (D, P, *x)
                pods = jnp.swapaxes(pods, 0, 1)               # (P, D, *x)
    alld = jnp.moveaxis(pods, (0, 1), (g, g + 1))             # x[:g],P,D,x[g:]
    P_, D_ = primitives.axis_size(cfg.pod_axis), primitives.axis_size(cfg.intra_axis)
    new_shape = x.shape[:g] + (P_ * D_ * x.shape[g],) + x.shape[g + 1:]
    return alld.reshape(new_shape)


# ---------------------------------------------------------------------------
# All2AllH (paper §5): intra dispatch -> border exchange -> redistribute
# ---------------------------------------------------------------------------

def _block_transpose(x: jax.Array, axis: int, a: int, b: int) -> jax.Array:
    """View dimension ``axis`` (length a·b·m) as [a, b, m] blocks and
    swap to [b, a, m].  A local relayout (reshape + transpose), no
    communication — the token resort between the phases of the
    hierarchical All2All."""
    m = x.shape[axis] // (a * b)
    y = x.reshape(x.shape[:axis] + (a, b, m) + x.shape[axis + 1:])
    return jnp.swapaxes(y, axis, axis + 1).reshape(x.shape)


def hier_all_to_all(x: jax.Array, cfg: CommConfig, split_dim: int,
                    concat_dim: int) -> jax.Array:
    """Global All2All over (pod, intra) via the mode's schedule,
    value-identical to the flat ``lax.all_to_all`` over both axes
    (global rank order pod-major).  The ``hier_a2a`` decomposition:

      IntraAll2All(start)  — resort destination blocks along split_dim
            from global pod-major (p', d') to intra-major (d', p')
            [a local block transpose], then exchange over the intra
            axis: each rank ends holding the tokens its intra index is
            responsible for, grouped per destination pod.
      BorderExchange       — pairwise cross-cluster exchange over the
            pod axis of the destination-pod-contiguous blocks (when
            split and concat share an axis the intra exchange
            concatenated sender blocks onto it, so one more local
            block transpose regroups [D'', P'] -> [P', D'']).
      IntraAll2All(end)    — model-only: the pairwise exchange already
            lands tokens on their destination ranks here; the pricer
            and the simulator charge the general border-rank case.

    A BorderExchange with no preceding intra dispatch (the ``flat_a2a``
    reference, or the legacy ``hier`` C2CCpy decomposition) lowers to
    the one global exchange."""
    cfg = resolve_config(cfg, x.nbytes)
    sched = schedule_ir.build_schedule("all_to_all", cfg.mode, cfg.n_chunks,
                                       cfg.compression)
    flat_sched = any(isinstance(s, schedule_ir.Flat) for s in sched.steps)
    if flat_sched or cfg.pod_axis is None:
        return primitives.hom_all_to_all(x, cfg.dp_axes, split_dim, concat_dim)
    pod, intra = cfg.pod_axis, cfg.intra_axis
    P_ = primitives.axis_size(pod)
    D_ = primitives.axis_size(intra)
    steps, _ = sched.unrolled()     # the a2a path is not chunk-pipelined
    codec: str | None = None
    dispatched = False
    for step in steps:
        if isinstance(step, schedule_ir.Compress):
            codec = step.codec
        elif isinstance(step, schedule_ir.Decompress):
            codec = None
        elif isinstance(step, schedule_ir.IntraAll2All):
            if step.model_only:
                continue
            x = _block_transpose(x, split_dim, P_, D_)
            x = primitives.hom_all_to_all(x, intra, split_dim, concat_dim)
            dispatched = True
        elif isinstance(step, (schedule_ir.BorderExchange,
                               schedule_ir.C2CCpy)):
            if not dispatched:
                x = _wire_cast(x, codec, lambda b: primitives.hom_all_to_all(
                    b, (pod, intra), split_dim, concat_dim))
                continue
            if split_dim == concat_dim:
                x = _block_transpose(x, split_dim, D_, P_)
            x = _wire_cast(x, codec, lambda b: primitives.hom_all_to_all(
                b, pod, split_dim, concat_dim))
    return x


# ---------------------------------------------------------------------------
# Pytree entry points with dtype-bucketed fusion (packed data path)
# ---------------------------------------------------------------------------

def _dp_world(cfg) -> int:
    """Total data-parallel world size of ``cfg`` (CommConfig or
    CommPlan — both expose ``dp_axes``)."""
    world = 1
    for ax in cfg.dp_axes:
        world *= primitives.axis_size(ax)
    return world


def wire_block(compression_codec: str | None) -> int:
    """Block alignment the wire codec needs: the int8 codec quantizes
    in ``kernels.quant.BLOCK``-element blocks; everything else is
    block-free."""
    from repro.kernels import quant as _qk
    return _qk.BLOCK if compression_codec == "int8" else 1


def _comm_layout_resolved(leaves, cfg, world: int | None = None
                          ) -> tuple[packing.PackedLayout, dict]:
    """(layout, per-segment resolved CommConfig) for one gradient sync:
    one segment per wire dtype, each aligned for the schedule that
    segment will actually run.  The config is resolved ONCE — by the
    segment's unpadded payload — and returned so execution runs exactly
    the schedule the buffer was aligned for (re-resolving a planner
    ``CommPlan`` at the *padded* size could land on a neighboring
    bucket whose chunk count the alignment never baked in, silently
    reviving the legacy re-pads)."""
    if world is None:
        world = _dp_world(cfg)
    metas = packing.tree_metas(leaves)
    cfgs: dict[str, CommConfig] = {}

    def align_for(dt: str, used: int) -> int:
        c = resolve_config(cfg, used * packing.itemsize_of(dt))
        cfgs[dt] = c
        return packing.comm_alignment(world, c.n_chunks,
                                      wire_block(c.compression))

    layout = packing.plan_layout(metas, world=world, align_for=align_for)
    return layout, cfgs


def comm_layout(leaves, cfg, world: int | None = None) -> packing.PackedLayout:
    """The persistent packed layout for one gradient sync (see
    ``_comm_layout_resolved``)."""
    return _comm_layout_resolved(leaves, cfg, world)[0]


def _bucket(tree: Any) -> tuple[dict[Any, jax.Array], Any, list]:
    """Legacy per-step flatten: one 1-D buffer per dtype, rebuilt with
    fresh concatenates every call (kept as the unpacked baseline the
    benchmarks A/B against — the packed path replaces it)."""
    leaves, treedef = jax.tree.flatten(tree)
    buckets: dict[Any, list[jax.Array]] = {}
    meta = []
    for lf in leaves:
        buckets.setdefault(lf.dtype, []).append(lf.reshape(-1))
        meta.append((lf.dtype, lf.shape, lf.size))
    joined = {dt: jnp.concatenate(parts) for dt, parts in buckets.items()}
    return joined, treedef, meta


def _unbucket(joined: dict, treedef, meta) -> Any:
    # barrier: see packing.unpack (the same compile-time blowup)
    joined = lax.optimization_barrier(joined)
    offs = {dt: 0 for dt in joined}
    leaves = []
    for dt, shape, size in meta:
        off = offs[dt]
        leaves.append(lax.dynamic_slice_in_dim(joined[dt], off, size).reshape(shape))
        offs[dt] = off + size
    return jax.tree.unflatten(treedef, leaves)


def tree_hier_psum(tree: Any, cfg: CommConfig, packed: bool = True) -> Any:
    """Gradient sync: bucketed AllReduceH over the whole pytree.

    ``cfg`` may be a single ``CommConfig`` or a planner ``CommPlan``:
    each dtype bucket resolves its own schedule by flat-buffer size
    (``resolve_config``), so e.g. a small bf16 bucket can ride a
    compressed sequential hier while the f32 bulk is pipelined.

    ``packed`` (default) runs the zero-copy data path: the persistent
    ``core/packing.py`` layout bakes every downstream padding in once,
    so the traced step performs exactly one pack concatenate per wire
    dtype and a slice-only unpack, and no collective re-pads
    (DESIGN.md §11; asserted by ``tests/mdscripts/check_packed.py``).
    ``packed=False`` keeps the legacy per-step re-flatten for A/B."""
    if not packed:
        with scopes.scoped(schedule_ir.Pack):
            joined, treedef, meta = _bucket(tree)
        out = {dt: hier_psum(buf, cfg) for dt, buf in joined.items()}
        with scopes.scoped(schedule_ir.Unpack):
            return _unbucket(out, treedef, meta)
    leaves, treedef = jax.tree.flatten(tree)
    layout, cfgs = _comm_layout_resolved(leaves, cfg)
    with scopes.scoped(schedule_ir.Pack):
        bufs = packing.pack(layout, leaves)
    out = {dt: hier_psum(buf, cfgs[dt]) for dt, buf in bufs.items()}
    with scopes.scoped(schedule_ir.Unpack):
        return jax.tree.unflatten(treedef, packing.unpack(layout, out))


def tree_hier_psum_mean(tree: Any, cfg: CommConfig) -> Any:
    n = 1
    for ax in cfg.dp_axes:
        n = n * primitives.axis_size(ax)
    summed = tree_hier_psum(tree, cfg)
    return jax.tree.map(lambda g: (g / n).astype(g.dtype), summed)


# --- ZeRO-1 flat-shard view ------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatShardMeta:
    """Static metadata for the packed flat f32 master view of a pytree
    (ZeRO-1).  The master is the concatenation of per-wire-dtype
    segments (``core/packing.py`` layout, each segment aligned to
    ``intra_size·BLOCK``), sharded *per segment* over the intra axis —
    so the gradient ReduceScatter and the param-reconstruction
    AllGather can each run in the segment's own wire dtype (bf16
    leaves cost 2 bytes on both hops; the old single-f32-buffer layout
    silently doubled their wire bytes)."""
    treedef: Any
    layout: packing.PackedLayout
    total: int           # unpadded total elements across segments
    padded: int          # master length (sum of padded segments)


def _zero1_layout(leaves, intra_size: int) -> packing.PackedLayout:
    """The persistent master layout shared by the bootstrap, the
    scattered grad sync, and the param reconstruction: segments per
    wire dtype, aligned so every segment's intra shard is whole and the
    int8 codec (if the pod hop compresses) never re-pads."""
    return packing.plan_layout(packing.tree_metas(leaves),
                               world=max(1, int(intra_size)),
                               block=packing.DEFAULT_BLOCK)


def zero1_local_shard(tree: Any, cfg: CommConfig) -> tuple[jax.Array, FlatShardMeta]:
    """Bootstrap the ZeRO-1 f32 master shard from local params inside
    shard_map: pack per segment, cast f32, take this device's slice of
    each segment, concatenate once."""
    intra = cfg.intra_axis
    isize = primitives.axis_size(intra)
    rank = lax.axis_index(intra)
    leaves, treedef = jax.tree.flatten(tree)
    layout = _zero1_layout(leaves, isize)
    bufs = packing.pack(layout, leaves)
    parts = []
    for seg in layout.segments:
        ssz = seg.padded // isize
        parts.append(lax.dynamic_slice_in_dim(
            bufs[seg.dtype].astype(jnp.float32), rank * ssz, ssz))
    shard = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return shard, FlatShardMeta(treedef, layout, layout.used_total,
                                layout.padded_total)


def tree_hier_psum_scatter(tree: Any, cfg: CommConfig) -> tuple[jax.Array, FlatShardMeta]:
    """Grad sync for ZeRO-1: returns the summed flat f32 master shard
    (size padded/intra_size) plus metadata to reconstruct params.

    Segments are laid out per wire dtype but the *gradient reduction*
    runs in f32 for every segment — same accumulation numerics as the
    old single-f32-buffer path (summing bf16 grads in bf16 would be a
    silent precision regression, not a wire-format change).  The 2-byte
    bf16 wire win lands on the param-reconstruction AllGather
    (``tree_hier_unscatter``), where casting before vs after the gather
    is value-identical."""
    isize = primitives.axis_size(cfg.intra_axis)
    leaves, treedef = jax.tree.flatten(tree)
    layout = _zero1_layout(leaves, isize)
    with scopes.scoped(schedule_ir.Pack):
        bufs = packing.pack(layout, leaves)
    shards = [hier_psum_scatter(bufs[seg.dtype].astype(jnp.float32), cfg)
              for seg in layout.segments]
    shard = shards[0] if len(shards) == 1 else jnp.concatenate(shards)
    return shard, FlatShardMeta(treedef, layout, layout.used_total,
                                layout.padded_total)


def tree_hier_unscatter(shard: jax.Array, fmeta: FlatShardMeta,
                        cfg: CommConfig) -> Any:
    """Inverse of ``tree_hier_psum_scatter``: gather each segment's
    shard slice over the intra axis *in the segment's wire dtype* — a
    bf16 segment's reconstruction AllGather moves 2 bytes/elem where
    the old unconditional-f32 gather moved 4 — and slice the leaves
    back out."""
    intra = cfg.intra_axis
    isize = primitives.axis_size(intra)
    gathered: dict[str, jax.Array] = {}
    off = 0
    for seg in fmeta.layout.segments:
        ssz = seg.padded // isize
        piece = shard[off:off + ssz]
        off += ssz
        with scopes.scoped(schedule_ir.IntraAllGather):
            gathered[seg.dtype] = primitives.hom_all_gather(
                piece.astype(seg.dtype), intra)
    leaves = []
    with scopes.scoped(schedule_ir.Unpack):
        for sl in fmeta.layout.slots:
            buf = gathered[sl.segment]
            piece = buf[sl.offset:sl.offset + sl.size].reshape(sl.shape)
            if str(piece.dtype) != sl.dtype:
                piece = piece.astype(sl.dtype)
            leaves.append(piece)
    return jax.tree.unflatten(fmeta.treedef, leaves)
