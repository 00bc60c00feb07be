"""Persistent packed gradient data path (zero-copy comm buffers).

HetCCL wins bandwidth by keeping the collective data path free of
redundant staging work — pre-registered buffers, no per-message
repacking (§4.1, Fig. 5).  Our repro's equivalent waste was per-step
re-packing: every gradient sync rebuilt its flat buffer with fresh
``jnp.concatenate``s, re-padded for the intra shard, re-padded again
for the chunk pipeline, and re-padded a third time for the int8 block
codec.  This module computes **one persistent layout at trace time**
and bakes every downstream alignment into it, so the traced step
contains exactly one pack (a scatter of static-offset in-place leaf
writes into one buffer per wire dtype — ZERO concatenates) and one
unpack (static slices), and no collective ever re-pads or
re-concatenates (``tests/mdscripts/check_packed.py`` asserts the
jaxpr).

Layout rules:

  * **dtype-bucketed segments** — leaves keep their own dtype on the
    wire (a bf16 leaf costs 2 bytes/elem, never silently upcast to
    fp32; the old ``tree_flatten_f32`` doubled bf16 wire bytes).
  * **alignment baked in once** — each segment is zero-padded to
    ``world * n_chunks * block`` elements.  That is a multiple of
    ``lcm(world·n_chunks, block)`` chosen so every derived quantity
    stays aligned: the intra shard (``padded % world == 0``), the
    pipelined chunk split (``padded % (n_chunks·intra) == 0``), the
    per-chunk int8 shard (``padded / (n_chunks·intra)`` is a multiple
    of ``block``), and the border-RS pod scatter (the shard divides by
    the pod count).  Downstream code paths keep their legacy padding
    branches for unpacked callers, but on a packed buffer every one of
    them is a no-op.
  * **whole reduce-scatter spans** — a segment synced across chips and
    large enough that it costs under 1/64 of it is padded on to a
    multiple of ``world * RS_SPAN`` too (``padded_size``).  The TPU
    v5e compiler's reduce-scatter over two chips moves each shard in
    spans of 4864 rows of 128 lanes: a shard of whole spans stays one
    plain ``reduce-scatter``, any other it pads itself, as a fusion,
    then mends the shifted shard with a permute and two copies of it.
  * **bucket slices** — the overlap scheduler's readiness-ordered
    buckets are *aligned contiguous slices of the one packed buffer*
    (``PackedLayout.bucket_bounds``), replacing the per-bucket
    re-flatten of the old ``overlap._bucket_buffer``.

The layout core below is pure stdlib (dataclasses + integer
arithmetic) so the no-jax CI gate (``tools/check_schedule_cover.py``)
can import it; JAX is imported lazily inside the pack/unpack
executors only.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

# Block granularity of the int8 wire codec (== kernels.quant.BLOCK;
# duplicated as a plain int so the layout math stays importable without
# jax — tests assert the two constants agree).
DEFAULT_BLOCK = 1024

# Elements a span of the TPU's reduce-scatter emitter moves: 4864 rows of
# the 128 lanes ``primitives.hom_reduce_scatter`` scatters by.
RS_SPAN = 4864 * 128
# Segments of fewer than this many padding units keep the plain alignment.
_RS_SPAN_MIN = 64

_ITEMSIZE = {
    "float32": 4, "float64": 8, "bfloat16": 2, "float16": 2,
    "int32": 4, "int64": 8, "int16": 2, "int8": 1, "uint8": 1,
    "bool": 1,
}


def itemsize_of(dtype_name: str) -> int:
    """Bytes per element of a wire dtype.  Unknown dtypes raise rather
    than silently pricing at 4 bytes — a wrong itemsize would steer
    ``resolve_config`` to the wrong bucket and falsify the wire-byte
    regression numbers."""
    try:
        return _ITEMSIZE[dtype_name]
    except KeyError:
        raise ValueError(
            f"unknown wire dtype {dtype_name!r}: add it to "
            "packing._ITEMSIZE") from None


def aligned_size(n: int, align: int) -> int:
    """Smallest multiple of ``align`` >= n (0 stays 0)."""
    align = max(1, int(align))
    return -(-int(n) // align) * align


def comm_alignment(world: int, n_chunks: int = 1,
                   block: int = 1) -> int:
    """Element alignment that keeps every downstream data-path step
    pad-free: ``world·n_chunks·block`` (see module docstring for why
    each factor is needed).  ``block`` should be ``DEFAULT_BLOCK`` when
    the int8 codec may run and 1 otherwise."""
    return max(1, int(world)) * max(1, int(n_chunks)) * max(1, int(block))


def padded_size(used: int, align: int, world: int = 1) -> int:
    """Padded extent of a segment of ``used`` elements: a multiple of
    ``align`` and, where ``world > 1`` and the segment holds at least
    ``_RS_SPAN_MIN`` units of ``lcm(align, world * RS_SPAN)``, of that
    unit, so that every reduce-scatter shard is whole spans."""
    if world > 1:
        unit = math.lcm(max(1, int(align)), world * RS_SPAN)
        if used >= _RS_SPAN_MIN * unit:
            return aligned_size(used, unit)
    return aligned_size(used, align)


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one leaf (or stacked-layer piece) lives in the packed
    buffers: ``segment`` names the wire-dtype buffer, ``offset`` the
    element offset inside it.  ``index`` is the slot's position in the
    caller's flatten order; ``bucket`` the overlap bucket (or 0)."""

    index: int
    segment: str
    offset: int
    size: int
    shape: tuple
    dtype: str
    bucket: int = 0


@dataclasses.dataclass(frozen=True)
class Segment:
    """One wire-dtype buffer: ``used`` payload elements, zero-padded to
    ``padded`` (a multiple of the layout alignment)."""

    dtype: str
    used: int
    padded: int

    @property
    def wire_bytes(self) -> int:
        """Bytes this segment puts on the wire (per rank, pre-codec) —
        the dtype-preservation regression tests pin this."""
        return self.padded * itemsize_of(self.dtype)


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """The persistent trace-time layout: every slot's home, every
    segment's padded extent, and (for overlap packing) the aligned
    bucket boundaries within the single segment."""

    slots: tuple[LeafSlot, ...]
    segments: tuple[Segment, ...]
    align: int
    # (start, end) element bounds per overlap bucket in segments[0]
    bucket_bounds: tuple[tuple[int, int], ...] = ()

    def segment(self, dtype: str) -> Segment:
        for s in self.segments:
            if s.dtype == dtype:
                return s
        raise KeyError(dtype)

    @property
    def padded_total(self) -> int:
        return sum(s.padded for s in self.segments)

    @property
    def used_total(self) -> int:
        return sum(s.used for s in self.segments)

    def wire_bytes(self) -> dict[str, int]:
        return {s.dtype: s.wire_bytes for s in self.segments}

    def segment_bounds(self) -> tuple[tuple[str, int, int], ...]:
        """(dtype, start, end) element bounds of each segment inside
        the concatenated single-buffer (f32 master) view, in segment
        order."""
        out = []
        off = 0
        for s in self.segments:
            out.append((s.dtype, off, off + s.padded))
            off += s.padded
        return tuple(out)

    def validate(self) -> None:
        """Structural invariants (the pure-math CI gate runs this):
        per-segment slots are disjoint, in-bounds, and tightly packed;
        padding respects the alignment."""
        by_seg: dict[str, list[LeafSlot]] = {}
        for sl in self.slots:
            by_seg.setdefault(sl.segment, []).append(sl)
        for seg in self.segments:
            if seg.padded % self.align != 0:
                raise ValueError(
                    f"segment {seg.dtype}: padded {seg.padded} not a "
                    f"multiple of align {self.align}")
            if not seg.used <= seg.padded:
                raise ValueError(f"segment {seg.dtype}: used > padded")
            slots = sorted(by_seg.get(seg.dtype, ()),
                           key=lambda s: s.offset)
            off = 0
            for sl in slots:
                if sl.offset < off:
                    raise ValueError(
                        f"overlapping slots in segment {seg.dtype} at "
                        f"offset {sl.offset}")
                off = sl.offset + sl.size
            if off > seg.padded:
                raise ValueError(f"segment {seg.dtype}: slots exceed pad")


def plan_layout(metas: Sequence[tuple[str, tuple, int]], *,
                world: int = 1, n_chunks: int = 1,
                block: int = 1,
                align_for: Callable[[str, int], int] | None = None
                ) -> PackedLayout:
    """Build the persistent layout for leaves described by ``metas``
    (ordered ``(dtype_name, shape, size)`` tuples — exactly what
    ``jax.tree.flatten`` order gives the jax-side wrappers).

    Leaves are grouped into one segment per wire dtype, preserving
    their relative order; each segment is padded to the comm alignment
    (``align_for(dtype, used)`` overrides the default
    ``comm_alignment(world, n_chunks, block)`` per segment), and a large
    one to whole reduce-scatter spans (``padded_size``)."""
    default_align = comm_alignment(world, n_chunks, block)
    order: list[str] = []
    used: dict[str, int] = {}
    slots: list[LeafSlot] = []
    for idx, (dt, shape, size) in enumerate(metas):
        if dt not in used:
            used[dt] = 0
            order.append(dt)
        slots.append(LeafSlot(idx, dt, used[dt], int(size),
                              tuple(shape), dt))
        used[dt] += int(size)
    segments = []
    for dt in order:
        a = align_for(dt, used[dt]) if align_for is not None else default_align
        segments.append(Segment(dt, used[dt],
                                padded_size(used[dt], a, world)))
    # `align` records the weakest guarantee across segments (validate()
    # checks each segment against it)
    align = default_align if align_for is None else _gcd_all(
        [s.padded or 1 for s in segments])
    layout = PackedLayout(tuple(slots), tuple(segments), align)
    layout.validate()
    return layout


def _gcd_all(xs: Sequence[int]) -> int:
    g = 0
    for x in xs:
        g = math.gcd(g, int(x))
    return max(1, g)


def plan_bucket_layout(bucket_metas: Sequence[Sequence[tuple[str, tuple, int]]],
                       *, align: int | Sequence[int]) -> PackedLayout:
    """Layout for the overlap scheduler: every bucket's pieces are cast
    to f32 and laid out contiguously, each bucket padded to ``align``
    (one int, or one per bucket — buckets may run different schedules,
    e.g. different chunk counts per the planner) so its slice of the
    one buffer is directly collective-ready (``bucket_bounds``).  Slot
    order is bucket-major (readiness order)."""
    aligns = ([int(align)] * len(bucket_metas)
              if isinstance(align, int) else [int(a) for a in align])
    if len(aligns) != len(bucket_metas):
        raise ValueError("need one alignment per bucket")
    slots: list[LeafSlot] = []
    bounds: list[tuple[int, int]] = []
    off = 0
    idx = 0
    for bi, metas in enumerate(bucket_metas):
        start = off
        for dt, shape, size in metas:
            slots.append(LeafSlot(idx, "float32", off, int(size),
                                  tuple(shape), dt, bucket=bi))
            off += int(size)
            idx += 1
        off = start + aligned_size(off - start, aligns[bi])
        bounds.append((start, off))
    layout = PackedLayout(tuple(slots),
                          (Segment("float32", off, off),),
                          _gcd_all([max(1, a) for a in aligns]),
                          bucket_bounds=tuple(bounds))
    # bucket padding lives between slots, so used == padded per segment
    # but every bucket boundary is align-multiple by construction
    layout.validate()
    return layout


# ---------------------------------------------------------------------------
# Elastic shard remap (DESIGN.md §15)
# ---------------------------------------------------------------------------
#
# A ZeRO-1 rank's master shard is the per-segment concatenation of its
# slices: shard(r) = concat over segments of seg_buffer[r*per : (r+1)*per]
# with per = seg.padded // world (collectives.zero1_local_shard).  When
# the intra world changes (host loss / recovery), the new shards are a
# pure *slice remap* of the old ones through the slot map — every payload
# element keeps its (segment, in-segment offset) identity, only its
# (rank, in-shard offset) home moves.  No re-flatten, no repacking of
# leaves; the tail padding of each segment is zeros on both sides, so
# copying min(old.padded, new.padded) elements per segment is exact.

@dataclasses.dataclass(frozen=True)
class ShardRemapOp:
    """One contiguous host copy realizing part of the remap:
    ``new_shards[dst_rank][dst_offset:dst_offset+length] =
    old_shards[src_rank][src_offset:src_offset+length]``.  Offsets are
    in per-rank master-shard coordinates (per-segment bases included)."""

    dtype: str
    src_rank: int
    src_offset: int
    dst_rank: int
    dst_offset: int
    length: int


def remap_shard_ops(old: PackedLayout, new: PackedLayout, *,
                    old_world: int, new_world: int
                    ) -> tuple[tuple[ShardRemapOp, ...], ...]:
    """Copy ops mapping per-rank ZeRO-1 master shards from ``old``
    (sharded ``old_world``-way) to ``new`` (``new_world``-way), grouped
    per destination rank.  Raises ``ValueError`` when the layouts are
    not remappable — different leaf contents (the segments' (dtype,
    used) sequences differ, e.g. a TP resize changed the local leaves)
    or a world that does not divide a segment (the mesh shrank below
    the layout's divisibility) — the caller's cue to fall back to
    ``CheckpointManager.restore`` with new shardings."""
    old_world, new_world = int(old_world), int(new_world)
    if old_world < 1 or new_world < 1:
        raise ValueError(
            f"remap_shard_ops: worlds must be >= 1, got "
            f"{old_world} -> {new_world}")
    sig_old = [(s.dtype, s.used) for s in old.segments]
    sig_new = [(s.dtype, s.used) for s in new.segments]
    if sig_old != sig_new:
        raise ValueError(
            "remap_shard_ops: layouts describe different leaf contents "
            f"(old segments {sig_old} != new segments {sig_new}) — "
            "a slice remap cannot relate them; restore from checkpoint")
    for tag, lay, world in (("old", old, old_world), ("new", new, new_world)):
        for s in lay.segments:
            if s.padded % world != 0:
                raise ValueError(
                    f"remap_shard_ops: {tag} segment {s.dtype} padded "
                    f"{s.padded} is not divisible by world {world} — "
                    "mesh shrank below the layout's divisibility; "
                    "restore from checkpoint")
    per_old = [s.padded // old_world for s in old.segments]
    per_new = [s.padded // new_world for s in new.segments]
    ops: list[list[ShardRemapOp]] = [[] for _ in range(new_world)]
    base_old = 0
    base_new = 0
    for si, (seg_o, seg_n) in enumerate(zip(old.segments, new.segments)):
        po, pn = per_old[si], per_new[si]
        extent = min(seg_o.padded, seg_n.padded)
        p = 0
        while p < extent and po and pn:
            src_rank, src_in_seg = divmod(p, po)
            dst_rank, dst_in_seg = divmod(p, pn)
            length = min(extent - p, po - src_in_seg, pn - dst_in_seg)
            ops[dst_rank].append(ShardRemapOp(
                seg_o.dtype, src_rank, base_old + src_in_seg,
                dst_rank, base_new + dst_in_seg, length))
            p += length
        base_old += po
        base_new += pn
    return tuple(tuple(rank_ops) for rank_ops in ops)


def apply_remap_ops(ops, old_shards, new_shard_size: int):
    """Execute :func:`remap_shard_ops` on host arrays: ``old_shards``
    is the list of old per-rank 1-D buffers; returns the zero-initialized
    new per-rank buffers with every op applied.  numpy is imported
    lazily like the JAX executors below, keeping the layout core
    importable by the no-jax CI gate."""
    import numpy as np
    if not old_shards:
        return []
    dtype = np.asarray(old_shards[0]).dtype
    out = [np.zeros(int(new_shard_size), dtype) for _ in range(len(ops))]
    for rank_ops in ops:
        for op in rank_ops:
            src = np.asarray(old_shards[op.src_rank])
            out[op.dst_rank][op.dst_offset:op.dst_offset + op.length] = \
                src[op.src_offset:op.src_offset + op.length]
    return out


# ---------------------------------------------------------------------------
# JAX executors (lazy import: the layout core above must stay loadable
# by the no-jax CI gate)
# ---------------------------------------------------------------------------

def tree_metas(leaves) -> list[tuple[str, tuple, int]]:
    """(dtype_name, shape, size) for arrays or ShapeDtypeStructs."""
    return [(str(lf.dtype), tuple(lf.shape), int(lf.size)) for lf in leaves]


def pack(layout: PackedLayout, leaves) -> dict[str, Any]:
    """Scatter-write ``leaves`` (in layout slot order) into one
    zero-initialised buffer per segment — one static-offset
    ``dynamic_update_slice`` per leaf via the slot map and NO
    concatenate (the jaxpr test counts zero; the old pack rebuilt each
    segment with a fused concatenate every step).  Each update consumes
    the previous buffer value, so XLA performs them in place; the
    output buffers feed donated comm steps, so the leaf writes land
    straight in the persistent comm allocation across steps.  The
    zero init keeps the tail pad summing away harmlessly downstream.
    (``kernels.quant.pack_slots_call`` is the explicit Pallas aliased
    twin of this scatter, and ``fused_pack_quant_call`` extends it
    with the one-pass int8 encode.)"""
    import jax.numpy as jnp
    from jax import lax
    out = {seg.dtype: jnp.zeros((seg.padded,), seg.dtype)
           for seg in layout.segments}
    for sl, lf in zip(layout.slots, leaves):
        out[sl.segment] = lax.dynamic_update_slice(
            out[sl.segment], lf.reshape(-1), (sl.offset,))
    return out


def pack_bucketed(layout: PackedLayout, pieces) -> Any:
    """Overlap variant of :func:`pack`: all pieces scatter-written (as
    f32) into the single bucket-sliced buffer — inter-bucket padding is
    just the untouched zero init, and again no concatenate."""
    import jax.numpy as jnp
    from jax import lax
    buf = jnp.zeros((layout.segments[0].padded,), jnp.float32)
    for sl, piece in zip(layout.slots, pieces):
        buf = lax.dynamic_update_slice(
            buf, piece.reshape(-1).astype(jnp.float32), (sl.offset,))
    return buf


def unpack(layout: PackedLayout, buffers: dict[str, Any]) -> list:
    """Static-slice every slot back out of its segment buffer (no
    concatenate, no dynamic slice — the one "unpack").  The slices read
    the finished comm buffer through an optimization barrier: left free
    to fuse them into a reduce-scatter/all-gather pair, the TPU compiler
    took minutes per layer on a full-width model (two 2048x11008 layers
    ran past half an hour on v5e), while one all-reduce was unaffected."""
    from jax import lax
    buffers = lax.optimization_barrier(buffers)
    leaves = []
    for sl in layout.slots:
        buf = buffers[sl.segment]
        piece = buf[sl.offset:sl.offset + sl.size].reshape(sl.shape)
        if str(piece.dtype) != sl.dtype:
            piece = piece.astype(sl.dtype)
        leaves.append(piece)
    return leaves
