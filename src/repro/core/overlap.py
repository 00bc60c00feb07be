"""Overlap-aware gradient communication scheduling (beyond-paper).

The paper hides C2C cost *inside* one collective by software-pipelining
the DCN hop against the ICI phases (§4.3.2, Fig. 9).  On heterogeneous
clusters the bigger win — H2 (arXiv:2505.17548), HETHUB
(arXiv:2405.16256) — is hiding cross-cluster communication behind the
backward *compute* that is still producing the remaining gradients.
This module supplies both halves of that optimization:

  * **Scheduling model** — partition the parameter tree into
    readiness-ordered, size-capped gradient buckets
    (``partition_tree`` / ``bucket_sizes_for_volume``).  Buckets are
    ordered by when their gradients materialize during the backward
    pass: output-side leaves (lm_head, final_norm) first, decoder
    layers in reverse, encoder layers next (their cotangents only
    finish accumulating once the decoder backward is done), embeddings
    last.  ``core.planner.plan(..., backward_compute_s=...)`` prices
    this schedule and reports *exposed* comm time — the part of the
    sync that sticks out past the end of the backward pass.

  * **Execution** — ``tree_hier_psum_overlap`` syncs each bucket with
    the hierarchical collectives, chaining bucket i+1's input on bucket
    i's output through ``lax.optimization_barrier``.  Each bucket's
    collectives depend only on that bucket's gradients plus the
    previous bucket's sync, so XLA's latency-hiding scheduler is free
    to issue the early buckets' C2C traffic while the backward ops
    producing later buckets are still running — the chain pins the
    issue *order* to readiness order without inserting any arithmetic.

Sizes follow cost_model conventions: bytes, seconds.  Wire payloads are
f32 (the sync buffer is the f32 flat view of each bucket, mirroring
the ZeRO-1 master layout of ``collectives.FlatShardMeta``).

Execution rides the packed data path (``core/packing.py``, DESIGN.md
§11): the whole tree is packed ONCE into a single bucket-sliced buffer
whose per-bucket bounds are aligned for each bucket's resolved
schedule, and every bucket's sync runs on a *slice of that one buffer*
— replacing the old per-bucket re-flatten (one concatenate per bucket
per step) with one pack and one unpack.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from . import collectives, packing, scopes
from . import schedule as schedule_ir

# Default per-bucket payload cap.  Large enough that α costs amortize,
# small enough that the first bucket's sync can start well before the
# backward pass finishes (the H2/HETHUB sweet spot is tens of MiB).
DEFAULT_CAP_BYTES = 64 << 20

# Top-level param-tree keys whose gradients only materialize at the very
# end of the backward pass (consumed at the start of the forward pass).
_TAIL_KEYS = ("embed", "pos_emb", "enc_norm")
# Stacked per-layer subtrees, in *forward* order of execution.  Encoder
# runs first in forward, but its cotangents finish accumulating only
# after every decoder cross-attention has back-propagated, so encoder
# buckets sort after the decoder ones in readiness order.
_LAYER_KEYS = ("layers", "enc_layers")


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """One readiness-ordered gradient bucket.

    ``entries`` addresses slices of the top-level tree: ``(key, None,
    None)`` takes the whole subtree under ``key``; ``(key, lo, hi)``
    takes layers ``lo:hi`` of the stacked subtree under ``key``.
    ``nbytes`` is the f32 wire payload of the bucket's flat buffer.
    """

    index: int                       # 0 = first gradients ready
    nbytes: int
    entries: tuple[tuple[str, int | None, int | None], ...]


def _subtree_f32_bytes(subtree: Any) -> int:
    return sum(4 * lf.size for lf in jax.tree.leaves(subtree))


def _stacked_len(subtree: Any) -> int:
    leaves = jax.tree.leaves(subtree)
    return leaves[0].shape[0] if leaves else 0


def _group_reversed_layers(key: str, n_layers: int, per_layer_bytes: int,
                           cap_bytes: int) -> list[tuple[int, tuple]]:
    """Group layers [n-1 .. 0] into consecutive runs of <= cap bytes."""
    out = []
    per_group = max(1, cap_bytes // max(1, per_layer_bytes))
    hi = n_layers
    while hi > 0:
        lo = max(0, hi - per_group)
        out.append((per_layer_bytes * (hi - lo), ((key, lo, hi),)))
        hi = lo
    return out


def _group_keys(pairs: list[tuple[tuple, int]],
                cap_bytes: int) -> list[tuple[int, tuple]]:
    """Group (entry, nbytes) pairs into cap-respecting buckets at key
    granularity; a single oversized key stays one bucket (leaves are
    never split, so e.g. an untied lm_head bigger than the cap syncs
    whole — but at least it no longer drags the norms and every other
    head leaf into the same oversized bucket)."""
    out: list[tuple[int, tuple]] = []
    cur: list[tuple] = []
    cur_b = 0
    for entry, b in pairs:
        if cur and cur_b + b > cap_bytes:
            out.append((cur_b, tuple(cur)))
            cur, cur_b = [], 0
        cur.append(entry)
        cur_b += b
    if cur:
        out.append((cur_b, tuple(cur)))
    return out


def partition_tree(tree: Any, cap_bytes: int = DEFAULT_CAP_BYTES
                   ) -> tuple[BucketSpec, ...]:
    """Partition a param/grad tree (arrays or ShapeDtypeStructs) into
    readiness-ordered buckets.  ``tree`` must be a dict at the top level
    (the Model param layout); unknown keys are treated as output-side
    ("head") leaves, which is correct for norms and projection heads and
    conservative (scheduled earliest) for anything else.  The cap
    applies to every bucket kind at its natural granularity: head/tail
    buckets split between top-level keys, layer buckets between layers."""
    if not isinstance(tree, dict):
        raise TypeError("partition_tree expects the top-level param dict")
    head: list[tuple[tuple, int]] = []
    tail: list[tuple[tuple, int]] = []
    groups: list[tuple[int, tuple]] = []
    for key in tree:
        if key in _LAYER_KEYS:
            continue
        pair = ((key, None, None), _subtree_f32_bytes(tree[key]))
        (tail if key in _TAIL_KEYS else head).append(pair)
    for key in _LAYER_KEYS:           # decoder groups first (ready first)
        if key not in tree:
            continue
        n = _stacked_len(tree[key])
        if n == 0:
            continue
        per = max(1, _subtree_f32_bytes(tree[key]) // n)
        groups.extend(_group_reversed_layers(key, n, per, cap_bytes))

    buckets: list[BucketSpec] = []
    for nbytes, entries in (_group_keys(head, cap_bytes) + groups
                            + _group_keys(tail, cap_bytes)):
        buckets.append(BucketSpec(len(buckets), max(1, nbytes), entries))
    if not buckets:
        raise ValueError("empty parameter tree")
    return tuple(buckets)


def bucket_sizes_for_volume(total_bytes: int, n_layers: int,
                            cap_bytes: int = DEFAULT_CAP_BYTES) -> list[int]:
    """Launcher-side approximation of ``partition_tree`` when only the
    total gradient volume is known: the volume is spread evenly over
    ``n_layers`` and grouped in reverse under the cap.  Returns bucket
    payloads in readiness order (for ``planner.plan``)."""
    total = max(1, int(total_bytes))
    # never more layers than bytes: per-layer size stays >= 1 and the
    # remainder fold-in below stays non-negative
    n_layers = max(1, min(int(n_layers), total))
    per = total // n_layers
    sizes = [b for b, _ in _group_reversed_layers("layers", n_layers, per,
                                                  cap_bytes)]
    # fold rounding remainder into the last-ready bucket
    sizes[-1] += total - sum(sizes)
    return sizes


# ---------------------------------------------------------------------------
# Execution: chained bucketed AllReduceH
# ---------------------------------------------------------------------------

def _chain(x: jax.Array, token: jax.Array | None) -> jax.Array:
    """Make ``x`` depend on ``token`` without changing its value, so the
    consuming collective cannot be scheduled before the token's
    producer.  optimization_barrier is a pure scheduling edge — no
    arithmetic, bit-exact identity."""
    if token is None:
        return x
    x, _ = lax.optimization_barrier((x, token))
    return x


def _bucket_buffer(tree: Any, spec: BucketSpec) -> tuple[jax.Array, list]:
    """Flatten the bucket's slices into one f32 buffer; the returned
    meta lets ``_unbucket_buffer`` restore every piece."""
    parts = []
    meta = []          # (key, lo, hi, leaf_index, shape, dtype, size)
    for key, lo, hi in spec.entries:
        leaves = jax.tree.leaves(tree[key])
        for li, lf in enumerate(leaves):
            piece = lf if lo is None else lax.slice_in_dim(lf, lo, hi, axis=0)
            parts.append(piece.reshape(-1).astype(jnp.float32))
            meta.append((key, lo, hi, li, piece.shape, lf.dtype, piece.size))
    return jnp.concatenate(parts), meta


def _packed_bucket_plan(tree: Any, layout: Sequence[BucketSpec], cfg):
    """Enumerate bucket pieces in readiness order and compute the
    persistent bucket-sliced packed layout: each bucket's bound is
    aligned for the schedule that bucket resolves to, so its slice of
    the one buffer feeds ``hier_psum`` with zero re-padding."""
    world = collectives._dp_world(cfg)
    pieces: list[jax.Array] = []
    meta: list[tuple] = []     # (key, lo, li, shape, dtype, size)
    bucket_metas: list[list[tuple]] = []
    aligns: list[int] = []
    rcs: list = []             # resolved CommConfig per bucket
    for spec in layout:
        bm: list[tuple] = []
        for key, lo, hi in spec.entries:
            leaves = jax.tree.leaves(tree[key])
            for li, lf in enumerate(leaves):
                piece = lf if lo is None else lax.slice_in_dim(lf, lo, hi,
                                                               axis=0)
                pieces.append(piece)
                meta.append((key, lo, li, piece.shape, lf.dtype, piece.size))
                bm.append((str(lf.dtype), tuple(piece.shape),
                           int(piece.size)))
        bucket_metas.append(bm)
        # resolve ONCE per bucket, by the spec's payload: execution
        # must run exactly the schedule the slice was aligned for
        rc = collectives.resolve_config(cfg, spec.nbytes)
        rcs.append(rc)
        aligns.append(packing.comm_alignment(
            world, rc.n_chunks, collectives.wire_block(rc.compression)))
    return pieces, meta, rcs, packing.plan_bucket_layout(bucket_metas,
                                                         align=aligns)


def tree_hier_psum_overlap(tree: Any, cfg,
                           cap_bytes: int = DEFAULT_CAP_BYTES,
                           layout: Sequence[BucketSpec] | None = None,
                           packed: bool = True) -> Any:
    """Gradient sync: AllReduceH per readiness-ordered bucket, buckets
    chained so XLA issues their C2C traffic in readiness order and can
    overlap it with the backward compute still producing later buckets.

    ``cfg`` is a ``CommConfig`` or a planner ``CommPlan`` — each bucket
    resolves its own schedule by payload size (``resolve_config``), so
    a plan tuned on the same bucket layout drives execution directly.
    Numerically identical to ``tree_hier_psum`` up to f32 casting and
    reduction order (the conformance matrix asserts so).

    With ``packed`` (default) the tree is packed once and every bucket
    syncs a slice of the one buffer (zero-copy data path, DESIGN.md
    §11); ``packed=False`` keeps the legacy per-bucket re-flatten for
    A/B benchmarking.

    Overlap caveat: the single pack naively makes bucket 0's slice
    data-depend on the whole concatenate.  Bucket bounds align exactly
    with piece boundaries, so XLA's algebraic simplifier rewrites each
    ``slice(concatenate)`` to consume only that bucket's pieces and the
    readiness chain (the ``optimization_barrier`` edges below) remains
    the only cross-bucket dependency; if a backend ever fails to split
    the concat, exposure regresses silently (numerics are unaffected) —
    the legacy path is the escape hatch.
    """
    if layout is None:
        layout = partition_tree(tree, cap_bytes)
    pieces: dict[tuple, jax.Array] = {}
    token = None
    if packed:
        plist, meta, rcs, playout = _packed_bucket_plan(tree, layout, cfg)
        with scopes.scoped(schedule_ir.Pack):
            buf = packing.pack_bucketed(playout, plist)
        outs = []
        for (start, end), rc in zip(playout.bucket_bounds, rcs):
            seg = _chain(buf[start:end], token)
            out = collectives.hier_psum(seg, rc)
            token = lax.slice_in_dim(out, 0, 1)
            outs.append(out)
        # slice-only unpack: every slot reads straight from its own
        # bucket's output (bounds are known statically) — no rebuild of
        # the full payload
        starts = [s for s, _ in playout.bucket_bounds]
        with scopes.scoped(schedule_ir.Unpack):
            for sl, (key, lo, li, shape, dtype, size) in zip(playout.slots,
                                                             meta):
                off = sl.offset - starts[sl.bucket]
                piece = outs[sl.bucket][off:off + size]
                pieces[(key, lo, li)] = piece.reshape(shape).astype(dtype)
    else:
        for spec in layout:
            with scopes.scoped(schedule_ir.Pack):
                buf, meta = _bucket_buffer(tree, spec)
            buf = _chain(buf, token)
            out = collectives.hier_psum(buf, cfg)
            token = lax.slice_in_dim(out, 0, 1)
            off = 0
            with scopes.scoped(schedule_ir.Unpack):
                for key, lo, hi, li, shape, dtype, size in meta:
                    piece = lax.dynamic_slice_in_dim(out, off, size)
                    pieces[(key, lo, li)] = piece.reshape(shape).astype(dtype)
                    off += size

    # ---- reassemble the tree -------------------------------------------
    def rebuild(key: str) -> Any:
        leaves, treedef = jax.tree.flatten(tree[key])
        slots: dict[int, list[tuple[int, jax.Array]]] = {}
        whole: dict[int, jax.Array] = {}
        for (k, lo, li), piece in pieces.items():
            if k != key:
                continue
            if lo is None:
                whole[li] = piece
            else:
                slots.setdefault(li, []).append((lo, piece))
        out_leaves = []
        for li in range(len(leaves)):
            if li in whole:
                out_leaves.append(whole[li])
            else:
                runs = sorted(slots[li])      # ascending layer order
                out_leaves.append(jnp.concatenate([p for _, p in runs], axis=0))
        return jax.tree.unflatten(treedef, out_leaves)

    with scopes.scoped(schedule_ir.Unpack):
        return {key: rebuild(key) if any(k == key for k, _, _ in pieces)
                else tree[key] for key in tree}
