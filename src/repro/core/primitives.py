"""Cluster-level primitives (paper §4.2.2, Table 4) as shard_map functions.

All functions here must be called *inside* a ``jax.shard_map`` region
whose mesh carries the axis names being passed.  On the TPU mapping:

  * ``homColl``  -> native XLA collectives over intra-pod axes (ICI).
  * ``c2cCpy``   -> chunk-wise ring exchange over the ``pod`` axis
                    (DCN), implemented with ``lax.ppermute`` so exactly
                    one copy of the data crosses pods and every chip
                    carries an equal slice (the border-rank load balance
                    of Fig. 7 — on v5e every chip has a DCN uplink, the
                    "all ranks are border ranks" case of §4.3.2).
  * ``c2cRed``   -> the pod-axis combining step.  Two implementations:
                    the TPU-idiomatic native DCN all-reduce, and the
                    mechanism-faithful P2P ring that accumulates the
                    peer cluster's shards (used by the pipelined path
                    for explicit chunk control).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# Fault-injection hook (chaos engine seam)
# ---------------------------------------------------------------------------
#
# The collective executors (core/collectives.py, core/pipelined.py) pass
# every payload about to enter a transport phase through
# ``apply_inject(buf, phase)``.  With no hook installed this is the
# identity and costs nothing at trace time.  The chaos engine
# (runtime/faults.py) installs a hook to corrupt payloads (NaN
# gradients, bit-flipped int8 blocks) *at trace time*: executors run
# inside jit/shard_map, so a hook only takes effect on functions traced
# while it is installed — the harness builds (and first-calls, which is
# when tracing happens) a dedicated faulted step inside the
# ``inject_hook`` context and uses it only on fault steps.
#
# Phases: "flat" (flat psum input), "intra_rs" (before the intra
# ReduceScatter), "c2c" (before a C2C reduce/copy), "chunk_c2c" (the
# encoded chunk entering the pipelined C2C transfer — for int8 this is
# the (q, scale) pair, which is how bit-flips land in real int8 blocks).

_INJECT_HOOK = None


@contextlib.contextmanager
def inject_hook(fn):
    """Install ``fn(buf, phase) -> buf`` as the payload-injection hook
    for the duration of the context.  Trace-time: see module note."""
    global _INJECT_HOOK
    prev = _INJECT_HOOK
    _INJECT_HOOK = fn
    try:
        yield
    finally:
        _INJECT_HOOK = prev


def apply_inject(buf, phase: str):
    """Pass a payload through the installed injection hook (identity
    when none is installed)."""
    if _INJECT_HOOK is None:
        return buf
    return _INJECT_HOOK(buf, phase)


# ---------------------------------------------------------------------------
# homColl — intra-cluster native collectives
# ---------------------------------------------------------------------------

def hom_psum(x: jax.Array, axis) -> jax.Array:
    return lax.psum(x, axis)


def hom_all_gather(x: jax.Array, axis, gather_dim: int = 0) -> jax.Array:
    return lax.all_gather(x, axis, axis=gather_dim, tiled=True)


# A 1-D operand is reduce-scattered as rows of ``RS_LANES``: on the TPU a
# 1-D array is stored in tiles of 8 x 128 elements, so a ``(rows, 128)``
# view whose shards are whole tiles is a bitcast of it, both ways.
RS_LANES = 128
_RS_TILE = 8 * RS_LANES


def hom_reduce_scatter(x: jax.Array, axis, scatter_dim: int = 0) -> jax.Array:
    """Tiled reduce-scatter over ``axis``.

    XLA's TPU compiler lowers a reduce-scatter of a 1-D operand as an
    all-reduce of the whole operand and a slice, twice the wire bytes of
    a reduce-scatter; a 2-D ``(rows, RS_LANES)`` operand keeps a true
    one.  So a 1-D operand whose length divides by ``n * 1024`` (``n``
    the axis size, above 1) is scattered as rows: split row-major,
    device ``i``'s rows are elements ``[i*len/n, (i+1)*len/n)``, the
    1-D shard.  Any other operand, and an axis of size 1, go as they
    are.  A shard that is not whole spans of the compiler's emitter
    (``packing.RS_SPAN``) it pads and mends itself, in a fusion; the
    packed layout pads large buffers to whole spans."""
    n = axis_size(axis)
    if x.ndim == 1 and n > 1 and x.shape[0] % (n * _RS_TILE) == 0:
        rows = lax.psum_scatter(x.reshape(-1, RS_LANES), axis,
                                scatter_dimension=0, tiled=True)
        return rows.reshape(-1)
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dim, tiled=True)


def hom_all_to_all(x: jax.Array, axis, split_dim: int, concat_dim: int) -> jax.Array:
    return lax.all_to_all(x, axis, split_axis=split_dim, concat_axis=concat_dim,
                          tiled=True)


# ---------------------------------------------------------------------------
# Ring helpers over the pod (cluster) axis
# ---------------------------------------------------------------------------

def _ring_perm(n: int, shift: int = 1) -> list[tuple[int, int]]:
    return [(i, (i + shift) % n) for i in range(n)]


def axis_size(axis) -> int:
    return lax.psum(1, axis)


def c2c_cpy(x: jax.Array, pod_axis: str) -> jax.Array:
    """Cluster-to-cluster copy: ring-gather the per-pod values over the
    pod axis.  Returns ``(n_pods, *x.shape)`` stacked in pod order.

    Exactly ``(n_pods - 1) * x.nbytes`` crosses the DCN per chip — the
    Table-7-optimal AllGather volume — because each chip only ever
    forwards single-pod-shard sized messages around the cluster ring.
    """
    n = axis_size(pod_axis)
    if n == 1:
        return x[None]
    my = lax.axis_index(pod_axis)

    def step(cur, _):
        nxt = lax.ppermute(cur, pod_axis, _ring_perm(n))
        return nxt, nxt

    # received[j] = shard of pod (my - 1 - j) mod n after j+1 ring hops.
    _, received = lax.scan(step, x, None, length=n - 1)
    slots = jnp.concatenate([x[None], received], axis=0)  # slot j: pod (my-j)%n
    return slots[(my - jnp.arange(n)) % n]  # realign to absolute pod order


def c2c_red(x: jax.Array, pod_axis: str) -> jax.Array:
    """Combining C2C step: sum the per-pod partial shards.  Uses the
    *native* combining collective over the pod axis — the reduction
    arithmetic runs inside the platform library, never in custom glue
    (the c2cRed discipline of §4.2.2)."""
    return lax.psum(x, pod_axis)


def c2c_red_ring(x: jax.Array, pod_axis: str) -> jax.Array:
    """Mechanism-faithful c2cRed: a cluster-level reduce ring.  Each hop
    ppermutes the running partial to the next cluster which accumulates
    it (paper Fig. 8 routes the incoming shard to a free offset and
    reduces with the border communicator's native Reduce; the
    accumulate here is the shard-local equivalent).  Used by the
    pipelined executor for explicit chunk scheduling; numerically equal
    to ``c2c_red`` (tests assert so)."""
    n = axis_size(pod_axis)

    def body(_, acc_cur):
        acc, cur = acc_cur
        nxt = lax.ppermute(cur, pod_axis, _ring_perm(n))
        return acc + nxt, nxt

    acc, _ = lax.fori_loop(0, n - 1, body, (x, x))
    return acc


def c2c_send_recv(x: jax.Array, pod_axis: str, shift: int = 1) -> jax.Array:
    """Heterogeneous SendRecv between adjacent clusters (PP handoff)."""
    n = axis_size(pod_axis)
    return lax.ppermute(x, pod_axis, _ring_perm(n, shift))


def c2c_bcast(x: jax.Array, pod_axis: str, root: int = 0) -> jax.Array:
    """Broadcast the root cluster's value to all clusters: only ``n``
    bytes leave the root (Table 7 BcastH row)."""
    n = axis_size(pod_axis)
    if n == 1:
        return x
    out = x
    # ring forward root's data n-1 hops; non-roots substitute received.
    def body(i, cur):
        nxt = lax.ppermute(cur, pod_axis, _ring_perm(n))
        keep_own = lax.axis_index(pod_axis) == root
        return jnp.where(keep_own, x, nxt)
    out = lax.fori_loop(0, n - 1, body, out)
    return out
