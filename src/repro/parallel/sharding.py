"""Explicit-SPMD sharding helpers (Megatron-style TP/SP + FSDP).

All model code runs inside ``jax.shard_map`` with ``check_vma=False``,
so replication is *not* tracked and autodiff will not insert collectives
for us.  The two custom-vjp helpers below carry the TP semantics:

  * ``copy_to_tp``     — fwd identity, bwd psum over the TP axis.
                         Marks activations entering a TP-parallel region
                         (each shard consumes the same x; the cotangents
                         from the shards must be summed).
  * ``reduce_from_tp`` — fwd psum over the TP axis, bwd identity.
                         Marks partial outputs leaving a row-parallel
                         matmul.

Sequence parallelism swaps the (AR) pair for (AG, RS), whose transposes
JAX already knows (they are each other), so ``gather_sp``/``scatter_sp``
are thin lax wrappers.  FSDP parameter gathering uses raw
``lax.all_gather`` whose transpose (psum_scatter) is exactly the ZeRO
gradient reduce-scatter — the paper's AllReduceH start step falls out of
autodiff for free (DESIGN.md §5).

Everything degrades to identity when the axis is ``None`` so the same
model code runs single-device (smoke tests) and sharded (dry-run).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Static distribution context threaded through the model code."""

    tp_axis: str | None = None      # tensor-parallel axis ("model")
    fsdp_axis: str | None = None    # param-sharding axis ("data")
    dp_axis: str | None = None      # batch axis ("data" or ("pod","data"))
    pod_axis: str | None = None     # cluster axis ("pod")
    tp_size: int = 1                # static size of tp axis (for padding)
    sp: bool = False                # Megatron sequence parallelism
    remat: bool = True              # activation checkpointing per layer
    remat_policy: str = "none"      # none | save_collectives
    # Pallas model kernels; interpreted only on the CPU backend
    # (repro.kernels.pallas_interpret)
    use_pallas: bool = False
    moe_capacity_factor: float = 1.25
    # MoE expert-parallel dispatch/combine (models/moe.py ep path):
    # the planner-selected All2All schedule mode, the cluster axis of
    # the ep group (None on the standard mesh — experts shard over the
    # model axis only, so the a2a never crosses pods), and the skew
    # per-cluster weights steering expert capacity (DESIGN.md §12)
    moe_a2a_mode: str = "flat"
    moe_a2a_pod_axis: str | None = None
    moe_cluster_weights: tuple[float, ...] | None = None

    @property
    def dp_axes(self) -> tuple[str, ...]:
        axes: tuple[str, ...] = ()
        if self.pod_axis:
            axes += (self.pod_axis,)
        if self.dp_axis:
            axes += (self.dp_axis,)
        return axes


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with this repo's default ``check_vma=False``
    (replication is carried by the custom-vjp pairs below, not tracked
    by JAX).  Every shard_map in this repo goes through here."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# ---------------------------------------------------------------------------
# TP custom-vjp pairs
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tp(x: jax.Array, axis: str | None) -> jax.Array:
    return x


def _copy_fwd(x, axis):
    return x, None


def _copy_bwd(axis, _, g):
    if axis is None:
        return (g,)
    return (lax.psum(g, axis),)


copy_to_tp.defvjp(_copy_fwd, _copy_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_fwd_identity_bwd(x: jax.Array, axis: str | None) -> jax.Array:
    return x if axis is None else lax.psum(x, axis)


def _red_fwd(x, axis):
    return _psum_fwd_identity_bwd(x, axis), None


def _red_bwd(axis, _, g):
    return (g,)


_psum_fwd_identity_bwd.defvjp(_red_fwd, _red_bwd)


def reduce_from_tp(x: jax.Array, axis: str | None) -> jax.Array:
    """Row-parallel output reduction.  The result is tagged with a
    checkpoint name so the ``save_collectives`` remat policy can keep it
    and skip re-running the psum in the backward pass (selective
    activation recompute — Korthikanti et al., arXiv:2205.05198)."""
    from jax.ad_checkpoint import checkpoint_name
    out = _psum_fwd_identity_bwd(x, axis)
    return checkpoint_name(out, "tp_collective")


SAVE_COLLECTIVES_POLICY = jax.checkpoint_policies.save_only_these_names(
    "tp_collective")


def remat_policy_for(rt: "Runtime"):
    if rt.remat_policy == "save_collectives":
        return SAVE_COLLECTIVES_POLICY
    return None


# ---------------------------------------------------------------------------
# Sequence parallelism: activations sharded on the sequence dim between
# TP regions.  gather: (B, S/t, D) -> (B, S, D); scatter: partial sums
# (B, S, D) -> reduced (B, S/t, D).
# ---------------------------------------------------------------------------

def tp_entry_axis(rt: "Runtime") -> str | None:
    """Axis for copy_to_tp at a TP-region entry.  Under sequence
    parallelism the gather/scatter pair already carries the reduction
    semantics (gather_sp's transpose is psum_scatter); adding the
    copy_to_tp backward psum on top would double-reduce — a t x gradient
    overcount — so SP suppresses it."""
    return None if rt.sp else rt.tp_axis


def gather_sp(x: jax.Array, axis: str | None, dim: int = 1) -> jax.Array:
    if axis is None:
        return x
    return lax.all_gather(x, axis, axis=dim, tiled=True)


def scatter_sp(x: jax.Array, axis: str | None, dim: int = 1) -> jax.Array:
    if axis is None:
        return x
    return lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)


# ---------------------------------------------------------------------------
# FSDP parameter gather (per-layer, inside the scan body)
# ---------------------------------------------------------------------------

FSDP_MIN_SIZE = 2 ** 16  # leaves smaller than this stay replicated


def fsdp_dim(global_shape: tuple[int, ...], fsdp_size: int,
             taken_dims: tuple[int, ...] = ()) -> int | None:
    """Choose the dim an FSDP shard lives on: the largest dim divisible
    by the shard count, excluding dims already sharded by TP or the
    stacked-layer dim; None keeps the leaf replicated."""
    if fsdp_size <= 1:
        return None
    size = 1
    for s in global_shape:
        size *= s
    if size < FSDP_MIN_SIZE:
        return None
    cands = [d for d in range(len(global_shape))
             if d not in taken_dims and global_shape[d] % fsdp_size == 0]
    if not cands:
        return None
    return max(cands, key=lambda d: global_shape[d])


def fsdp_gather(params: Any, dims: Any, axis: str | None) -> Any:
    """All-gather the FSDP-sharded leaves of a local param subtree.

    ``dims`` mirrors ``params`` with the (local) dim index each leaf is
    FSDP-sharded on, or ``-1`` for replicated leaves (a sentinel, since
    None is an empty pytree to jax).  Computed once at init by the
    model's sharding rules and closed over, so it is static inside the
    layer scan.  Autodiff's transpose of the all_gather is psum_scatter
    — the ZeRO gradient reduce-scatter for free."""
    if axis is None:
        return params
    return jax.tree.map(
        lambda p, d: p if d < 0 else lax.all_gather(p, axis, axis=d, tiled=True),
        params, dims)
