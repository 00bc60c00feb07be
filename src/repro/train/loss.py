"""Vocab-sharded cross-entropy (Megatron-style).

The LM head produces logits sharded over the model axis on the vocab
dim; the softmax statistics are reduced with one pmax + one psum of
(B, S) scalars instead of ever materializing full logits.  Padded vocab
rows (vocab rounded up for even TP sharding) are masked out of the
logsumexp.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from functools import partial

from repro.parallel.sharding import Runtime, reduce_from_tp


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _pmax_nograd(x, axis):
    """pmax for the softmax max-shift: gradient-free by construction
    (the shift cancels in the softmax), and pmax has no JVP rule."""
    return lax.pmax(x, axis)


_pmax_nograd.defvjp(lambda x, axis: (lax.pmax(x, axis), None),
                    lambda axis, res, g: (jnp.zeros_like(g),))


def sharded_xent(logits: jax.Array, labels: jax.Array, rt: Runtime,
                 vocab_size: int, z_loss: float = 0.0):
    """logits: (B, S, Vl) f32 vocab-sharded; labels: (B, S) global ids.

    Returns (mean loss over local tokens, metrics dict).  Caller psums
    the loss over DP axes for reporting (grads sync separately).
    """
    B, S, Vl = logits.shape
    if rt.tp_axis is not None:
        shard = lax.axis_index(rt.tp_axis)
    else:
        shard = 0
    off = shard * Vl
    gid = off + jnp.arange(Vl)
    valid_col = gid < vocab_size
    neg = jnp.asarray(-1e30, logits.dtype)
    logits = jnp.where(valid_col[None, None, :], logits, neg)

    local_max = lax.stop_gradient(jnp.max(logits, axis=-1))
    gmax = _pmax_nograd(local_max, rt.tp_axis) if rt.tp_axis else local_max
    sumexp = jnp.sum(jnp.exp(logits - gmax[..., None]), axis=-1)
    # every TP rank computes the same (replicated) loss from these sums,
    # so the cotangent of each partial sum is the loss cotangent itself:
    # psum forward, identity backward (a plain psum would transpose to a
    # psum and scale every gradient by the TP size)
    if rt.tp_axis:
        sumexp = reduce_from_tp(sumexp, rt.tp_axis)
    lse = jnp.log(sumexp) + gmax                        # (B, S)

    lbl_local = labels - off
    in_shard = (lbl_local >= 0) & (lbl_local < Vl)
    lbl_safe = jnp.clip(lbl_local, 0, Vl - 1)
    picked = jnp.take_along_axis(logits, lbl_safe[..., None], axis=-1)[..., 0]
    picked = jnp.where(in_shard, picked, 0.0)
    if rt.tp_axis:
        picked = reduce_from_tp(picked, rt.tp_axis)

    tok_mask = (labels >= 0) & (labels < vocab_size)
    nll = jnp.where(tok_mask, lse - picked, 0.0)
    if z_loss:
        nll = nll + jnp.where(tok_mask, z_loss * lse * lse, 0.0)
    n_tok = jnp.maximum(1, jnp.sum(tok_mask))
    loss = jnp.sum(nll) / n_tok
    acc_logit = picked - lse                             # log prob of label
    metrics = {"nll_sum": jnp.sum(nll), "n_tok": n_tok,
               "mean_logp": jnp.sum(jnp.where(tok_mask, acc_logit, 0.0)) / n_tok}
    return loss, metrics


def sharded_argmax(logits: jax.Array, rt: Runtime, vocab_size: int) -> jax.Array:
    """Greedy sampling from vocab-sharded logits: (B, S, Vl) -> (B, S)."""
    B, S, Vl = logits.shape
    shard = lax.axis_index(rt.tp_axis) if rt.tp_axis else 0
    off = shard * Vl
    gid = off + jnp.arange(Vl)
    logits = jnp.where((gid < vocab_size)[None, None, :], logits, -1e30)
    local_max = jnp.max(logits, axis=-1)
    local_arg = jnp.argmax(logits, axis=-1) + off
    if rt.tp_axis is None:
        return local_arg
    gmax = lax.pmax(local_max, rt.tp_axis)
    # break ties toward the smallest id: encode (is_max, -id) preference
    cand = jnp.where(local_max >= gmax, local_arg, jnp.int32(2 ** 30))
    return lax.pmin(cand, rt.tp_axis)
