"""Fused flash attention for TPU (Pallas): causal / sliding-window GQA.

FlashAttention-2 restructured for the TPU grid model: the KV-tile loop
is the innermost *sequential* grid dimension, with the running softmax
statistics (m, l) and the f32 accumulator carried in VMEM scratch
across grid steps — the standard TPU adaptation of the GPU algorithm
(no warp shuffles; the MXU consumes (block_q x dh) @ (dh x block_k)
tiles, dh padded to the 128-lane register width by the ops wrapper).

HBM traffic is O(S·dh) per head (Q, K, V, O read/written once); the
S x S score matrix lives only as a (block_q x block_k) VMEM tile —
this is what collapses the memory roofline term of the reference path.

Layout: q (B, H, Sq, dh); k/v (B, K, Skv, dh); grid (B, H, Sq/bq,
Skv/bk); the GQA head mapping h -> h*K//H happens in the BlockSpec
index maps, so KV tiles are fetched once per query-head group.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int | None,
                 block_q: int, block_k: int, seq_q: int, seq_k: int,
                 q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, dh)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, dh)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + q_offset
    kpos = ki * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                             # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    safe = m_new > NEG_INF / 2
    p = jnp.exp(jnp.where(safe, s - m_new, NEG_INF))
    alpha = jnp.exp(jnp.where(safe, m_prev - m_new, 0.0))
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         window: int | None = None, q_offset: int = 0,
                         block_q: int = 128, block_k: int = 128,
                         sm_scale: float | None = None,
                         valid_kv: int | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """q: (B, H, Sq, dh), k/v: (B, K, Skv, dh) -> (B, H, Sq, dh).

    Sq/Skv padded to block multiples by the caller (ops.py).  dh should
    be a multiple of 128 on real TPU; sm_scale carries the *pre-padding*
    1/sqrt(dh)."""
    B, H, Sq, dh = q.shape
    K, Skv = k.shape[1], k.shape[2]
    assert H % K == 0
    rep = H // K
    nq = -(-Sq // block_q)
    nk = -(-Skv // block_k)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(dh)

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_q=Sq,
        seq_k=valid_kv if valid_kv is not None else Skv,
        q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, qi, ki: (b, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, qi, ki: (b, h // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        scratch_shapes=[
            # running max / sum (bq, 1) and the f32 output accumulator
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=pallas_interpret(interpret),
    )(q, k, v)
