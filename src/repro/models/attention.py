"""GQA attention: training, prefill (returns KV cache) and decode paths.

Head layout: q heads are padded to a multiple of the TP degree
(config.padded_heads); when n_kv < tp the single local KV head is shared
by all local Q heads (replicated-KV GQA).  Params hold *local* shards:

    wq (D, Hl*dh)   wk/wv (D, Kl*dh)   wo (Hl*dh, D)

Masks: causal, optional sliding window (Mistral/Hymba-style), or full
bidirectional (Whisper encoder); cross-attention takes explicit K/V
source.  The compute core dispatches to the Pallas flash kernel when
``rt.use_pallas`` (validated in interpret mode on CPU) and to the
reference jnp path otherwise.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.parallel.sharding import Runtime, copy_to_tp, reduce_from_tp, tp_entry_axis
from . import layers


class KVCache(NamedTuple):
    k: jax.Array          # (B, W, Kl, dh) — W = window or max seq
    v: jax.Array
    length: jax.Array     # () int32: tokens written so far (global position)

    @property
    def window(self) -> int:
        return self.k.shape[1]


def init_attention(key, cfg: ModelConfig, tp: int, dtype, cross: bool = False):
    """Global (pre-shard) attention params.  Q heads padded to a multiple
    of tp (padded columns of wq and rows of wo are zero-initialized so
    phantom heads start contributing nothing); KV heads padded when
    sharded (n_kv >= tp) or stored at true count when replicated."""
    D, dh = cfg.d_model, cfg.head_dim
    hp, kp = cfg.padded_heads(tp), cfg.padded_kv_heads(tp)
    ks = jax.random.split(key, 4)
    wq = layers.init_dense(ks[0], D, hp * dh, dtype)
    wk = layers.init_dense(ks[1], D, kp * dh, dtype)
    wv = layers.init_dense(ks[2], D, kp * dh, dtype)
    wo = layers.init_dense(ks[3], hp * dh, D, dtype,
                           scale=1.0 / math.sqrt(max(1, cfg.n_heads) * dh))
    if hp > cfg.n_heads:  # zero the phantom heads
        wq = wq.at[:, cfg.n_heads * dh:].set(0)
        wo = wo.at[cfg.n_heads * dh:, :].set(0)
    if kp > cfg.n_kv_heads:
        wk = wk.at[:, cfg.n_kv_heads * dh:].set(0)
        wv = wv.at[:, cfg.n_kv_heads * dh:].set(0)
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((hp * dh,), dtype)
        p["bk"] = jnp.zeros((kp * dh,), dtype)
        p["bv"] = jnp.zeros((kp * dh,), dtype)
    return p


def _kv_map_for_local_q(cfg: ModelConfig, rt: Runtime) -> jax.Array:
    """Replicated-KV path: index of the KV head each *local* Q head
    uses.  Global q head h -> kv head h * K // Hp (phantom heads wrap)."""
    tp = rt.tp_size
    hl = cfg.local_q_heads(tp)
    hp, K = cfg.padded_heads(tp), cfg.n_kv_heads
    base = lax.axis_index(rt.tp_axis) * hl if rt.tp_axis else 0
    qh = base + jnp.arange(hl)
    return jnp.clip(qh * K // hp, 0, K - 1)


def _project_qkv(p, xq, xkv, cfg: ModelConfig, rt: Runtime):
    """Returns q (B,Sq,hl,dh) and k/v (B,Skv,kl,dh) with hl % kl == 0
    after the replicated-KV gather, ready for grouped attention."""
    dh = cfg.head_dim
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    B, Sq = xq.shape[0], xq.shape[1]
    Skv = xkv.shape[1]
    q = q.reshape(B, Sq, -1, dh)
    k = k.reshape(B, Skv, -1, dh)
    v = v.reshape(B, Skv, -1, dh)
    if cfg.qk_norm:
        q, k = layers.rms_norm_head(q), layers.rms_norm_head(k)
    tp = rt.tp_size if rt.tp_axis else 1
    if cfg.kv_replicated(tp) and rt.tp_axis is not None:
        kv_map = _kv_map_for_local_q(cfg, rt)
        k = jnp.take(k, kv_map, axis=2)   # align one kv head per q head
        v = jnp.take(v, kv_map, axis=2)
    return q, k, v


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def sdpa_reference(q, k, v, *, causal: bool, window: int | None,
                   q_offset, kv_len=None) -> jax.Array:
    """Pure-jnp scaled-dot-product attention oracle.

    q: (B, Sq, H, dh); k/v: (B, Skv, K, dh) with H % K == 0.
    q_offset: scalar global position of q[0] (decode: cache length).
    kv_len: optional scalar count of valid kv positions (cache fill).
    """
    B, Sq, H, dh = q.shape
    Skv, K = k.shape[1], k.shape[2]
    k = _repeat_kv(k, H // K)
    v = _repeat_kv(v, H // K)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(dh)
    qpos = jnp.arange(Sq) + q_offset           # (Sq,)
    kpos = jnp.arange(Skv)                      # (Skv,)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    if kv_len is not None:
        mask &= kpos[None, :] < kv_len
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


CHUNKED_ATTN_MIN_KV = 2048


def chunked_attention(q, k, v, *, causal: bool, window: int | None,
                      q_offset, chunk: int = 512) -> jax.Array:
    """Memory-efficient attention (Rabe & Staats / flash-in-XLA): an
    online-softmax scan over KV chunks.  Peak live set is
    (B, H, Sq, chunk) instead of (B, H, Sq, Skv) — this is what the
    Pallas kernel does in VMEM, expressed for the XLA scheduler; used
    for long sequences when the kernel path is off (and it is the dry-
    run's memory shape on CPU)."""
    B, Sq, H, dh = q.shape
    Skv, K = k.shape[1], k.shape[2]
    rep = H // K
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nck = (Skv + pad) // chunk
    kc = k.reshape(B, nck, chunk, H, dh)
    vc = v.reshape(B, nck, chunk, H, dh)
    qf = q.astype(jnp.float32) / math.sqrt(dh)
    qpos = jnp.arange(Sq) + q_offset

    def step(carry, inp):
        m, l, acc = carry
        ci, kci, vci = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kci.astype(jnp.float32))
        kpos = ci * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < Skv
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        safe = m_new > -1e29
        p = jnp.exp(jnp.where(safe[..., None], s - m_new[..., None], -1e30))
        alpha = jnp.exp(jnp.where(safe, m - m_new, 0.0))
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vci.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, dh), jnp.float32)
    ks = jnp.moveaxis(kc, 1, 0)
    vs = jnp.moveaxis(vc, 1, 0)
    (m, l, acc), _ = lax.scan(jax.checkpoint(step),
                              (m0, l0, a0), (jnp.arange(nck), ks, vs))
    l = jnp.where(l == 0, 1.0, l)
    out = (acc / l[..., None]).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2)  # (B, Sq, H, dh)


def _attn_core(q, k, v, cfg: ModelConfig, rt: Runtime, *, causal: bool,
               q_offset, kv_len=None) -> jax.Array:
    window = cfg.sliding_window
    if rt.use_pallas and kv_len is None and q.shape[1] >= 128:
        from repro.kernels import ops as kops
        return kops.flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset)
    if kv_len is None and k.shape[1] >= CHUNKED_ATTN_MIN_KV:
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
    return sdpa_reference(q, k, v, causal=causal, window=window,
                          q_offset=q_offset, kv_len=kv_len)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def attention_train(p, x, cfg: ModelConfig, rt: Runtime, *,
                    positions=None, causal: bool = True,
                    x_cross=None, reduce: bool = True) -> jax.Array:
    """Full-sequence attention (training / encoder). x: (B, S, D).
    ``x_cross`` switches to cross-attention (no RoPE, as in Whisper)."""
    x = copy_to_tp(x, tp_entry_axis(rt))
    xkv = x if x_cross is None else copy_to_tp(x_cross, tp_entry_axis(rt))
    q, k, v = _project_qkv(p, x, xkv, cfg, rt)
    if x_cross is None and cfg.n_heads > 0:
        pos = positions if positions is not None \
            else jnp.arange(x.shape[1])[None, :]
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k = layers.apply_rope(k, pos, cfg.rope_theta)
    out = _attn_core(q, k, v, cfg, rt, causal=causal and x_cross is None,
                     q_offset=jnp.int32(0))
    B, S = x.shape[0], x.shape[1]
    out = out.reshape(B, S, -1) @ p["wo"]
    return reduce_from_tp(out, rt.tp_axis) if reduce else out


def attention_prefill(p, x, cfg: ModelConfig, rt: Runtime, cache: KVCache,
                      x_cross=None):
    """Prefill: run causal attention AND write the KV cache."""
    x = copy_to_tp(x, rt.tp_axis)
    xkv = x if x_cross is None else copy_to_tp(x_cross, rt.tp_axis)
    q, k, v = _project_qkv(p, x, xkv, cfg, rt)
    S = x.shape[1]
    if x_cross is None:
        pos = jnp.arange(S)[None, :]
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k = layers.apply_rope(k, pos, cfg.rope_theta)
    out = _attn_core(q, k, v, cfg, rt, causal=x_cross is None,
                     q_offset=jnp.int32(0))
    W = cache.window
    if x_cross is None:
        if S >= W:   # keep last W positions, rolled so slot == pos % W
            k_keep = jnp.roll(k[:, S - W:], S % W, axis=1)
            v_keep = jnp.roll(v[:, S - W:], S % W, axis=1)
            new = KVCache(k_keep.astype(cache.k.dtype),
                          v_keep.astype(cache.v.dtype), jnp.int32(S))
        else:
            zk = jnp.zeros_like(cache.k)
            new = KVCache(lax.dynamic_update_slice_in_dim(zk, k.astype(cache.k.dtype), 0, 1),
                          lax.dynamic_update_slice_in_dim(jnp.zeros_like(cache.v),
                                                          v.astype(cache.v.dtype), 0, 1),
                          jnp.int32(S))
    else:            # cross-attention cache: static K/V from encoder
        new = KVCache(k.astype(cache.k.dtype), v.astype(cache.v.dtype),
                      jnp.int32(k.shape[1]))
    B = x.shape[0]
    out = out.reshape(B, S, -1) @ p["wo"]
    return reduce_from_tp(out, rt.tp_axis), new


def attention_decode(p, x, cfg: ModelConfig, rt: Runtime, cache: KVCache,
                     cross: bool = False):
    """One-token decode step. x: (B, 1, D).  Sliding-window caches use a
    ring buffer (position mod W); full caches use W = max seq."""
    x = copy_to_tp(x, rt.tp_axis)
    q, k, v = _project_qkv(p, x, x, cfg, rt)
    pos = cache.length                     # scalar global position
    if cross:
        # cross cache is read-only; attend over stored encoder K/V
        out = sdpa_reference(q, cache.k.astype(q.dtype), cache.v.astype(q.dtype),
                             causal=False, window=None, q_offset=pos,
                             kv_len=cache.length)
        new = cache
    else:
        q = layers.apply_rope(q, pos[None, None] if pos.ndim == 0 else pos,
                              cfg.rope_theta)
        k = layers.apply_rope(k, pos[None, None] if pos.ndim == 0 else pos,
                              cfg.rope_theta)
        W = cache.window
        slot = jnp.mod(pos, W)
        ck = lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                      (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                      (0, slot, 0, 0))
        # ring-aware mask: valid slots are the min(pos+1, W) most recent.
        n_valid = jnp.minimum(pos + 1, W)
        kpos = jnp.arange(W)
        # slot s holds global position: for full cache, s; for ring, the
        # largest g <= pos with g % W == s.
        gpos = jnp.where(kpos <= slot, pos - slot + kpos, pos - slot + kpos - W)
        valid = gpos >= jnp.maximum(0, pos + 1 - n_valid)
        if cfg.sliding_window is not None:
            valid &= gpos > pos - cfg.sliding_window
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            _repeat_kv(ck, q.shape[2] // ck.shape[2]).astype(jnp.float32))
        scores = scores / math.sqrt(cfg.head_dim)
        scores = jnp.where(valid[None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                         _repeat_kv(cv, q.shape[2] // cv.shape[2]).astype(jnp.float32))
        out = out.astype(x.dtype)
        new = KVCache(ck, cv, pos + 1)
    B = x.shape[0]
    out = out.reshape(B, 1, -1) @ p["wo"]
    return reduce_from_tp(out, rt.tp_axis), new


def make_cache(cfg: ModelConfig, batch: int, tp: int, seq_len: int,
               dtype=jnp.bfloat16, cross: bool = False,
               enc_seq: int = 0) -> KVCache:
    """Allocate an empty KV cache (local shapes given local batch).
    Replicated-KV configs cache the per-q-head gathered layout (hl
    heads); sharded-KV configs cache the local KV shard."""
    dh = cfg.head_dim
    if cfg.kv_replicated(tp):
        kl = cfg.local_q_heads(tp)
    else:
        kl = max(1, cfg.padded_kv_heads(tp) // max(1, tp))
    if cross:
        W = enc_seq
    elif cfg.sliding_window is not None:
        W = min(cfg.sliding_window, seq_len)
    else:
        W = seq_len
    return KVCache(jnp.zeros((batch, W, kl, dh), dtype),
                   jnp.zeros((batch, W, kl, dh), dtype), jnp.int32(0))
