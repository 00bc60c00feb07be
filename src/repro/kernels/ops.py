"""jit'd dispatch wrappers over the Pallas kernels.

Each op takes the model-layer layout, handles padding/transposes, calls
the kernel (interpreted on the CPU, compiled on the TPU —
``repro.kernels.pallas_interpret``), and exposes the
exact same semantics as the pure-jnp oracle in ref.py (tests sweep
shapes/dtypes and assert_allclose the two).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import flash_attention as _fa
from . import quant as _q
from . import ref
from . import ssd as _ssd


def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    q_offset=0, block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, Sq, H, dh); k/v: (B, Skv, K, dh) -> (B, Sq, H, dh).

    Model layout is sequence-major; the kernel wants head-major — the
    transposes fuse into the surrounding projections on TPU."""
    B, Sq, H, dh = q.shape
    Skv = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    # pad dh to the 128-lane width and seqs to block multiples
    qt, dpad = _pad_axis(qt, 3, 128)
    kt, _ = _pad_axis(kt, 3, 128)
    vt, _ = _pad_axis(vt, 3, 128)
    bq = min(block_q, max(16, 1 << (Sq - 1).bit_length()))
    bk = min(block_k, max(16, 1 << (Skv - 1).bit_length()))
    qt, qpad = _pad_axis(qt, 2, bq)
    kt, kpad = _pad_axis(kt, 2, bk)
    vt, _ = _pad_axis(vt, 2, bk)
    off = jnp.asarray(q_offset, jnp.int32) if not isinstance(q_offset, int) \
        else q_offset
    if not isinstance(off, int):
        # kernel needs a static offset; decode path uses the ref oracle
        return ref.attention(q, k, v, causal=causal, window=window,
                             q_offset=off)
    out = _fa.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                   q_offset=off, block_q=bq, block_k=bk,
                                   sm_scale=1.0 / (dh ** 0.5), valid_kv=Skv,
                                   interpret=interpret)
    out = out[:, :, :Sq, :dh]
    return jnp.swapaxes(out, 1, 2)


def ssd_chunked(x, dt, A, B, C, chunk: int = 128, h0=None,
                interpret: bool | None = None):
    """Same contract as ref.ssd_chunked: x (b,s,h,p), dt (b,s,h), A (h,),
    B/C (b,s,g,n) -> (y (b,s,h,p), final state (b,h,p,n))."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0
    nc, q = s // chunk, chunk
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2) if rep > 1 else B
    Ch = jnp.repeat(C, rep, axis=2) if rep > 1 else C

    # (b, nc, h, q, ·) layout for the kernel
    xc = jnp.moveaxis(x.reshape(b, nc, q, h, p), 3, 2)
    dtc = jnp.moveaxis(dt.astype(jnp.float32).reshape(b, nc, q, h), 3, 2)
    Bc = jnp.moveaxis(Bh.reshape(b, nc, q, h, n), 3, 2)
    Cc = jnp.moveaxis(Ch.reshape(b, nc, q, h, n), 3, 2)

    y_diag, states = _ssd.ssd_chunk_call(xc, dtc, A.astype(jnp.float32),
                                         Bc, Cc, interpret=interpret)

    # (b) inter-chunk recurrence in jnp: O(nc) steps on (p, n) states
    dA = dtc * A.astype(jnp.float32)[None, None, :, None]   # (b,nc,h,q)
    dA_cs = jnp.cumsum(dA, axis=3)
    chunk_decay = jnp.exp(dA_cs[..., -1])                    # (b,nc,h)
    init = jnp.zeros((b, h, p, n), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)

    def scan_fn(hprev, inp):
        dec, st = inp
        return hprev * dec[..., None, None] + st, hprev

    decs = jnp.moveaxis(chunk_decay, 1, 0)                   # (nc, b, h)
    sts = jnp.moveaxis(states, 1, 0)                         # (nc, b, h, p, n)
    h_last, h_before = lax.scan(scan_fn, init, (decs, sts))
    h_before = jnp.moveaxis(h_before, 0, 1)                  # (b, nc, h, p, n)

    in_decay = jnp.exp(dA_cs)                                # (b, nc, h, q)
    y_off = jnp.einsum("bchqn,bchq,bchpn->bchqp", Cc, in_decay, h_before)
    y = (y_diag + y_off)                                     # (b,nc,h,q,p)
    y = jnp.moveaxis(y, 2, 3).reshape(b, s, h, p)
    return y.astype(x.dtype), h_last


def causal_conv1d(x, w, bias=None, *, interpret: bool | None = None):
    """Depthwise causal conv; small filter — the jnp form already fuses
    into a few VPU ops, no dedicated kernel needed."""
    return ref.causal_conv1d(x, w, bias)


def quant_int8(x: jax.Array, *, interpret: bool | None = None):
    """x: any shape -> (q (nb, 1024) int8, scales (nb,), orig_size)."""
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % _q.BLOCK
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    q, s = _q.quant_int8_call(flat, interpret=interpret)
    return q, s, x.size


def dequant_int8(q, s, size: int, shape, dtype=jnp.float32, *,
                 interpret: bool | None = None):
    flat = _q.dequant_int8_call(q, s, dtype=dtype, interpret=interpret)
    return flat[:size].reshape(shape)
