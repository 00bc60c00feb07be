"""Mamba2 SSD chunk kernel (Pallas TPU).

The SSD algorithm splits into (a) an embarrassingly parallel per-chunk
part — the within-chunk "masked attention" y_diag and the chunk-state
outer products — and (b) a tiny sequential inter-chunk scan.  (a) is
the FLOP hot-spot (O(S·q·(n+p)) per head) and lives here as one fused
kernel over grid (batch, chunk, head): the (q x q) decay mask, the two
MXU contractions, and the state outer product never leave VMEM.  (b)
stays in jnp (ops.py) — it is O(S/q) steps over (p x n) states.

VMEM per grid step (q=128, p=64, n=128, f32):
  x (q,p) 32K, B/C (q,n) 64K each, L (q,q) 64K, scores (q,q) 64K,
  y (q,p) 32K, state (p,n) 32K  ->  ~0.4 MiB; MXU dims all 128-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import pallas_interpret

NEG = -1e30


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, st_ref, *, q: int):
    x = x_ref[0, 0, 0].astype(jnp.float32)          # (q, p)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (q,)
    A = a_ref[0].astype(jnp.float32)                 # scalar in (1,)
    B = b_ref[0, 0, 0].astype(jnp.float32)           # (q, n)
    C = c_ref[0, 0, 0].astype(jnp.float32)           # (q, n)

    dA = dt * A                                      # (q,)
    dA_cs = jnp.cumsum(dA)                           # (q,)

    seg = dA_cs[:, None] - dA_cs[None, :]            # (q_i, q_j)
    ii = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    seg = jnp.where(jj <= ii, seg, NEG)              # mask BEFORE exp
    L = jnp.exp(seg)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    xw = x * dt[:, None]                             # dt_j * x_j
    y = jax.lax.dot_general(scores, xw, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    decay_end = jnp.exp(dA_cs[-1] - dA_cs)           # (q,)
    bw = B * (decay_end * dt)[:, None]               # (q, n)
    st = jax.lax.dot_general(x, bw, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (p, n)
    st_ref[0, 0, 0] = st


def ssd_chunk_call(xc, dtc, A, Bc, Cc, *, interpret: bool | None = None):
    """xc: (b, nc, h, q, p); dtc: (b, nc, h, q); A: (h,);
    Bc/Cc: (b, nc, h, q, n)  ->  (y_diag (b,nc,h,q,p) f32,
    states (b,nc,h,p,n) f32)."""
    b, nc, h, q, p = xc.shape
    n = Bc.shape[-1]
    kernel = functools.partial(_ssd_chunk_kernel, q=q)
    return pl.pallas_call(
        kernel,
        grid=(b, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda bi, ci, hi: (bi, ci, hi, 0)),
            pl.BlockSpec((1,), lambda bi, ci, hi: (hi,)),
            pl.BlockSpec((1, 1, 1, q, n), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        interpret=pallas_interpret(interpret),
    )(xc, dtc, A, Bc, Cc)
