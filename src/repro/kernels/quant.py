"""Blockwise int8 quantize / dequantize (Pallas).

The codec behind the DCN-hop gradient compression and the disaggregated
KV-cache transfer: symmetric per-block int8 with an f32 scale.  On TPU
this fuses the amax reduction, scaling, rounding and clipping into one
VMEM pass per block (the jnp fallback materializes three HBM-sized
intermediates).  Block = 1024 lanes = 8 full 128-lane vregs.

Three kernel families (``core/compression.py`` is the consumer):

  * ``quant_int8_call`` — fused amax+scale+round+clip, one pass.  Used
    when the scale is local (standalone quantization, KV transfer).
  * ``amax_block_call`` + ``quant_scaled_call`` — the *shared-scale*
    collective codec: the per-block amax reduction is its own one-read
    pass so the scales can be ``pmax``'d across the axis (integer
    partial sums stay exact), then the quantize runs one fused
    read+write pass with the agreed scale.  The per-cluster gradient
    weight folds into the nb-sized scale vector (scale/w on the
    encode side ≡ multiplying the payload by w), so the schedule IR's
    ``Scale`` step costs zero payload-sized HBM traffic.
  * ``dequant_int8_call`` — decode; an optional ``gain`` folds any
    post-sum scalar (cluster scale epilogue, 1/n mean) into the same
    nb-sized scale multiply instead of a payload-sized pass.
  * ``pack_slots_call`` / ``fused_pack_quant_call`` — the fused packed
    data path: leaf slices are written straight into the persistent
    comm buffer via the ``PackedLayout`` slot map (aliased in-place
    writes, no per-step concatenate), and the quantize runs one
    amax+scale+round+clip pass over the packed blocks — bit-identical
    to the pack → amax → scaled-quant composition.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_interpret

BLOCK = 1024
# blocks per grid step: a multiple of int8's 32-row native tile, so every
# operand tiles natively on the TPU (the f32/int32 payload, the int8 wire
# and the (rows, 1) per-block scale column).  256 rows keep the largest
# double-buffered working set (int32 in + f32 out) at 4 MiB of VMEM.
ROWS = 256


def _row_spec(nb: int, width: int) -> pl.BlockSpec:
    """(rows, width) tiles over an (nb, width) array: ROWS rows per step,
    or the whole array when it is smaller (a block equal to the full
    dims is always legal).  A ragged last step reads padding rows whose
    writes are dropped, and every row is independent."""
    return pl.BlockSpec((min(nb, ROWS), width), lambda i: (i, 0))


def _grid(nb: int) -> tuple[int]:
    return (pl.cdiv(nb, ROWS),)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                       # (rows, BLOCK)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)        # (rows, 1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(x_ref.dtype)


def _amax_kernel(x_ref, a_ref):
    a_ref[...] = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)), axis=1,
                         keepdims=True)


def _quant_scaled_kernel(x_ref, s_ref, q_ref):
    x = x_ref[...].astype(jnp.float32)
    # an all-zero block can reach this kernel with scale 0 from callers
    # that skip the shared-scale clamp; dividing by it would put
    # NaN/inf on the wire, so guard exactly like _quant_kernel does
    # (the block is all zeros, so any positive scale encodes it as 0)
    s = s_ref[...]
    scale = jnp.where(s > 0, s, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)


def quant_int8_call(x: jax.Array, *, interpret: bool | None = None):
    """x: flat (N,) with N % BLOCK == 0 -> (q (nb, BLOCK) int8, s (nb,) f32)."""
    assert x.ndim == 1 and x.size % BLOCK == 0, x.shape
    nb = x.size // BLOCK
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=_grid(nb),
        in_specs=[_row_spec(nb, BLOCK)],
        out_specs=[_row_spec(nb, BLOCK), _row_spec(nb, 1)],
        out_shape=[jax.ShapeDtypeStruct((nb, BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(x.reshape(nb, BLOCK))
    return q, s[:, 0]


def amax_block_call(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """x: flat (N,) with N % BLOCK == 0 -> per-block |max| (nb,) f32.
    The one read pass of the shared-scale collective codec (the caller
    pmax'es the result across the comm axis before quantizing)."""
    assert x.ndim == 1 and x.size % BLOCK == 0, x.shape
    nb = x.size // BLOCK
    a = pl.pallas_call(
        _amax_kernel,
        grid=_grid(nb),
        in_specs=[_row_spec(nb, BLOCK)],
        out_specs=_row_spec(nb, 1),
        out_shape=jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        interpret=pallas_interpret(interpret),
    )(x.reshape(nb, BLOCK))
    return a[:, 0]


def quant_scaled_call(x: jax.Array, scale: jax.Array, *,
                      interpret: bool | None = None) -> jax.Array:
    """Quantize flat ``x`` with a caller-provided per-block scale
    (shared-scale codec): one fused scale+round+clip+cast pass.
    Cluster-weight folding happens in the nb-sized ``scale`` argument
    (pass ``scale / w``), never on the payload."""
    assert x.ndim == 1 and x.size % BLOCK == 0, x.shape
    nb = x.size // BLOCK
    return pl.pallas_call(
        _quant_scaled_kernel,
        grid=_grid(nb),
        in_specs=[_row_spec(nb, BLOCK), _row_spec(nb, 1)],
        out_specs=_row_spec(nb, BLOCK),
        out_shape=jax.ShapeDtypeStruct((nb, BLOCK), jnp.int8),
        interpret=pallas_interpret(interpret),
    )(x.reshape(nb, BLOCK), scale.reshape(nb, 1))


def _pack_leaf_kernel(off, n, buf_ref, leaf_ref, o_ref):
    # o_ref aliases buf_ref (input_output_aliases): only the leaf's
    # [off, off+n) span is written; the rest of the persistent comm
    # buffer — other leaves, the zero tail pad — is never touched, so
    # packing costs exactly one write of the leaf bytes, no
    # concatenate, no read-modify-write of the buffer.
    del buf_ref
    o_ref[pl.ds(off, n)] = leaf_ref[...].astype(o_ref.dtype)


def pack_slots_call(pieces, padded: int, dtype=jnp.float32, *,
                    buf: jax.Array | None = None,
                    interpret: bool | None = None):
    """Scatter-pack ``pieces = [(offset, leaf), ...]`` (offsets static,
    from the ``PackedLayout`` slot map) into one padded 1-D buffer with
    Pallas in-place writes.  ``buf`` is the persistent comm buffer to
    write into (zero-initialised when omitted — the tail pad must stay
    zero so downstream collectives sum it away harmlessly)."""
    if buf is None:
        buf = jnp.zeros((padded,), dtype)
    assert buf.shape == (padded,), buf.shape
    for off, leaf in pieces:
        flat = leaf.reshape(-1)
        buf = pl.pallas_call(
            functools.partial(_pack_leaf_kernel, int(off), flat.size),
            out_shape=jax.ShapeDtypeStruct((padded,), dtype),
            input_output_aliases={0: 0},
            interpret=pallas_interpret(interpret),
        )(buf, flat)
    return buf


def fused_pack_quant_call(pieces, padded: int, *,
                          interpret: bool | None = None):
    """Fused pack+quantize for a BLOCK-aligned segment: leaf slices are
    scattered straight into the comm buffer via the slot map (aliased
    in-place writes, no concatenate), then ONE amax+scale+round+clip
    pass per block writes the int8 wire payload.  Versus the two-pass
    composition (concatenate-pack → amax pass → scaled-quant pass) this
    saves a full payload read and the pack buffer churn; the quantized
    blocks and per-block scales are bit-identical to the composition
    (conformance rows assert so)."""
    assert padded % BLOCK == 0, padded
    buf = pack_slots_call(pieces, padded, jnp.float32, interpret=interpret)
    return quant_int8_call(buf, interpret=interpret)


def dequant_int8_call(q: jax.Array, s: jax.Array, *, dtype=jnp.float32,
                      gain: jax.Array | float | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Decode (nb, BLOCK) int8 (or the ring's int32 sums) with per-block
    scale ``s``.  ``gain`` is the fused epilogue: any post-sum scalar
    (cluster weight, 1/n mean) multiplies the nb-sized scale vector here
    instead of costing a payload-sized HBM pass after the decode."""
    nb = q.shape[0]
    if gain is not None:
        s = s * gain
    out = pl.pallas_call(
        _dequant_kernel,
        grid=_grid(nb),
        in_specs=[_row_spec(nb, BLOCK), _row_spec(nb, 1)],
        out_specs=_row_spec(nb, BLOCK),
        out_shape=jax.ShapeDtypeStruct((nb, BLOCK), dtype),
        interpret=pallas_interpret(interpret),
    )(q, s.reshape(nb, 1).astype(jnp.float32))
    return out.reshape(-1)
