"""DCN-hop gradient compression (beyond-paper optimization).

The hierarchical breakdown makes the pod (DCN) hop carry tiny
1/intra_size shards; compressing *only that hop* shrinks the slowest
link's traffic 2–4x more while the lossless ICI phases keep full
precision.  Error feedback (Karimireddy et al., arXiv:1901.09847) keeps
SGD convergence: the quantization residual is added back into the next
step's gradient.

Codecs:
  * ``bf16`` — round-to-nearest bf16 on the wire (2x), lossless enough
               for grads that are already bf16-scaled.
  * ``int8`` — per-block symmetric int8 with an f32 scale (≈4x); the
               psum runs in int32 partial sums so the reduction is exact
               given the shared scale (scale = global max via pmax).

The int8 block codec is implemented by the fused Pallas kernels in
``kernels/quant.py`` (one read pass for the per-block amax, one fused
scale+round+clip+cast pass for the encode, one fused decode pass) when
running on TPU — ``REPRO_PALLAS_QUANT=1/0`` overrides the backend
default, and the jnp fallback mirrors the kernels bit-for-bit for CPU
emulation.  Payloads packed by ``core/packing.py`` arrive pre-aligned
to the BLOCK granularity, so the legacy zero-pad concatenate below is
a dead branch on the packed data path (asserted by the jaxpr test).

Cluster-weight folding (schedule IR ``Scale``, DESIGN.md §10/§11):
``compressed_psum(..., weight=w)`` applies the per-cluster gradient
weight *inside the codec* — on the nb-sized scale vector (encode side:
quantizing with ``scale/w`` ≡ multiplying the payload by ``w``; the
pmax'd shared scale covers ``w·x`` because per-block amax scales
linearly in ``w``) — so the weighted reduction costs zero extra
payload-sized HBM traffic.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import quant as _qk

from . import scopes

BLOCK = _qk.BLOCK          # scale granularity for int8
_CHUNK = BLOCK             # legacy alias (pre-packing callers)


def use_pallas() -> bool:
    """Whether the fused Pallas codec kernels run (TPU default;
    ``REPRO_PALLAS_QUANT`` forces either way — interpret-mode Pallas on
    CPU is correct but slow, so emulation defaults to the fused jnp
    mirror)."""
    env = os.environ.get("REPRO_PALLAS_QUANT")
    if env is not None:
        return env not in ("0", "false", "False", "")
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Block codec primitives (Pallas on TPU, fused-jnp mirror elsewhere).
# All take/return flat f32 payloads whose size % BLOCK == 0.
# ---------------------------------------------------------------------------

def _block_amax(xf: jax.Array) -> jax.Array:
    """Per-block |max| of flat f32 ``xf`` -> (nb,) f32 (one read pass)."""
    if use_pallas():
        return _qk.amax_block_call(xf)
    return jnp.max(jnp.abs(xf.reshape(-1, BLOCK)), axis=1)


def _encode_scaled(xf: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantize flat f32 ``xf`` with per-block ``scale`` -> (nb, BLOCK)
    int8 (one fused scale+round+clip+cast pass).  A zero scale (an
    all-zero block from a caller that skipped ``_shared_scale``'s
    clamp) divides as 1.0 — the block is all zeros anyway, so the guard
    only keeps NaN/inf off the wire."""
    if use_pallas():
        return _qk.quant_scaled_call(xf, scale)
    blocks = xf.reshape(-1, BLOCK)
    safe = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(blocks / safe[:, None]),
                    -127, 127).astype(jnp.int8)


def _decode(q: jax.Array, scale: jax.Array, gain=None) -> jax.Array:
    """Decode (nb, BLOCK) int8/int32 with per-block ``scale`` -> flat
    f32.  ``gain`` is the fused epilogue: post-sum scalars (cluster
    scale, 1/n mean) multiply the nb-sized scale vector, never the
    payload.  int32 is the ring accumulator's output — the Pallas
    kernel reads either width (it upcasts to f32 in-register), so the
    hot collective decode stays fused too."""
    if use_pallas() and q.dtype in (jnp.int8, jnp.int32):
        return _qk.dequant_int8_call(q, scale, gain=gain)
    if gain is not None:
        scale = scale * gain
    return (q.astype(jnp.float32) * scale[:, None]).reshape(-1)


def _shared_scale(amax: jax.Array, axis: str | None) -> jax.Array:
    if axis is not None:
        amax = lax.pmax(amax, axis)
    return jnp.where(amax > 0, amax / 127.0, 1.0)


def _flat_blocks(x: jax.Array) -> tuple[jax.Array, int]:
    """Flat f32 view padded to BLOCK.  Packed payloads
    (core/packing.py) are pre-aligned, so ``pad == 0`` and no
    concatenate is traced; the pad branch only serves legacy unpacked
    callers."""
    xf = x.astype(jnp.float32).reshape(-1)
    pad = (-xf.size) % BLOCK
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad,), jnp.float32)])
    return xf, pad


def _ring_int8_sum(q: jax.Array, axis: str) -> jax.Array:
    """Sum int8 payloads over ``axis`` with int8 on the wire: a reduce
    ring of ppermutes accumulating locally in int32."""
    world = lax.psum(1, axis)
    if world <= 1:
        return q.astype(jnp.int32)
    perm = [(i, (i + 1) % world) for i in range(world)]

    def body(_, acc_cur):
        acc, cur = acc_cur
        nxt = lax.ppermute(cur, axis, perm)          # int8 on the wire
        return acc + nxt.astype(jnp.int32), nxt

    summed, _ = lax.fori_loop(0, world - 1, body, (q.astype(jnp.int32), q))
    return summed


def compressed_psum(x: jax.Array, axis: str, codec: str,
                    weight: jax.Array | None = None) -> jax.Array:
    """All-reduce ``x`` over ``axis`` with wire compression.  Exposes
    the same signature as lax.psum on 1-D inputs; ``weight`` is this
    device's cluster gradient weight (the deferred ``Scale`` step),
    folded into the codec at zero payload cost (module docstring)."""
    if codec == "bf16":
        with jax.named_scope(scopes.ENCODE):
            if weight is not None:
                x = x * jnp.asarray(weight, x.dtype)  # fuses into the cast
            enc = x.astype(jnp.bfloat16)
        summed = lax.psum(enc, axis)
        with jax.named_scope(scopes.DECODE):
            return summed.astype(x.dtype)
    if codec == "int8":
        return _int8_psum(x, axis, weight=weight)
    raise ValueError(f"unknown codec {codec!r}")


def int8_encode(x: jax.Array, axis: str | None,
                weight: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Compress stage of the shared-scale collective codec: per-block
    amax → cluster-weight fold → cross-``axis`` pmax → quantize.
    Returns ``(q, scale)`` — the int8 wire payload and the shared
    per-block f32 scale the decode side needs.  Split out of
    ``_int8_psum`` so the pipelined chunk loop can carry the
    pre-quantized next chunk and overlap this stage with the previous
    chunk's ring transfer (``core/pipelined.py``)."""
    with jax.named_scope(scopes.ENCODE):
        xf, _ = _flat_blocks(x)
        amax = _block_amax(xf)
        if weight is not None:
            # amax(w·x) == w·amax(x) for w > 0: the weighted payload's
            # shared scale comes from the nb-sized vector, not a payload
            # pass
            weight = jnp.asarray(weight, jnp.float32)
            amax = amax * weight
        scale = _shared_scale(amax, axis)
        enc_scale = scale if weight is None else scale / weight
        return _encode_scaled(xf, enc_scale), scale


def int8_transfer(q: jax.Array, scale: jax.Array, axis: str, size: int,
                  dtype=jnp.float32) -> jax.Array:
    """Transfer stage: int8 reduce ring over ``axis`` + fused decode,
    sliced back to the caller's flat ``size``."""
    summed = _ring_int8_sum(q, axis)
    with jax.named_scope(scopes.DECODE):
        return _decode(summed, scale)[:size].astype(dtype)


def _int8_psum(x: jax.Array, axis: str,
               weight: jax.Array | None = None) -> jax.Array:
    """All-reduce with int8 WIRE bytes: the payload crosses the (DCN)
    axis as int8 via a reduce ring of ppermutes, accumulating locally in
    int32, with one shared f32 scale per block (pmax'd so the integer
    sums are exact).  A plain psum of int32 would quadruple the wire."""
    q, scale = int8_encode(x, axis, weight=weight)
    return int8_transfer(q, scale, axis, x.size, x.dtype).reshape(x.shape)


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Standalone per-block int8 quantization (local scale — the
    serving KV-cache transfer and the kernel reference path)."""
    xf, _ = _flat_blocks(x)
    if use_pallas():
        return _qk.quant_int8_call(xf)
    amax = _block_amax(xf)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return _encode_scaled(xf, scale), scale


def dequantize_int8(q: jax.Array, scale: jax.Array, size: int,
                    dtype=jnp.float32, gain=None) -> jax.Array:
    out = _decode(q, scale, gain=gain)[:size]
    return out.astype(dtype)


def psum_ef(x: jax.Array, residual: jax.Array, axis: str,
            codec: str) -> tuple[jax.Array, jax.Array]:
    """Error-feedback compressed all-reduce: the wire carries the
    compressed payload, the local quantization error is returned as the
    next step's residual.

        corrected = x + residual
        wire      = psum(encode(corrected))          # compressed payload
        residual' = corrected - decode(encode(corrected))
    """
    corrected = x + residual
    if codec == "bf16":
        enc = corrected.astype(jnp.bfloat16)
        summed = lax.psum(enc, axis).astype(x.dtype)
        return summed, corrected - enc.astype(corrected.dtype)
    if codec == "int8":
        cf, pad = _flat_blocks(corrected)
        scale = _shared_scale(_block_amax(cf), axis)
        q = _encode_scaled(cf, scale)
        local_dec = _decode(q, scale)
        summed = _decode(_ring_int8_sum(q, axis), scale)
        if pad:
            summed, local_dec = summed[:-pad], local_dec[:-pad]
        new_res = (corrected.reshape(-1).astype(jnp.float32) - local_dec)
        return (summed.reshape(x.shape).astype(x.dtype),
                new_res.reshape(x.shape).astype(residual.dtype))
    raise ValueError(codec)
