"""Mamba2 (SSD) block: projections + causal conv + chunked SSD + gate.

TP sharding: the inner dim (z, x) and the SSM heads are sharded over the
model axis; B/C group projections (g=1 for the assigned configs) and the
conv over their channels are replicated per device (tiny).  out_proj is
row-parallel with a TP psum.

Decode carries (conv_state (B, W-1, ch), ssm_state (B, Hl, P, N)).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.parallel.sharding import Runtime, copy_to_tp, reduce_from_tp, tp_entry_axis
from repro.kernels import ref as kref
from . import layers


class SSMState(NamedTuple):
    conv: jax.Array     # (B, W-1, ch_local)  last conv inputs
    ssm: jax.Array      # (B, Hl, P, N) f32
    length: jax.Array   # () int32


def init_ssm(key, cfg: ModelConfig, tp: int, dtype):
    """Global (pre-shard) params.  The conv over the x channels is
    TP-sharded with the inner dim; the conv over B/C channels is
    replicated — stored as separate depthwise stacks so each can carry
    its own PartitionSpec."""
    D = cfg.d_model
    di, hd, ns, g = cfg.d_inner, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    h = di // hd
    ks = jax.random.split(key, 7)
    cscale = 1.0 / math.sqrt(cfg.conv_width)
    p = {
        # in_proj split: z/x/dt columns TP-sharded, B/C replicated
        "w_z": layers.init_dense(ks[0], D, di, dtype),
        "w_x": layers.init_dense(ks[1], D, di, dtype),
        "w_bc": layers.init_dense(ks[2], D, 2 * g * ns, dtype),
        "w_dt": layers.init_dense(ks[3], D, h, dtype),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)),
        "D_skip": jnp.ones((h,), jnp.float32),
        "conv_w_x": (jax.random.normal(ks[4], (di, cfg.conv_width), jnp.float32)
                     * cscale).astype(dtype),
        "conv_b_x": jnp.zeros((di,), dtype),
        "conv_w_bc": (jax.random.normal(ks[5], (2 * g * ns, cfg.conv_width),
                                        jnp.float32) * cscale).astype(dtype),
        "conv_b_bc": jnp.zeros((2 * g * ns,), dtype),
        "norm_scale": jnp.ones((di,), dtype),
        "w_out": layers.init_dense(ks[6], di, D, dtype),
    }
    return p


def _split_conv_channels(cfg: ModelConfig, tp: int):
    di_l = cfg.d_inner // tp
    gn = cfg.ssm_groups * cfg.ssm_state
    return di_l, gn


def _ssd(x, dt, A, B, C, chunk, rt: Runtime, h0=None):
    if rt.use_pallas:
        from repro.kernels import ops as kops
        return kops.ssd_chunked(x, dt, A, B, C, chunk=chunk, h0=h0)
    return kref.ssd_chunked(x, dt, A, B, C, chunk=chunk, h0=h0)


def apply_ssm(p, x, cfg: ModelConfig, rt: Runtime, *, chunk: int = 128,
              state: SSMState | None = None, return_state: bool = False):
    """x: (B, S, D) -> (B, S, D) [, final SSMState]."""
    Bsz, S, D = x.shape
    x = copy_to_tp(x, tp_entry_axis(rt))
    tp = rt.tp_size if rt.tp_axis else 1
    di_l, gn = _split_conv_channels(cfg, tp)
    hd, ns, g = cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    h_l = di_l // hd

    z = x @ p["w_z"]                                  # (B, S, di_l)
    xs = x @ p["w_x"]                                 # (B, S, di_l)
    bc = x @ p["w_bc"]                                # (B, S, 2gn)
    dt_raw = x @ p["w_dt"]                            # (B, S, h_l)

    conv_in = jnp.concatenate([xs, bc], axis=-1)      # (B, S, di_l + 2gn)
    conv_w = jnp.concatenate([p["conv_w_x"], p["conv_w_bc"]], axis=0)
    conv_b = jnp.concatenate([p["conv_b_x"], p["conv_b_bc"]], axis=0)
    if state is not None:
        full = jnp.concatenate([state.conv.astype(conv_in.dtype), conv_in], axis=1)
        conv = kref.causal_conv1d(full, conv_w, conv_b)[:, -S:]
    else:
        if rt.use_pallas:
            from repro.kernels import ops as kops
            conv = kops.causal_conv1d(conv_in, conv_w, conv_b)
        else:
            conv = kref.causal_conv1d(conv_in, conv_w, conv_b)
    conv = jax.nn.silu(conv.astype(jnp.float32)).astype(conv_in.dtype)
    xs = conv[..., :di_l].reshape(Bsz, S, h_l, hd)
    Bmat = conv[..., di_l:di_l + gn].reshape(Bsz, S, g, ns)
    Cmat = conv[..., di_l + gn:].reshape(Bsz, S, g, ns)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    pad = (-S) % chunk
    if pad:
        xs_p = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_p = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C_p = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        xs_p, dt_p, B_p, C_p = xs, dt, Bmat, Cmat
    h0 = state.ssm if state is not None else None
    y, h_last = _ssd(xs_p, dt_p, A, B_p, C_p, chunk, rt, h0=h0)
    if pad:
        y = y[:, :S]
    y = y + xs * p["D_skip"][None, None, :, None].astype(y.dtype)
    y = y.reshape(Bsz, S, di_l)

    # gated RMSNorm (Mamba2): norm(y * silu(z))
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = kref.rmsnorm(y, p["norm_scale"]).astype(x.dtype)
    out = reduce_from_tp(y @ p["w_out"], rt.tp_axis)
    if not return_state:
        return out
    W = cfg.conv_width
    new_state = SSMState(conv=conv_in[:, -(W - 1):].astype(jnp.bfloat16),
                         ssm=h_last,
                         length=(state.length if state is not None
                                 else jnp.int32(0)) + S)
    return out, new_state


def apply_ssm_decode(p, x, cfg: ModelConfig, rt: Runtime, state: SSMState):
    """Single-token step. x: (B, 1, D) -> ((B, 1, D), new state)."""
    Bsz, _, D = x.shape
    x = copy_to_tp(x, rt.tp_axis)
    tp = rt.tp_size if rt.tp_axis else 1
    di_l, gn = _split_conv_channels(cfg, tp)
    hd, ns, g = cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    h_l = di_l // hd
    xt = x[:, 0]                                       # (B, D)

    z = xt @ p["w_z"]
    xs = xt @ p["w_x"]
    bc = xt @ p["w_bc"]
    dt_raw = xt @ p["w_dt"]

    conv_w = jnp.concatenate([p["conv_w_x"], p["conv_w_bc"]], axis=0)
    conv_b = jnp.concatenate([p["conv_b_x"], p["conv_b_bc"]], axis=0)
    conv_in = jnp.concatenate([xs, bc], axis=-1)       # (B, ch)
    hist = jnp.concatenate([state.conv.astype(conv_in.dtype),
                            conv_in[:, None]], axis=1)  # (B, W, ch)
    conv = jnp.einsum("bwc,cw->bc", hist.astype(jnp.float32),
                      conv_w.astype(jnp.float32)) + conv_b.astype(jnp.float32)
    conv = jax.nn.silu(conv).astype(conv_in.dtype)
    xs_t = conv[:, :di_l].reshape(Bsz, h_l, hd)
    B_t = conv[:, di_l:di_l + gn].reshape(Bsz, g, ns)
    C_t = conv[:, di_l + gn:].reshape(Bsz, g, ns)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    y, new_ssm = kref.ssd_decode_step(state.ssm, xs_t, dt, A, B_t, C_t)
    y = y + xs_t * p["D_skip"][None, :, None].astype(y.dtype)
    y = y.reshape(Bsz, di_l)
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = kref.rmsnorm(y, p["norm_scale"]).astype(x.dtype)
    out = reduce_from_tp(y @ p["w_out"], rt.tp_axis)
    new_state = SSMState(conv=hist[:, 1:].astype(state.conv.dtype),
                         ssm=new_ssm, length=state.length + 1)
    return out[:, None], new_state


def make_ssm_state(cfg: ModelConfig, batch: int, tp: int) -> SSMState:
    di_l, gn = _split_conv_channels(cfg, tp)
    h_l = di_l // cfg.ssm_head_dim
    ch = di_l + 2 * gn
    return SSMState(
        conv=jnp.zeros((batch, cfg.conv_width - 1, ch), jnp.bfloat16),
        ssm=jnp.zeros((batch, h_l, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        length=jnp.int32(0))
