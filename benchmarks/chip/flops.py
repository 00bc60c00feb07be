"""Model FLOPs of one training token of a dense decoder, from the
configuration's shapes alone.

Counts the matrix multiplications the forward and backward passes
require (backward = 2x forward): the Q/K/V/O projections, the SwiGLU
MLP, the LM head, and causal attention (QK^T and PV over the
S(S+1)/2 visible pairs of a sequence of length S).  Elementwise work
(norms, RoPE, softmax, the loss) is not counted, nor is anything the
program recomputes to save memory (activation checkpointing).
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"D": d, "H": h, "K": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim", d // h),
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul for every token (the
    embedding lookup is a gather; a tied head is counted once, as the
    head)."""
    g = dims(cfg)
    D, H, K, dh, F, V, L = (g[k] for k in ("D", "H", "K", "dh", "F", "V",
                                           "L"))
    per_layer = D * H * dh + 2 * D * K * dh + H * dh * D + 3 * D * F
    return L * per_layer + V * D


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 x matmul weights + causal attention, forward and backward."""
    g = dims(cfg)
    attn = 6 * g["L"] * g["H"] * g["dh"] * (seq_len + 1)
    return 6.0 * matmul_params(cfg) + attn
