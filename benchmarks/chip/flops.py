"""Operations and bytes the algorithm needs, from the configuration's
shapes. Recomputation (remat, the fused loss's backward re-reading the
logits) is never counted, so a share computed from these stays at or
under the hardware's peak when the timing covers all the work.
"""
from __future__ import annotations

from typing import Iterable

import weights as W


def matmul_params(c: dict) -> int:
    """Weights that take part in a matrix product per token, as the
    configuration's architecture module (`arch/<arch>.py`) counts them."""
    return W.arch(c).matmul_params(c)


def attn_flops_per_token(c: dict, context: int) -> int:
    """Attention FLOPs of one query against `context` keys, over all
    layers (forward), as the architecture module counts them."""
    return W.arch(c).attn_flops_per_token(c, context)


def forward_flops(c: dict, tokens: int, context_sum: int) -> int:
    """Forward pass over `tokens` tokens whose attention contexts sum to
    `context_sum`."""
    return (2 * matmul_params(c) * tokens
            + attn_flops_per_token(c, 1) * context_sum)


def train_flops(c: dict, tokens: int, context_sum: int) -> int:
    """Forward and backward (3x the forward)."""
    return 3 * forward_flops(c, tokens, context_sum)


def causal_context_sum(lengths: Iterable[int]) -> int:
    """Sum over every token of its causal context (1..L) per segment."""
    return sum(L * (L + 1) // 2 for L in lengths)


def decode_kernel_cost(c: dict, context_sum: int, queries: int,
                       dtype_bytes: int = 2) -> tuple:
    """Decode attention for `queries` slot-steps whose caches hold
    `context_sum` tokens in all, over all layers: FLOPs (scores and
    weighted values) and the bytes the calls need (the K/V those lengths
    hold, plus each query's q and o). Returns (flops, bytes)."""
    L, H, KV, Dh = (c["num_hidden_layers"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    flops = 4 * L * H * Dh * context_sum
    kv = 2 * L * KV * Dh * context_sum * dtype_bytes
    qo = 2 * L * queries * H * Dh * dtype_bytes
    return flops, kv + qo


def fused_loss_cost(c: dict, n_rows: int, dtype_bytes: int = 2) -> tuple:
    """Fused head + cross-entropy over n_rows hidden rows, forward and
    backward: the logits product once forward, and the two gradient
    products (dh, dW) backward. Bytes: hidden rows and head read, dh and
    dW written, once each."""
    d, V = c["hidden_size"], c["vocab_size"]
    flops = 3 * 2 * n_rows * d * V
    nbytes = (2 * n_rows * d + 2 * d * V) * dtype_bytes
    return flops, nbytes
