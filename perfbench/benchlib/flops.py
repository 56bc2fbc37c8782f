"""Operations and bytes computed from shapes.

``train_flops_per_token`` counts what the forward and backward passes of
a dense decoder require, as MFU counts it: 6 x the matmul parameters of
the layers and the LM head (the embedding lookup is no matmul), plus
6 x layers x seq x heads x head_dim for causal attention (QK^T and PV,
2 FLOPs a multiply-add, half the S^2 pairs, backward twice the forward).
Recomputed operations are not counted.

``flash_fwd_cost`` is the least work of one call of the causal flash-
attention forward kernel: the FLOPs of the unmasked half of QK^T and PV,
and the bytes of reading q, k, v once and writing the output once."""
from __future__ import annotations

from benchlib.model import Dense


def matmul_params(m: Dense) -> int:
    d, h, kv, hd, f = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return m.layers * per_layer + d * m.vocab


def train_flops_per_token(m: Dense, seq: int) -> float:
    attention = 6 * m.layers * seq * m.heads * m.head_dim
    return 6.0 * matmul_params(m) + attention


def flash_fwd_cost(batch_heads: int, seq: int, head_dim: int,
                   dtype_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of one causal self-attention forward call over
    ``batch_heads`` (batch x query heads) rows of length ``seq``."""
    flops = 2 * 2 * batch_heads * seq * seq * head_dim / 2
    bytes_ = 4 * batch_heads * seq * head_dim * dtype_bytes
    return float(flops), float(bytes_)


def roofline_seconds(flops: float, bytes_: float, peak_flops: float,
                     peak_bytes_per_s: float) -> tuple:
    """(least seconds, bound) where bound is "compute" or "memory"."""
    tc, tm = flops / peak_flops, bytes_ / peak_bytes_per_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
