"""Operations and bytes one flash attention BACKWARD call needs,
computed from shapes: the yardstick of ``flash_bwd_roofline``
(``benchmark/flops.py`` holds the forward's and is not this PR's to
edit).

Counted as ``benchmark/flops.py`` counts the forward: matmul-only, 2
FLOPs per multiply-accumulate, the masked half not counted when
causal, recomputation included because the algorithm needs it (the
backward has no stored probabilities to read: S = Q K^T is part of
every backward, in any implementation that keeps memory O(seq)).
"""

from __future__ import annotations


def attention_backward_flops(
    batch_heads: int, seq_len: int, head_dim: int, causal: bool = True
) -> float:
    """The five matmuls of one backward call — S = Q K^T, dP = dO V^T,
    dV = P^T dO, dK = dS^T Q, dQ = dS K — each ``seq x seq x head_dim``
    multiply-accumulates per (batch, head): 2.5 times the forward's
    two."""
    full = 5 * 2 * batch_heads * seq_len * seq_len * head_dim
    return float(full) * (0.5 if causal else 1.0)


def attention_backward_bytes(
    batch_heads: int, seq_len: int, head_dim: int, itemsize: int = 2
) -> float:
    """Bytes one backward call must move: q, k, v, the output and its
    cotangent read and dq, dk, dv written once in the compute type,
    plus the float32 log-sum-exp per query row. (``delta`` = rowsum(dO
    * O) is derived from the two it reads.)"""
    tensors = 8 * batch_heads * seq_len * head_dim * itemsize
    return float(tensors + batch_heads * seq_len * 4)
