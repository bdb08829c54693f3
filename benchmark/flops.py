"""Operations a training step needs, computed from shapes.

The benchmark's own yardstick (arithmetic copied from
``adaptdl_tpu/flops.py::transformer_train_flops``, which later PRs may
change): matmul-only accounting, 2 FLOPs per multiply-accumulate, the
backward pass costed at twice the forward, causal attention scored as
half the [seq, seq] rectangle, recomputation (remat) NOT counted —
model FLOPs, not hardware FLOPs.
"""

from __future__ import annotations


def lm_forward_flops_per_token(
    n_layer: int,
    d_model: int,
    d_ff: int,
    vocab_size: int,
    seq_len: int,
    causal: bool = True,
) -> dict[str, float]:
    """Forward matmul FLOPs per token of a dense decoder, by part."""
    proj = 2 * 4 * d_model * d_model  # fused QKV (3 d^2) + output (d^2)
    ffn = 2 * 2 * d_model * d_ff  # up + down
    # QK^T and PV: each 2 * seq * d_model per token summed over heads.
    attention = 2 * 2 * seq_len * d_model * (0.5 if causal else 1.0)
    return {
        "projections": float(n_layer * proj),
        "ffn": float(n_layer * ffn),
        "attention": float(n_layer * attention),
        "head": float(2 * d_model * vocab_size),
    }


def lm_train_flops_per_token(**shape) -> float:
    """Forward + backward (3x forward) model FLOPs per trained token."""
    return 3.0 * sum(lm_forward_flops_per_token(**shape).values())


def mfu_percent(
    flops_per_unit: float,
    units_per_s: float,
    chips: int,
    peak_flops_per_s: float,
) -> float:
    """Model FLOP/s utilisation, in percent of ``chips`` x peak."""
    return 100.0 * flops_per_unit * units_per_s / (chips * peak_flops_per_s)


def attention_forward_flops(
    batch_heads: int, seq_len: int, head_dim: int, causal: bool = True
) -> float:
    """QK^T and PV of one attention forward call: 2 FLOPs per
    multiply-accumulate, the masked half not counted when causal."""
    full = 2 * 2 * batch_heads * seq_len * seq_len * head_dim
    return float(full) * (0.5 if causal else 1.0)


def attention_forward_bytes(
    batch_heads: int, seq_len: int, head_dim: int, itemsize: int = 2
) -> float:
    """Bytes one attention forward call must move: q, k, v read and
    the output written once in the compute type, plus one float32
    log-sum-exp per query row for the backward pass."""
    qkvo = 4 * batch_heads * seq_len * head_dim * itemsize
    return float(qkvo + batch_heads * seq_len * 4)
