"""Operations and bytes of the chunked gated delta rule ("KDA"),
computed from shapes: the yardstick of ``kda_roofline`` and the
``kda_mixing`` part of the configuration's FLOP count.

The recurrence ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} +
beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` over chunks of ``chunk``
tokens needs, a chunk and head (``dk`` / ``dv`` the key and value
widths of a head): the chunk's own four products — ``A = K K^T`` and
``B = Q K^T`` under the decay (``chunk x chunk x dk`` each) and the
solved ``W_k = T K``, ``W_v = T V`` (``chunk x chunk x dk``, ``.. x
dv``) — and the four that carry the state: ``W_k S``, ``Q S``, ``K^T
U`` (``chunk x dk x dv`` each) and ``B U`` (``chunk x chunk x dv``).
Counted as ``benchmark/flops.py`` counts: matmul-only, 2 FLOPs per
multiply-accumulate, the triangular solve's substitution and every
exponent left out.

``kernel_*`` count one CALL of the kernels that carry the state (the
forward's four products; the backward's nine: ``U`` again from the
saved state, then ``dU``, ``dB``, ``dQ``, ``dW_k``, ``dK`` and the
state's gradient from three) and what such a call must move whatever
implements it: the five operands of a token (three ``dk`` wide, one
``dv`` wide, one ``chunk`` wide) and the output in the compute type, a
float32 state a chunk and head written by the forward and read by the
backward, which writes five gradients as wide as the operands.
"""

from __future__ import annotations


def forward_flops_per_token(heads: int, dk: int, dv: int, chunk: int) -> float:
    """Forward FLOPs a token of one layer, the chunk's own products and
    the state's."""
    own = 3 * chunk * dk + chunk * dv
    state = 3 * dk * dv + chunk * dv
    return 2.0 * heads * (own + state)


def kernel_flops(
    batch: int, heads: int, seq_len: int, dk: int, dv: int, chunk: int,
    backward: bool,
) -> float:
    """One call over ``batch`` rows of ``seq_len`` tokens."""
    tokens = batch * heads * seq_len
    if backward:
        return 2.0 * tokens * (7 * dk * dv + 2 * chunk * dv)
    return 2.0 * tokens * (3 * dk * dv + chunk * dv)


def kernel_bytes(
    batch: int, heads: int, seq_len: int, dk: int, dv: int, chunk: int,
    backward: bool, itemsize: int = 2,
) -> float:
    tokens = batch * heads * seq_len
    chunks = batch * heads * -(-seq_len // chunk)
    row = (3 * dk + dv + chunk) * itemsize  # the operands of a token
    state = chunks * (dk * dv + dk) * 4.0  # and the chunk's decay
    if backward:
        return tokens * (2 * row + dv * itemsize) + state + chunks * dk * 4.0
    return tokens * (row + dv * itemsize) + state


def least_seconds(shape: dict, backward: bool, peak: dict) -> float:
    """The least time one call could take on a chip with these peaks:
    the larger of its FLOPs over the bf16 peak and its bytes over the
    HBM peak."""
    return max(
        kernel_flops(backward=backward, **shape) / peak["bf16_flops_per_s"],
        kernel_bytes(backward=backward, **shape) / peak["hbm_bytes_per_s"],
    )


def layer_shape(record: dict) -> dict | None:
    """The shape of one layer's rule in a cell's step (all its heads,
    one micro-batch), from the run's record; None where the
    configuration has no such layer."""
    sizes, geometry = record.get("sizes", {}), record.get("geometry", {})
    linear = sizes.get("linear_attn_config")
    if not linear or "kda_chunk" not in sizes:
        return None
    return dict(
        batch=geometry["atomic_bsz"],
        heads=linear["num_heads"],
        seq_len=sizes["sequence_length"],
        dk=linear["head_dim"],
        dv=linear["head_dim"],
        chunk=sizes["kda_chunk"],
    )
