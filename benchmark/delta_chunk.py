"""Operations and bytes of the delta rule's chunk-local ("own") work
for a rule with ONE decay a head and token, computed from shapes: the
yardstick of ``delta_chunk_roofline``.

Before the state is carried from chunk to chunk (``benchmark/kda.py``)
every chunk of ``chunk`` tokens forms, a head, ``A = tril(K K^T) * D``
and ``B = tril(Q K^T) * D`` with ``D_ti = e^{G_t - G_i}`` ONE ``[chunk,
chunk]`` matrix a head, solves ``T = (I + Diag(beta) A)^{-1}
Diag(beta)`` and forms ``W_k = T (K e^G)`` and ``W_v = T V``: four
products, ``3 x chunk x dk + chunk x dv`` multiply-accumulates a token
and head. The backward is counted at twice the forward; a forward
formed again inside the backward, the substitution of the solve and
every exponent earn nothing. Counted as ``benchmark/flops.py`` counts:
2 FLOPs per multiply-accumulate.

What the work must move whatever implements it, a token: q and k of
the KEY heads and v of the value heads in the compute type, one
float32 ``g`` and one float32 ``beta`` a value head, read; ``q e^G``,
``k e^{G_C - G}``, ``W_k`` (``dk`` wide), ``W_v`` (``dv``) and ``B``
(``chunk``) a value head in the compute type, written. The backward
reads the same operands and a cotangent as wide as each result, and
writes a gradient as wide as each operand. A body that takes the decay
a CHANNEL and is fed the head's decay broadcast moves ``dk`` times the
decay and takes ``chunk x dk`` exponents a pair where this count has
one: it reads low against this yardstick, which is the finding.
"""

from __future__ import annotations


def flops(shape: dict, backward: bool) -> float:
    """One pass over a layer's heads and a micro-batch's tokens."""
    tokens = shape["batch"] * shape["heads"] * shape["seq_len"]
    own = 3 * shape["chunk"] * shape["dk"] + shape["chunk"] * shape["dv"]
    return (2.0 if backward else 1.0) * 2.0 * tokens * own


def bytes_moved(shape: dict, backward: bool, itemsize: int = 2) -> float:
    tokens = shape["batch"] * shape["seq_len"]
    heads, key_heads = shape["heads"], shape["key_heads"]
    dk, dv, chunk = shape["dk"], shape["dv"], shape["chunk"]
    operands = (2 * key_heads * dk + heads * dv) * itemsize + heads * 8.0
    results = heads * (3 * dk + dv + chunk) * itemsize
    if backward:  # operands and cotangents in, gradients out
        return tokens * (2 * operands + results)
    return tokens * (operands + results)


def least_seconds(shape: dict, backward: bool, peak: dict) -> float:
    """The least time one pass could take on a chip with these peaks:
    the larger of its FLOPs over the bf16 peak and its bytes over the
    HBM peak."""
    return max(
        flops(shape, backward) / peak["bf16_flops_per_s"],
        bytes_moved(shape, backward) / peak["hbm_bytes_per_s"],
    )


def layer_shape(record: dict) -> dict | None:
    """The shape of one layer's rule in a cell's step (all its heads,
    one micro-batch), from the run's record: ``benchmark/kda.py``'s,
    and how many key heads serve the heads (all of them where the
    configuration does not say). None where the configuration has no
    such layer."""
    from benchmark import kda

    shape = kda.layer_shape(record)
    if shape is None:
        return None
    return dict(
        shape,
        key_heads=record["sizes"].get("linear_num_key_heads", shape["heads"]),
    )


def layer_passes(record: dict) -> int:
    """(layer, micro-batch) pairs a step."""
    return len(record["sizes"]["linear_attn_config"]["kda_layers"]) * (
        record["geometry"]["accum_steps"] + 1
    )
