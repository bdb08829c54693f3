"""Operations and bytes of sliding-window attention, computed from
shapes: the yardstick of ``window_attn_roofline``.

A "sliding_attention" layer's query ``i`` sees the keys ``j <= i``
with ``i - j < window``: ``min(i + 1, window)`` of them, the BAND. One
forward pass multiplies every pair of the band twice over ``head_dim``
(``Q K^T`` and ``P V``): ``4 x head_dim x sum_i min(i + 1, window)``
FLOPs a head; the backward is counted at 2.5 times the forward (five
products a pair for the forward's two), as ``benchmark/
attention_backward.py`` counts the full layers'. Counted as
``benchmark/flops.py`` counts: 2 FLOPs per multiply-accumulate, and
what the MODEL defines whatever implements it — whole blocks above the
band's edges, a forward run again, k and v repeated for the query
heads of a group earn nothing.

What the work must move, a pass: q and the output of the query heads
and k and v of the KEY/VALUE heads once in the compute type, and one
float32 log-sum-exp a query head and position; the backward reads
those and the output's cotangent and writes a gradient as wide as
each of q, k and v.
"""

from __future__ import annotations


def band_pairs(seq_len: int, window: int) -> int:
    """``sum_i min(i + 1, window)`` over a row's queries."""
    reach = min(window, seq_len)
    return reach * (reach + 1) // 2 + (seq_len - reach) * reach


def flops(shape: dict, backward: bool) -> float:
    """One pass over a layer's query heads and a micro-batch's rows."""
    pairs = band_pairs(shape["seq_len"], shape["window"])
    forward = 4.0 * shape["head_dim"] * pairs * shape["batch"] * shape["heads"]
    return 2.5 * forward if backward else forward


def bytes_moved(shape: dict, backward: bool, itemsize: int = 2) -> float:
    tokens = shape["batch"] * shape["seq_len"]
    heads, kv_heads, hd = shape["heads"], shape["kv_heads"], shape["head_dim"]
    lse = 4.0 * tokens * heads
    if backward:  # q, o, dO read and dQ written; k, v read, dK, dV written
        return tokens * hd * itemsize * (4 * heads + 4 * kv_heads) + lse
    return tokens * hd * itemsize * (2 * heads + 2 * kv_heads) + lse


def least_seconds(shape: dict, backward: bool, peak: dict) -> float:
    """The least time one pass could take on a chip with these peaks:
    the larger of its FLOPs over the bf16 peak and its bytes over the
    HBM peak."""
    return max(
        flops(shape, backward) / peak["bf16_flops_per_s"],
        bytes_moved(shape, backward) / peak["hbm_bytes_per_s"],
    )


def sliding_layers(sizes: dict) -> list[int]:
    return [
        at for at, kind in enumerate(sizes.get("layer_types", ()))
        if kind == "sliding_attention"
    ]


def layer_shape(record: dict) -> dict | None:
    """The shape of one sliding layer's attention in a cell's step (all
    its query heads, one micro-batch), from the run's record. None
    where the configuration has no such layer."""
    sizes, geometry = record.get("sizes", {}), record.get("geometry", {})
    layers = sliding_layers(sizes)
    try:
        return {
            "batch": geometry["atomic_bsz"],
            "heads": sizes["num_attention_heads_per_layer"][layers[0]],
            "kv_heads": sizes["num_key_value_heads"],
            "head_dim": sizes["head_dim"],
            "seq_len": sizes["sequence_length"],
            "window": sizes["sliding_window"],
        }
    except (KeyError, IndexError):
        return None


def layer_passes(record: dict) -> int:
    """(layer, micro-batch) pairs a step."""
    return len(sliding_layers(record["sizes"])) * (
        record["geometry"]["accum_steps"] + 1
    )


def keys_events(spans_snapshot, record: dict) -> list[dict]:
    """The attributes of the ``window.keys`` events in a snapshot of
    the program's trace buffer (``adaptdl_tpu.trace.snapshot_spans()``)
    journalled at the cell's own row length and window."""
    sizes = record.get("sizes", {})
    want = (sizes.get("sequence_length"), sizes.get("sliding_window"))
    return [
        rec["attrs"]
        for rec in spans_snapshot
        if rec.get("name") == "window.keys"
        and (rec.get("attrs", {}).get("seq_len"),
             rec.get("attrs", {}).get("window")) == want
        and rec["attrs"].get("keys_in_window")
    ]


def program_keys_events(record: dict) -> list[dict]:
    """Those events of THIS process's program, or none where the
    program has no such tracing (a parent commit)."""
    try:
        from adaptdl_tpu import trace
    except ImportError:
        return []
    snapshot = getattr(trace, "snapshot_spans", None)
    return keys_events(snapshot(), record) if snapshot else []
