"""Operations and bytes the sparse attention of a learned indexer
needs, computed from shapes and from the pairs actually SELECTED: the
yardstick of ``sparse_attn_roofline`` and ``indexer_roofline``.

Counted as ``benchmark/flops.py`` and ``benchmark/attention_backward.py``
count: matmul-only, 2 FLOPs per multiply-accumulate, what the model
defines and nothing an implementation adds. Attention earns the pairs
the selection KEPT (a kernel that multiplies every causal pair and
masks earns no more for it, and padding earns nothing); the index
scores earn every causal pair, because the model scores every earlier
key before it can choose.
"""

from __future__ import annotations

# QK^T and PV forward; S = QK^T again, dP, dV, dK, dQ backward.
FORWARD_MATMULS = 2
BACKWARD_MATMULS = 5


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len: int, topk: int) -> int:
    """Pairs the selection keeps in one row: every earlier key while a
    query has at most ``topk``, ``topk`` after."""
    full = min(seq_len, topk)
    return full * (full + 1) // 2 + max(seq_len - topk, 0) * topk


def attention_flops(pairs: float, heads: int, head_dim: int,
                    matmuls: int) -> float:
    """``matmuls`` products of ``head_dim`` multiply-accumulates a
    (query, key) pair and query head."""
    return 2.0 * matmuls * heads * head_dim * pairs


def attention_forward_bytes(
    tokens: float, heads: int, kv_heads: int, head_dim: int,
    itemsize: int = 2,
) -> float:
    """q read and the output written for every query head, k and v
    read for every kv head, once, in the compute type; one float32
    log-sum-exp a query head and token."""
    return tokens * (
        (2 * heads + 2 * kv_heads) * head_dim * itemsize + 4 * heads
    )


def attention_backward_bytes(
    tokens: float, heads: int, kv_heads: int, head_dim: int,
    itemsize: int = 2,
) -> float:
    """q, the output and its cotangent read and dq written for every
    query head; k, v read and dk, dv written for every kv head; the
    log-sum-exp."""
    return tokens * (
        (4 * heads + 4 * kv_heads) * head_dim * itemsize + 4 * heads
    )


def attention_least_seconds(
    pairs: float, tokens: float, heads: int, kv_heads: int,
    head_dim: int, peak: dict,
) -> float:
    """The least time the attention over ``pairs`` selected pairs of
    ``tokens`` tokens could take, forward and backward (each token's
    bytes are moved by exactly one call of each)."""
    flops, bw = peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"]
    forward = max(
        attention_flops(pairs, heads, head_dim, FORWARD_MATMULS) / flops,
        attention_forward_bytes(tokens, heads, kv_heads, head_dim) / bw,
    )
    backward = max(
        attention_flops(pairs, heads, head_dim, BACKWARD_MATMULS) / flops,
        attention_backward_bytes(tokens, heads, kv_heads, head_dim) / bw,
    )
    return forward + backward


def index_flops(pairs: float, index_heads: int, index_dim: int) -> float:
    """One dot of ``index_dim`` an indexer head and causal pair."""
    return 2.0 * index_heads * index_dim * pairs


def index_bytes(
    tokens: float, index_heads: int, index_dim: int, itemsize: int = 2
) -> float:
    """qI and kI read in the compute type, the heads' weights in
    float32, and what the selection writes a query: threshold, tie
    position and the scores' log-sum-exp (4 bytes each)."""
    return tokens * (
        (index_heads + 1) * index_dim * itemsize + 4 * index_heads + 12
    )


def index_least_seconds(
    pairs: float, tokens: float, index_heads: int, index_dim: int,
    peak: dict,
) -> float:
    return max(
        index_flops(pairs, index_heads, index_dim)
        / peak["bf16_flops_per_s"],
        index_bytes(tokens, index_heads, index_dim)
        / peak["hbm_bytes_per_s"],
    )


def select_events(spans_snapshot, record: dict) -> list[dict]:
    """The attributes of the ``sparse.select`` events in a snapshot of
    the program's trace buffer that are whole optimizer steps of the
    cell's geometry: every sparse layer accounts for ``global_batch x
    sequence`` queries (a warm-up step before the loader adopts the
    pinned accumulation journals fewer micro-batches, and is left
    out)."""
    sizes, geometry = record.get("sizes", {}), record.get("geometry", {})
    try:
        queries = geometry["global_batch"] * sizes["sequence_length"]
    except KeyError:
        return []
    return [
        rec["attrs"]
        for rec in spans_snapshot
        if rec.get("name") == "sparse.select"
        and rec.get("attrs", {}).get("queries")
        and all(q == queries for q in rec["attrs"]["queries"])
    ]


def program_select_events(record: dict) -> list[dict]:
    """Those events of THIS process's program, or none where the
    program has no such tracing (a parent commit)."""
    try:
        from adaptdl_tpu import trace
    except ImportError:
        return []
    snapshot = getattr(trace, "snapshot_spans", None)
    return select_events(snapshot(), record) if snapshot else []


def mean_per_layer(events: list[dict], name: str) -> list[float]:
    """Mean over the events of a per-layer counter."""
    return [
        sum(ev[name][layer] for ev in events) / len(events)
        for layer in range(len(events[0][name]))
    ]


def roofline_share(trace, record, pattern, select_events, least_seconds):
    """What both roofline readers do around their own count: 100 x
    ``least_seconds(sizes, peak, events)`` over the device time a step
    spends in the calls matching ``pattern``; None (the metric is left
    out) where the trace has no step program or no such call, the
    record no peaks, or the program journalled no whole step."""
    peak = record.get("peak_table")
    program = trace.step_program() if trace is not None else None
    if program is None or not peak:
        return None
    seconds, _exposed, calls = trace.matching_s(pattern)
    if select_events is None:
        select_events = program_select_events(record)
    if not calls or not select_events:
        return None
    measured = seconds / program[1]
    if measured <= 0:
        return None
    return 100.0 * least_seconds(record["sizes"], peak, select_events) / measured
