"""Operations and bytes the grouped matrix products of a routed expert
layer need, computed from shapes and from the rows actually routed:
the yardstick of ``moe_gmm_roofline`` (``benchmark/flops.py`` is not
this PR's to edit).

One routed layer and micro-batch runs the three products of
``adaptdl_tpu/ops/grouped_matmul.py`` over the same ``rows`` (the rows
placed for held experts, padding not counted) and the same two widths
``d`` (model) and ``f`` (expert): ``x W`` (``moe_gmm``), ``dy W^T``
(``moe_gmm`` again) and ``x^T dy`` (``moe_tgmm``), once for each of the
expert's three weights. Counted as ``benchmark/flops.py`` counts: 2
FLOPs per multiply-accumulate, what the algorithm needs and nothing
the implementation adds (no padded row, no row of an absent expert).
"""

from __future__ import annotations


def product_flops(rows: float, d: int, f: int) -> float:
    """Any one of the three products over ``rows`` rows: ``rows x d x
    f`` multiply-accumulates."""
    return 2.0 * rows * d * f


def gmm_bytes(
    rows: float, experts_with_rows: float, d: int, f: int,
    itemsize: int = 2,
) -> float:
    """``x W`` or ``dy W^T``: the rows read and written once in the
    compute type, and each expert's weight that has rows read once."""
    return itemsize * (rows * (d + f) + experts_with_rows * d * f)


def tgmm_bytes(
    rows: float, experts_with_rows: float, d: int, f: int,
    itemsize: int = 2,
) -> float:
    """``x^T dy``: both row operands read once in the compute type and
    each expert's gradient written once in float32."""
    return itemsize * rows * (d + f) + 4.0 * experts_with_rows * d * f


def least_seconds(
    rows: float, experts_with_rows: float, d: int, f: int,
    gmm_calls: float, tgmm_calls: float, peak: dict,
) -> float:
    """The least time ``gmm_calls`` + ``tgmm_calls`` products over
    ``rows`` rows each could take on a chip with these peaks: for each
    kind the larger of its FLOPs over the bf16 peak and its bytes over
    the HBM peak."""
    flops_s = product_flops(rows, d, f) / peak["bf16_flops_per_s"]
    return gmm_calls * max(
        flops_s,
        gmm_bytes(rows, experts_with_rows, d, f) / peak["hbm_bytes_per_s"],
    ) + tgmm_calls * max(
        flops_s,
        tgmm_bytes(rows, experts_with_rows, d, f) / peak["hbm_bytes_per_s"],
    )


def load_events(spans_snapshot, record: dict) -> list[dict]:
    """The attributes of the ``moe.load`` events in a snapshot of the
    program's trace buffer (``adaptdl_tpu.trace.snapshot_spans()``)
    that are whole optimizer steps of the cell's geometry: every
    routed layer accounts for ``global_batch x sequence x top_k``
    assignments (a warm-up step before the loader adopts the pinned
    accumulation journals fewer micro-batches, and is left out)."""
    sizes, geometry = record.get("sizes", {}), record.get("geometry", {})
    try:
        assignments = (
            geometry["global_batch"] * sizes["sequence_length"]
            * sizes["num_experts_per_tok"]
        )
    except KeyError:
        return []
    return [
        rec["attrs"]
        for rec in spans_snapshot
        if rec.get("name") == "moe.load"
        and rec.get("attrs", {}).get("held_rows")
        and all(
            sum(rows) + left == assignments
            for rows, left in zip(
                rec["attrs"]["held_rows"], rec["attrs"]["left_out"]
            )
        )
    ]


def program_load_events(record: dict) -> list[dict]:
    """Those events of THIS process's program, or none where the
    program has no such tracing (a parent commit)."""
    try:
        from adaptdl_tpu import trace
    except ImportError:
        return []
    snapshot = getattr(trace, "snapshot_spans", None)
    return load_events(snapshot(), record) if snapshot else []
