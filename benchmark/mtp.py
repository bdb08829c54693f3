"""What the readers of the multi-token-prediction module's counters
share (``mtp_loss_share``, ``head_rows_per_token``): the program's own
``mtp.schedule`` and ``mtp.loss`` events. ``harness.finish`` hands
readers durations only, so they take the events from
``adaptdl_tpu.trace.snapshot_spans()`` themselves."""

from __future__ import annotations


def program_events(*names: str) -> list[dict]:
    """The records of THIS process's program with one of ``names``, or
    none where the program has no such tracing (a parent commit)."""
    try:
        from adaptdl_tpu import trace
    except ImportError:
        return []
    snapshot = getattr(trace, "snapshot_spans", None)
    return [
        rec for rec in (snapshot() if snapshot else [])
        if rec.get("name") in names
    ]
