"""One rescale's timeline out of the successor's own ring buffer.

The readers ``rescale_span_s``, ``exit_agree_s``, ``ckpt_snapshot_s``,
``ckpt_write_s``, ``exit_teardown_s``, ``boot_process_s`` and
``boot_import_s`` share this: ``harness.finish`` hands a reader
durations by name, and a timeline needs each record's ``ts`` (the
machine's wall clock, the one clock two processes share) and ``inc``
(which incarnation recorded it). So they take the records from
``adaptdl_tpu.trace.snapshot_spans()`` themselves, as
``moe_load_max_over_mean`` does. The dying predecessor hands its spans
from the signal on to the successor through the checkpoint directory
(``trace.adopt_handover``); a program without that (a parent commit)
holds none of them and every reader here reads nothing.
"""

from __future__ import annotations

PREDECESSOR, SUCCESSOR = -1, 0


def program_records() -> tuple[int, list[dict]]:
    """(this worker's incarnation, its ring buffer's records)."""
    try:
        from adaptdl_tpu import env, trace
    except ImportError:
        return 0, []
    snapshot = getattr(trace, "snapshot_spans", None)
    return env.num_restarts(), snapshot() if snapshot else []


def find(name: str, who: int, records=None) -> dict | None:
    """The last span ``name`` of the window's worker (``SUCCESSOR``) or
    of the incarnation one below it (``PREDECESSOR``): an older
    incarnation's record is not this rescale's."""
    own, recs = program_records() if records is None else records
    found = [
        r for r in recs
        if r.get("name") == name
        and r.get("inc") == own + who
        and r.get("kind") != "event"
        and "ts" in r
        and "dur" in r
    ]
    return found[-1] if found else None


def duration(name: str, who: int, records=None) -> float | None:
    rec = find(name, who, records)
    return float(rec["dur"]) if rec else None


def end(rec: dict) -> float:
    return float(rec["ts"]) + float(rec["dur"])
