"""The exit a token is expected to take, ``sum_t t p_t`` of the learned
exit distribution, mean over tokens and over the window's journalled
whole steps: 1 .. ``total_ut_steps``. In training every pass runs for
every token whatever the gate says, so this moves NO rate (the entry's
``moves`` names the cell's only one); it is watched because a gate that
has collapsed onto one exit (1.0 or 4.0) leaves the other exits' heads
without gradient, and the cell then no longer measures the objective it
names — the drift PERF.md section 7 records for the routed cells'
routers.

From the program's own counters: ``expected_exit`` over
``micro_batches`` of the ``loop.exit`` events the trainer journals
where it pulls its statistics (every tenth step). ``harness.finish``
hands readers durations only, so this reader takes the events from
``adaptdl_tpu.trace.snapshot_spans()`` itself; a program without them
(a parent commit) reads nothing and the metric is left out."""

UNIT = "exit"
LAYER = "loss (looped exits)"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def exit_events(spans_snapshot, record) -> list[dict]:
    """The ``loop.exit`` events of whole steps (the calibration
    program's single micro-batch is journalled too)."""
    micro_batches = record["geometry"]["accum_steps"] + 1
    return [
        rec["attrs"]
        for rec in spans_snapshot
        if rec.get("name") == "loop.exit"
        and rec.get("attrs", {}).get("micro_batches") == micro_batches
    ]


def read(trace, spans, record, events=None):
    if events is None:
        try:
            from adaptdl_tpu import trace as program_trace
        except ImportError:
            return None
        snapshot = getattr(program_trace, "snapshot_spans", None)
        events = exit_events(snapshot(), record) if snapshot else []
    values = [ev["expected_exit"] / ev["micro_batches"] for ev in events]
    return sum(values) / len(values) if values else None
