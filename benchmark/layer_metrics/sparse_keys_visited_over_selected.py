"""Keys the sparse attention kernels multiply a query over keys the
selection kept, all sparse layers, mean over the window's journalled
whole steps. 1.0 is a kernel that touches only what was picked; a
kernel that multiplies every causal pair reads ~4.3 at rows of 16 384
with 2048 kept (134 M causal pairs over 31.5 M selected), a little
more for the whole tiles along the diagonal.

From the program's own counters: ``keys_visited`` and ``keys_selected``
of the ``sparse.select`` events the trainer journals where it pulls its
statistics (every tenth step). ``harness.finish`` hands readers
durations only, so this reader takes the events from
``adaptdl_tpu.trace.snapshot_spans()`` itself; a program without them
(a parent commit) reads nothing and the metric is left out."""

UNIT = "x"
LAYER = "sparse attention"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(trace, spans, record, select_events=None):
    from benchmark import sparse_attention

    if select_events is None:
        select_events = sparse_attention.program_select_events(record)
    ratios = [
        sum(ev["keys_visited"]) / sum(ev["keys_selected"])
        for ev in select_events
        if sum(ev.get("keys_selected", [])) > 0
    ]
    return sum(ratios) / len(ratios) if ratios else None
