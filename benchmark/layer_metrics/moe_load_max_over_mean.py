"""Imbalance of the routed experts' load: the largest over the mean
number of rows a HELD expert received in a step, worst routed layer,
mean over the journalled steps. 1.0 is perfect balance; the grouped
products' time follows the sum of rows (they are dropless and skip
nothing a held expert was sent), but in the deployment this cell
stands for the slowest chip of the expert-parallel group sets the
step, and that is the chip with the largest held load.

From the program's own counters: the ``moe.load`` events the trainer
journals where it pulls its statistics (every tenth step). ``harness.
finish`` hands readers durations only, so this reader takes the events
from ``adaptdl_tpu.trace.snapshot_spans()`` itself; a program without
them (a parent commit) reads nothing and the metric is left out."""

UNIT = "x"
LAYER = "routed experts"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(trace, spans, record, load_events=None):
    from benchmark import grouped_matmul

    if load_events is None:
        load_events = grouped_matmul.program_load_events(record)
    ratios = []
    for ev in load_events:
        per_layer = [
            max(rows) / (sum(rows) / len(rows))
            for rows in ev.get("held_rows", [])
            if sum(rows) > 0
        ]
        if per_layer:
            ratios.append(max(per_layer))
    return sum(ratios) / len(ratios) if ratios else None
