"""Logit columns the sliding-window kernels compute a query over the
keys of its window, forward and backward together, all sliding layers.
1.0 is a kernel that multiplies only the band; whole blocks along its
two edges read 1.5 at blocks of 256 keys and a window of 512; a kernel
that walks every causal pair and masks reads ~16.3 at rows of 16 384.

From the program's own counters: ``keys_visited`` and
``keys_in_window`` of the ``window.keys`` events, journalled where a
windowed call's schedule is chosen (both are static). ``harness.
finish`` hands readers durations only, so this reader takes the events
from ``adaptdl_tpu.trace.snapshot_spans()`` itself; a program without
them (a parent commit) reads nothing and the metric is left out."""

UNIT = "x"
LAYER = "window attention"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(trace, spans, record, keys_events=None):
    from benchmark import window_attention

    if keys_events is None:
        keys_events = window_attention.program_keys_events(record)
    visited = sum(
        ev["keys_visited"] * ev.get("batch_heads", 1) for ev in keys_events
    )
    within = sum(
        ev["keys_in_window"] * ev.get("batch_heads", 1) for ev in keys_events
    )
    return visited / within if within else None
