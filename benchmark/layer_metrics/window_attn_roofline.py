"""Share of its roofline sliding-window attention reaches, in percent:
the least time ONE forward and ONE backward pass over a sliding
layer's BAND could take — for each the larger of its FLOPs over the
bf16 peak and its bytes over the HBM peak (``benchmark/
window_attention.py``, ``benchmark/peaks.json``) — times the
configuration's sliding layers and the step's micro-batches, over the
device time a step spends in the calls named ``window_attn*``
(``window_attn_ms``). The count is of what the model defines, whatever
implements it: whole blocks multiplied above the band's edges, and k
and v repeated for a group's query heads, are in the time and not in
the least, so the share cannot pass 100."""

import re

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*window_attn[\w\-]*[.\d]* = '
    r'.*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    from benchmark import window_attention

    peak = record.get("peak_table")
    program = trace.step_program() if trace is not None else None
    shape = window_attention.layer_shape(record)
    if program is None or not peak or shape is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    if not events or seconds <= 0:
        return None
    least = window_attention.layer_passes(record) * (
        window_attention.least_seconds(shape, False, peak)
        + window_attention.least_seconds(shape, True, peak)
    )
    return 100.0 * least / (seconds / program[1])
