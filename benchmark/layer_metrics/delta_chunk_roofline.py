"""Share of its roofline the delta rule's chunk-local work reaches, in
percent: the least time ONE forward and ONE backward pass over a
layer's heads could take FOR A RULE WITH ONE DECAY A HEAD — for each
the larger of its FLOPs over the bf16 peak and its bytes over the HBM
peak (``benchmark/delta_chunk.py``, ``benchmark/peaks.json``) — times
the configuration's delta-rule layers and the step's micro-batches,
over the device time a step spends in the calls named
``delta_chunk*`` (``delta_chunk_ms``). The count is of the work the
model defines, whatever implements it: a forward formed again in a
group's backward, and a per-channel body fed a broadcast decay, are in
the time and not in the least, so the share cannot pass 100 and reads
low where the body does more than one decay a head needs."""

import re

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*delta_chunk[\w\-]*[.\d]* = '
    r'.*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    from benchmark import delta_chunk

    peak = record.get("peak_table")
    program = trace.step_program() if trace is not None else None
    shape = delta_chunk.layer_shape(record)
    if program is None or not peak or shape is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    if not events or seconds <= 0:
        return None
    least = delta_chunk.layer_passes(record) * (
        delta_chunk.least_seconds(shape, False, peak)
        + delta_chunk.least_seconds(shape, True, peak)
    )
    return 100.0 * least / (seconds / program[1])
