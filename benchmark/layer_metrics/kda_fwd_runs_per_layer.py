"""Times the delta rule's FORWARD state kernel runs per "kda" layer and
micro-batch in an optimizer step: its Mosaic custom calls in the trace
(``%kda_fwd.<n>``) over the BACKWARD kernel's (``%kda_bwd.<n>``), which
runs once a layer, micro-batch and group of heads whatever the
forward does (a layer's heads run in groups, a call a group). 1.0
when nothing runs twice; 2.0 when the rule's backward
runs the forward kernel again, as it does where a long row's heads go
a group at a time and each group's work is done again in its
backward; 3.0 if a remat'd block's recomputation ran it too (the
block keeps the rule's output by name so that it does not). What
``loop_attention_runs_per_layer`` is to the looped stack. A program without the kernel, or a trace without a step
program, reads nothing and the metric is left out."""

import re

UNIT = "x"
LAYER = "step, device side"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*kda_fwd[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)
BACKWARD = re.compile(
    r'^%[\w\-]*kda_bwd[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    if trace is None or trace.step_program() is None:
        return None
    _seconds, _exposed, forward = trace.matching_s(PATTERN)
    _seconds, _exposed, backward = trace.matching_s(BACKWARD)
    return forward / backward if forward and backward else None
