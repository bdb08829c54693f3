"""Device milliseconds per optimizer step inside the Pallas flash
attention forward kernel: sum of the durations of its Mosaic custom
calls (the trace names them ``%attention.<n> = ... custom-call(...)
custom_call_target="tpu_custom_call"``; looked at by hand, PR 22) over
the step program's executions. With remat the kernel runs twice per
layer and micro-batch: once forward, once recomputed for backward."""

import re

UNIT = "ms"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(r'^%attention[.\d]* = .*custom_call_target="tpu_custom_call"')


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    return 1e3 * seconds / program[1] if events else None
