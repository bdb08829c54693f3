"""Times the flash attention FORWARD kernel runs per held layer and
micro-batch in an optimizer step of a looped model: its Mosaic custom
calls in the trace (the accepted ``%attention.<n>`` pattern of
``flash_fwd_ms``; a ``lax.scan`` over the passes executes one call
site once a pass, and every execution is an event) over the step
program's executions, ``num_hidden_layers`` and the step's
micro-batches. ``total_ut_steps`` (4.0) when every pass runs and
nothing runs twice; twice that if a remat'd block application re-ran
its kernel in the backward; less if a pass went missing. A program
without the kernel, or a trace without a step program, reads nothing
and the metric is left out."""

import re

UNIT = "x"
LAYER = "step, device side"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(r'^%attention[.\d]* = .*custom_call_target="tpu_custom_call"')


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    _seconds, _exposed, events = trace.matching_s(PATTERN)
    if not events:
        return None
    micro_batches = record["geometry"]["accum_steps"] + 1
    layers = record["sizes"]["num_hidden_layers"]
    return events / (program[1] * layers * micro_batches)
