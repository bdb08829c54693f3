"""Device milliseconds of the jitted train-step program per optimizer
step: mean duration of the executions of the program with most device
time in the profiled slice (first chip's ``XLA Modules`` line)."""

UNIT = "ms"
LAYER = "step, device side"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    return None if program is None else 1e3 * program[2]
