"""Seconds the window's worker spent in the program's own
``step.calibrate`` spans (adaptdl_tpu.trace): ``calibrate_accum_time``
whole, with the trace, lower and compile (or cache load) of the
calibration program and its timed runs. Its ``jit.*`` children are
also in ``trace_lower_s``: the two do not add."""

UNIT = "s"
LAYER = "trainer set-up"
SOURCE = "program_span"
MOVES = "setup_s"


def read(trace, spans, record):
    values = spans.get("step.calibrate")
    return sum(values) if values else None
