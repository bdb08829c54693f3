"""Seconds of the program's own ``restart.first_step`` span
(adaptdl_tpu.trace) in the window's worker: ``initialize_job`` to the
first profiled step, the program's reading of what the parent's clock
reports as ``rescale_s`` less save, exit and reaching the chip."""

UNIT = "s"
LAYER = "launcher + job bootstrap"
SOURCE = "program_span"
MOVES = "setup_s"


def read(trace, spans, record):
    values = spans.get("restart.first_step")
    return sum(values) if values else None
