"""Programs the process asked the compiler for inside the measured
window (``jax.monitoring`` backend-compile events, cache-served or
not). Must be 0: anything else makes the run not ``correct``."""

UNIT = "count"
LAYER = "step, host side"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(trace, spans, record):
    return record.get("compiles_in_window")
