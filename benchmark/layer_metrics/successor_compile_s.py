"""Seconds the successor spent in backend compiles and persistent-cache
loads before its first completed step (sum of ``jax.monitoring``
backend-compile durations up to that step)."""

UNIT = "s"
LAYER = "rescale"
SOURCE = "program_counter"
MOVES = "rescale_s"


def read(trace, spans, record):
    return record.get("successor_compile_s")
