"""Seconds the window's worker spent tracing functions to jaxprs and
lowering them to MLIR, over every program it made: the program's
``jit.trace`` and ``jit.lower`` spans (adaptdl_tpu.trace's bridge to
``jax.monitoring``; outermost phases only, so they do not overlap).
No cache serves this work; an AOT-cache hit skips it."""

UNIT = "s"
LAYER = "trainer set-up"
SOURCE = "program_span"
MOVES = "setup_s"


def read(trace, spans, record):
    values = spans.get("jit.trace", []) + spans.get("jit.lower", [])
    return sum(values) if values else None
