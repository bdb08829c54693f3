"""Device milliseconds per optimizer step inside all-reduce ops (the
data-axis ``pmean`` of the gradients and the GNS statistics), averaged
over the chips: union of the op intervals in the profiled slice over
the step program's executions."""

from benchmark.xplane import ALL_REDUCE

UNIT = "ms"
LAYER = "collectives"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    seconds, _exposed, events = trace.matching_s(ALL_REDUCE)
    return 1e3 * seconds / program[1] if events else None
