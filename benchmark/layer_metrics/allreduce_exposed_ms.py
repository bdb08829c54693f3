"""The part of ``allreduce_ms`` during which no other op ran on that
chip: what any work on the collective can give back at most."""

from benchmark.xplane import ALL_REDUCE

UNIT = "ms"
LAYER = "collectives"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    _seconds, exposed, events = trace.matching_s(ALL_REDUCE)
    return 1e3 * exposed / program[1] if events else None
