"""Milliseconds per optimizer step in which the device sat idle while
the host was inside ``next(loader_iter)`` (exit agreement, sampler,
host gather): idle intervals of the first chip in the profiled slice
that fall under the benchmark's ``bench.data_next`` annotation, over
the step program's executions. What the loader costs the rate; its own
host time moves nothing while the device has work queued."""

UNIT = "ms"
LAYER = "data"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    return 1e3 * trace.idle_by_host().get("bench.data_next", 0.0) / program[1]
