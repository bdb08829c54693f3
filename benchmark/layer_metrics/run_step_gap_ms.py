"""Milliseconds per optimizer step in which the device sat idle while
the host was inside ``ElasticTrainer.run_step`` (``shard_batch``'s
``device_put``, dispatch, every tenth step the metric drain): idle
intervals of the first chip in the profiled slice that fall under the
benchmark's ``bench.run_step`` annotation, over the step program's
executions. The host clock around the call cannot tell this: in a
device-bound cell the call blocks on the device and reads the step
time (PERF.md, Findings PR 22)."""

UNIT = "ms"
LAYER = "step, host side"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    return 1e3 * trace.idle_by_host().get("bench.run_step", 0.0) / program[1]
