"""Milliseconds per optimizer step in which the device sat idle while
the host was in ``shard_batch`` (the batch's ``device_put``) or in the
call of the step program: idle intervals of the first chip in the
profiled slice under the PROGRAM's own annotations
``adaptdl.step.shard`` and ``adaptdl.step.dispatch``
(``adaptdl_tpu.trace.StepCycle``), over the step program's executions.
One part of what ``run_step_gap_ms`` reads under the benchmark's
``bench.run_step`` from outside; the other is ``after_pull_gap_ms``."""

UNIT = "ms"
LAYER = "step, host side"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

NAMES = ("adaptdl.step.shard", "adaptdl.step.dispatch")


def read(trace, spans, record, annotations=None):
    from benchmark import step_cycles

    return step_cycles.gap_ms_a_step(trace, NAMES, annotations)
