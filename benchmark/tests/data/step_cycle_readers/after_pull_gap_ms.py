"""Milliseconds per optimizer step in which the device sat idle at the
end of a pull and after it: idle intervals of the first chip in the
profiled slice under the tail of the PROGRAM's annotation
``adaptdl.step.pull`` (the device's last op done -> ``block_until_ready``
returned; the gaps between a step's ops while the host waits are the
device's, not the host's) and under ``adaptdl.step.after_pull`` (the
``float()``s, the GNS and progress updates, the guard, the counters'
journal, to ``run_step``'s return), over the step program's
executions. A pull comes every tenth step, so a millisecond here is
ten after each pull. One part of what ``run_step_gap_ms`` reads under
``bench.run_step``; the other is ``shard_dispatch_gap_ms``."""

UNIT = "ms"
LAYER = "step, host side"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

NAMES = ("adaptdl.step.pull", "adaptdl.step.after_pull")


def read(trace, spans, record, annotations=None):
    from benchmark import step_cycles

    return step_cycles.gap_ms_a_step(trace, NAMES, annotations)
