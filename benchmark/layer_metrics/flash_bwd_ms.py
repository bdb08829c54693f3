"""Device milliseconds per optimizer step inside the Pallas flash
attention BACKWARD kernel: sum of the durations of its Mosaic custom
calls over the step program's executions. The kernel is named
(``pallas_call(name="flash_bwd")``), so the trace has it as
``%flash_bwd.<n> = ... custom-call(...)
custom_call_target="tpu_custom_call"`` from inside a model's scopes
(looked at in the gpt2-124m step program compiled for a v5e) and as
``%transpose_jvp_flash_bwd__.<n>`` where no scope surrounds the call:
any name that contains ``flash_bwd``. The forward is
``%attention.<n>`` (``flash_fwd_ms``) and neither pattern matches the
other's name. One call per layer and micro-batch:
24 a step in the gpt2-124m cells. A program without the kernel (the
parent of PR 26 ran the backward as a ``lax.scan`` of XLA fusions) has
no such op: nothing is read and the metric is left out."""

import re

UNIT = "ms"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*flash_bwd[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    return 1e3 * seconds / program[1] if events else None
