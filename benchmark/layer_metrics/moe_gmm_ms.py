"""Device milliseconds per optimizer step inside the grouped matrix
products of the routed expert layers: sum of the durations of their
Mosaic custom calls over the step program's executions. The kernels
are named (``pallas_call(name="moe_gmm")`` for ``x W`` and ``dy W^T``,
``name="moe_tgmm"`` for ``x^T dy``), so the trace has them as
``%moe_gmm.<n>`` / ``%moe_tgmm.<n> = ... custom-call(...)
custom_call_target="tpu_custom_call"``: any name that contains
``moe_gmm`` or ``moe_tgmm``, as ``flash_bwd_ms`` finds its calls. In
lfm2-8b-a1b-steady: 4 routed layers x 2 micro-batches x (3 forward + 3
recomputed under remat + 3 input-gradient ``moe_gmm`` + 3 ``moe_tgmm``)
= 96 calls a step. A program without the kernels has no such op:
nothing is read and the metric is left out."""

import re

UNIT = "ms"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*moe_t?gmm[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    return 1e3 * seconds / program[1] if events else None
