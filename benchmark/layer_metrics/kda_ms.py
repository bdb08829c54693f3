"""Device milliseconds per optimizer step inside the Pallas kernels
that carry the gated delta rule's state from chunk to chunk: sum of
the durations of their Mosaic custom calls over the step program's
executions. The kernels are named (``pallas_call(name="kda_fwd")``,
``name="kda_bwd"``), so the trace has them as ``%kda_fwd.<n>`` /
``%kda_bwd.<n> = ... custom-call(...)
custom_call_target="tpu_custom_call"``: any name that contains
``kda_``, as ``moe_gmm_ms`` finds its calls. The chunks' own work (the
decay sums, the triangular solve) runs as XLA fusions under the
``kda`` scope and is not in this number. A program without the
kernels (a parent commit) has no such op: nothing is read and the
metric is left out."""

import re

UNIT = "ms"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*kda_[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    return 1e3 * seconds / program[1] if events else None
