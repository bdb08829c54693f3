"""Device milliseconds per optimizer step inside the NAMED calls that
do the delta rule's chunk-local work (the decay sums, ``A``, ``B``, the
triangular inverse, ``W_k``, ``W_v``, and their backward): sum of the
durations of the Mosaic custom calls whose name contains
``delta_chunk`` (``pallas_call(name="delta_chunk_fwd")``,
``name="delta_chunk_bwd"``: ``%delta_chunk_fwd.<n>`` in the trace) over
the step program's executions, as ``kda_ms`` finds the state kernels.
Where that work runs as XLA fusions and no named kernel (a parent
commit, or the fallback path), nothing is read and the metric is left
out."""

import re

UNIT = "ms"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*delta_chunk[\w\-]*[.\d]* = '
    r'.*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    return 1e3 * seconds / program[1] if events else None
