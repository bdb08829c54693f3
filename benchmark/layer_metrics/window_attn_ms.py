"""Device milliseconds per optimizer step inside the NAMED calls of
sliding-window attention: sum of the durations of the Mosaic custom
calls whose name contains ``window_attn`` (``pallas_call(name=
"window_attn_fwd")``, ``name="window_attn_bwd"``: ``%window_attn_fwd.
<n>`` in the trace) over the step program's executions. The full
layers' kernels keep their own names (``flash_fwd_ms`` reads
``%attention.<n>``, which these names do not match). A program with no
such call (a parent commit, or a windowed layer that runs as a mask
over the full walk) reads nothing and the metric is left out."""

import re

UNIT = "ms"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*window_attn[\w\-]*[.\d]* = '
    r'.*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    return 1e3 * seconds / program[1] if events else None
