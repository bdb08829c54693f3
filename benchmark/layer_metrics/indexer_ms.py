"""Device milliseconds per optimizer step inside the indexer's kernel:
the index scores of every causal pair and the exact selection of the
2048 largest a query (``adaptdl_tpu/ops/sparse_attention.py``:
``pallas_call(name="sparse_index_select")``, in the trace as
``%sparse_index_select.<n> = ... custom_call_target="tpu_custom_call"``:
any name that contains ``sparse_index``). One call a sparse layer and
micro-batch (8 a step in keye-vl-2.0-30b-a3b-steady: 4 layers x 2):
a remat'd block saves the selection by name. The attention kernels recompute a tile's
scores to apply the selection; that time is theirs (``sparse_attn_ms``).
A program without the kernel has no such op: nothing is read and the
metric is left out."""

import re

UNIT = "ms"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*sparse_index[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    return 1e3 * seconds / program[1] if events else None
