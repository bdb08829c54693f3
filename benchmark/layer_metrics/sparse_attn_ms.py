"""Device milliseconds per optimizer step inside the sparse attention
kernels, forward and backward: sum of the durations of their Mosaic
custom calls over the step program's executions. The kernels are named
(``adaptdl_tpu/ops/sparse_attention.py``: ``sparse_attn_fwd``,
``sparse_attn_kl`` — the indexer's loss, which needs the attention's
probabilities a second time —, ``sparse_attn_bwd_q``,
``sparse_attn_bwd_kv``), so the trace has them as
``%sparse_attn_fwd.<n> = ... custom-call(...)
custom_call_target="tpu_custom_call"``: any name that contains
``sparse_attn``. The index scores and the selection are
``%sparse_index_select.<n>`` (``indexer_ms``) and neither pattern
matches the other's names. In keye-vl-2.0-30b-a3b-steady: 4 layers x 2
micro-batches x 4 kernels = 32 calls a step (a remat'd block saves the
forward's results by name and runs it once). A program without the
kernels has no such op: nothing is read and the metric is left out."""

import re

UNIT = "ms"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*sparse_attn[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    return 1e3 * seconds / program[1] if events else None
