"""Share of its roofline the flash attention BACKWARD kernel reaches,
in percent: the least time one call could take on this chip — the
larger of its FLOPs over the peak FLOP/s and its bytes over the peak
bytes/s, from ``benchmark/attention_backward.py`` and
``benchmark/peaks.json`` — over the mean duration of its calls in the
trace (``%flash_bwd.<n>``, as ``flash_bwd_ms`` finds them). At head
size 64 and sequence 1024 the FLOP bound is the larger (0.327 ms
against 0.247 ms for a call over 16 sequences)."""

import re

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(
    r'^%[\w\-]*flash_bwd[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    from benchmark import attention_backward

    peak = record.get("peak_table")
    if trace is None or not peak:
        return None
    seconds, _exposed, events = trace.matching_s(PATTERN)
    if not events:
        return None
    sizes, geometry = record["sizes"], record["geometry"]
    shape = dict(
        batch_heads=geometry["atomic_bsz"] * sizes["n_head"],
        seq_len=sizes["n_positions"],
        head_dim=sizes["n_embd"] // sizes["n_head"],
    )
    least = max(
        attention_backward.attention_backward_flops(**shape)
        / peak["bf16_flops_per_s"],
        attention_backward.attention_backward_bytes(**shape)
        / peak["hbm_bytes_per_s"],
    )
    return 100.0 * least / (seconds / events)
