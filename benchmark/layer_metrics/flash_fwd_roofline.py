"""Share of its roofline the flash attention forward kernel reaches,
in percent: the least time one call could take on this chip — the
larger of its FLOPs over the peak FLOP/s and its bytes over the peak
bytes/s, both from ``benchmark/flops.py`` and ``benchmark/peaks.json``
— over the mean duration of its calls in the trace. At head size 64
and sequence 1024 the FLOP bound is the larger (0.131 ms against
0.123 ms for a call over 16 sequences)."""

import re

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
PATTERN = re.compile(r'^%attention[.\d]* = .*custom_call_target="tpu_custom_call"')


def read(trace, spans, record):
    from benchmark import flops

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
        flops.attention_forward_flops(**shape) / peak["bf16_flops_per_s"],
        flops.attention_forward_bytes(**shape) / peak["hbm_bytes_per_s"],
    )
    return 100.0 * least / (seconds / events)
