"""Model FLOP/s utilisation in percent: the benchmark's own FLOP
function (matmul-only, causal half, recomputation not counted) times
the window's measured rate over chips times the peak of
``benchmark/peaks.json`` for this exact ``device_kind``. In a traced
run the rate is the traced run's own (slightly below an untraced
one)."""

UNIT = "%"
LAYER = "step, device side"
SOURCE = "host_clock"
MOVES = "tokens_per_s"


def read(trace, spans, record):
    from benchmark import flops

    peak = record.get("peak_table")
    if not peak or not record.get("flops_per_unit"):
        return None
    return flops.mfu_percent(
        record["flops_per_unit"],
        record["rate"],
        record["chips"],
        peak["bf16_flops_per_s"],
    )
