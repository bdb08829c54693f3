"""Share of their roofline the delta rule's state kernels reach, in
percent: the least time ONE forward and ONE backward over all of a
layer's heads could take — for each the larger of its FLOPs over the
bf16 peak and its bytes over the HBM peak (``benchmark/kda.py``,
``benchmark/peaks.json``) — times the configuration's kda layers and
the step's micro-batches, over the device time a step spends in
``%kda_fwd.<n>`` and ``%kda_bwd.<n>`` (``kda_ms``). The count is of
the work the model defines: in how many calls the heads go, and a
forward call that runs a second time, are in the time and not in the
least, and the share cannot pass 100."""

import re

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
FWD = re.compile(
    r'^%[\w\-]*kda_fwd[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)
BWD = re.compile(
    r'^%[\w\-]*kda_bwd[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record):
    from benchmark import kda

    peak = record.get("peak_table")
    program = trace.step_program() if trace is not None else None
    shape = kda.layer_shape(record)
    if program is None or not peak or shape is None:
        return None
    fwd_s, _, fwd_calls = trace.matching_s(FWD)
    bwd_s, _, bwd_calls = trace.matching_s(BWD)
    if not (fwd_calls and bwd_calls) or fwd_s + bwd_s <= 0:
        return None
    sizes, geometry = record["sizes"], record["geometry"]
    pairs = len(sizes["linear_attn_config"]["kda_layers"]) * (
        geometry["accum_steps"] + 1
    )  # (layer, micro-batch) pairs a step
    least = pairs * (
        kda.least_seconds(shape, False, peak)
        + kda.least_seconds(shape, True, peak)
    )
    return 100.0 * least / ((fwd_s + bwd_s) / program[1])
