"""Share of their roofline the grouped matrix products of the routed
expert layers reach, in percent: the least time the products that RAN
could take for the rows actually ROUTED — for each call the larger of
its FLOPs over the bf16 peak and its bytes over the HBM peak
(``benchmark/grouped_matmul.py``, ``benchmark/peaks.json``) — over the
device time the trace shows in ``%moe_gmm.<n>`` / ``%moe_tgmm.<n>``.

Rows come from the program's ``moe.load`` events (per routed layer the
rows each held expert received in a step, summed over its
micro-batches; padding to whole tiles and rows of absent experts are
not in them), calls from the trace (``moe_gmm`` and ``moe_tgmm`` calls
a step, spread evenly over routed layers and micro-batches: recomputed
forward products count, because they ran). Rows the kernels pad and
tiles they skip cost time and earn nothing here, so the share cannot
pass 100."""

import re

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
GMM = re.compile(
    r'^%[\w\-]*moe_gmm[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)
TGMM = re.compile(
    r'^%[\w\-]*moe_tgmm[\w\-]*[.\d]* = .*custom_call_target="tpu_custom_call"'
)


def read(trace, spans, record, load_events=None):
    from benchmark import grouped_matmul

    peak = record.get("peak_table")
    program = trace.step_program() if trace is not None else None
    if program is None or not peak:
        return None
    gmm_s, _, gmm_calls = trace.matching_s(GMM)
    tgmm_s, _, tgmm_calls = trace.matching_s(TGMM)
    if load_events is None:
        load_events = grouped_matmul.program_load_events(record)
    if not (gmm_calls or tgmm_calls) or not load_events:
        return None
    sizes, geometry = record["sizes"], record["geometry"]
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    steps = program[1]
    micro = geometry["accum_steps"] + 1
    # Mean over the journalled steps of each routed layer's rows per
    # held expert, a step.
    layers = len(load_events[0]["held_rows"])
    least = 0.0
    for layer in range(layers):
        per_expert = [
            sum(ev["held_rows"][layer][e] for ev in load_events)
            / len(load_events)
            for e in range(len(load_events[0]["held_rows"][layer]))
        ]
        with_rows = sum(1 for rows in per_expert if rows > 0)
        pairs = layers * micro  # (layer, micro-batch) pairs a step
        least += micro * grouped_matmul.least_seconds(
            sum(per_expert) / micro, with_rows, d, f,
            gmm_calls / steps / pairs, tgmm_calls / steps / pairs, peak,
        )
    measured = (gmm_s + tgmm_s) / steps
    return 100.0 * least / measured if measured > 0 else None
