"""Share of the routed experts' gated hidden values that are exactly
zero, in percent: ``hidden_zero`` over ``held rows x
moe_intermediate_size``, summed over the routed layers, mean over the
window's journalled whole steps. ``hidden = relu(x W_gate) * (x
W_up)`` of the rows PLACED for held experts (padding not counted): the
zeros are the model's own reason for ReGLU — what a later kernel may
skip in the down product and in the backward's three — and the
witness that the TIMED program gates with ``relu``: a ``silu`` gate
leaves no exact zero, and a program that gates so journals no such
counter.

From the program's own counters: ``hidden_zero`` beside ``held_rows``
in the ``moe.load`` events the trainer journals where it pulls its
statistics (every tenth step), taken from the program's own buffer
(``benchmark/grouped_matmul.py``); a program without the counter (a
parent commit, another activation) reads nothing and the metric is
left out."""

UNIT = "%"
LAYER = "routed experts"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(trace, spans, record, load_events=None):
    from benchmark import grouped_matmul

    if load_events is None:
        load_events = grouped_matmul.program_load_events(record)
    width = record.get("sizes", {}).get("moe_intermediate_size")
    shares = []
    for ev in load_events:
        placed = sum(sum(rows) for rows in ev.get("held_rows", []))
        if "hidden_zero" in ev and placed and width:
            shares.append(100.0 * sum(ev["hidden_zero"]) / (placed * width))
    return sum(shares) / len(shares) if shares else None
