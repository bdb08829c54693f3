"""Rows the streamed head multiplies against the output table per
trained token: 1.0 for a model with one stream of rows, 2.0 where a
multi-token-prediction module sends a second stream through the same
table (``glm-4.7-flash``: 2 x 16 384 rows a micro-batch of 16 384
tokens, the last position of a row included although it weighs 0). A
later change that stops a stream from reading the table again moves it
down with the rate; one that silently drops a stream moves it down
with the objective (``mtp_loss_share`` then falls too).

From the program's own counters: ``head_rows`` over ``tokens`` of the
``mtp.schedule`` events (static counts, journalled where the loss is
traced) whose ``tokens`` are one micro-batch of the cell's geometry.
A program without the event (a parent commit) reads nothing and the
metric is left out."""

UNIT = "x"
LAYER = "head"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(trace, spans, record, events=None):
    if events is None:
        from benchmark import mtp

        events = mtp.program_events("mtp.schedule")
    try:
        tokens = (
            record["geometry"]["atomic_bsz"]
            * record["sizes"]["sequence_length"]
        )
    except KeyError:
        return None
    ratios = [
        rec["attrs"]["head_rows"] / rec["attrs"]["tokens"]
        for rec in events
        if rec.get("name") == "mtp.schedule"
        and rec.get("attrs", {}).get("tokens") == tokens
    ]
    return sum(ratios) / len(ratios) if ratios else None
