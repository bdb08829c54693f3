"""Seconds of the predecessor's last ``ckpt.snapshot`` span: the final
save's device -> host copy of every registered state, read from the
records the dying worker handed to its successor."""

UNIT = "s"
LAYER = "rescale"
SOURCE = "program_span"
MOVES = "rescale_s"


def read(trace, spans, record, records=None):
    from benchmark import rescale_timeline as timeline

    return timeline.duration(
        "ckpt.snapshot", timeline.PREDECESSOR, records
    )
