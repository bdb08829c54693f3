"""Seconds of the predecessor's last ``ckpt.write`` span: the final
save's serialisation, write, fsync and rename into the checkpoint
directory, read from the records the dying worker handed to its
successor."""

UNIT = "s"
LAYER = "rescale"
SOURCE = "program_span"
MOVES = "rescale_s"


def read(trace, spans, record, records=None):
    from benchmark import rescale_timeline as timeline

    return timeline.duration("ckpt.write", timeline.PREDECESSOR, records)
