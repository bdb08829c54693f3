"""Seconds the successor spent in the program's own ``ckpt.restore``
spans (adaptdl_tpu.trace), summed over the restored states."""

UNIT = "s"
LAYER = "rescale"
SOURCE = "program_span"
MOVES = "rescale_s"


def read(trace, spans, record):
    values = spans.get("ckpt.restore")
    return sum(values) if values else None
