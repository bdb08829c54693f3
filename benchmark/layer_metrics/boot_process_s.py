"""Seconds of the successor's ``boot.process`` span: the kernel
starting the worker's process to ``initialize_job`` being entered.
Over ``reach_chip_s`` (spawn -> ``jax.devices()`` returned, from
outside) it is what the script's remaining imports, this package's
among them, add before the job can open its first span."""

UNIT = "s"
LAYER = "launcher + job bootstrap"
SOURCE = "program_span"
MOVES = "rescale_s"


def read(trace, spans, record, records=None):
    from benchmark import rescale_timeline as timeline

    return timeline.duration("boot.process", timeline.SUCCESSOR, records)
