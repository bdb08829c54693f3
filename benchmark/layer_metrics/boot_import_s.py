"""Seconds of the successor's ``boot.import`` span: ``import
adaptdl_tpu`` from the package's first line to its last (a child of
``boot.process``), what every restart of every job pays before
``initialize_job`` can run."""

UNIT = "s"
LAYER = "launcher + job bootstrap"
SOURCE = "program_span"
MOVES = "rescale_s"


def read(trace, spans, record, records=None):
    from benchmark import rescale_timeline as timeline

    return timeline.duration("boot.import", timeline.SUCCESSOR, records)
