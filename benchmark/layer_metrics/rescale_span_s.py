"""Seconds from the signal to the successor's first profiled step, on
the program's own records: the start of the predecessor's
``exit.agree`` span (the SIGTERM handler's wall clock) to the end of
the successor's ``restart.first_step``. The program's reading of the
parent's ``rescale_s``, and what an operator has without a benchmark
(``adaptdl-tpu trace``); the span closes at the first profiled step's
dispatch, the parent's clock stops after ``block_until_ready``, so the
two lie up to one step apart."""

UNIT = "s"
LAYER = "rescale"
SOURCE = "program_span"
MOVES = "rescale_s"


def read(trace, spans, record, records=None):
    from benchmark import rescale_timeline as timeline

    agree = timeline.find("exit.agree", timeline.PREDECESSOR, records)
    first = timeline.find(
        "restart.first_step", timeline.SUCCESSOR, records
    )
    if agree is None or first is None:
        return None
    return timeline.end(first) - float(agree["ts"])
