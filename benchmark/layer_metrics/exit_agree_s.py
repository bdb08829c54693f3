"""Seconds of the predecessor's ``exit.agree`` span: the SIGTERM
handler's clock to the loader's exit agreement saying "exit" (up to two
loader steps: the vote is an asynchronous all-reduce launched at one
step and read at the next)."""

UNIT = "s"
LAYER = "rescale"
SOURCE = "program_span"
MOVES = "rescale_s"


def read(trace, spans, record, records=None):
    from benchmark import rescale_timeline as timeline

    return timeline.duration("exit.agree", timeline.PREDECESSOR, records)
