"""Seconds from the final checkpoint being written to the predecessor
being gone: the parent's ``save_exit_s`` (SIGTERM sent -> exit code
collected) less the program's own account of signal -> write done (the
end of ``ckpt.write`` less the start of ``exit.agree``). The program's
``exit.atexit`` span names the part of it that is the joins of its own
atexit hooks; the rest (jax's hooks, interpreter finalisation, the
runtime letting go of the chip) no span of a dying process can hold,
so it is taken from outside."""

UNIT = "s"
LAYER = "rescale"
SOURCE = "host_clock"
MOVES = "rescale_s"


def read(trace, spans, record, records=None):
    from benchmark import rescale_timeline as timeline

    save_exit_s = record.get("parent", {}).get("save_exit_s")
    agree = timeline.find("exit.agree", timeline.PREDECESSOR, records)
    write = timeline.find("ckpt.write", timeline.PREDECESSOR, records)
    if save_exit_s is None or agree is None or write is None:
        return None
    return save_exit_s - (timeline.end(write) - float(agree["ts"]))
