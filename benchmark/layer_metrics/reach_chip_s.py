"""Seconds from spawning the window's worker to ``jax.devices()``
having returned in it (process start, ``import jax``, claiming the
chip): the worker's own timestamp against its parent's."""

UNIT = "s"
LAYER = "launcher + job bootstrap"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(trace, spans, record):
    return record["device"].get("reach_chip_s")
