"""Seconds on the parent's clock from sending SIGTERM to the
predecessor having exited 143 (up to two steps of exit agreement, the
device->host snapshot, the checkpoint write, interpreter teardown)."""

UNIT = "s"
LAYER = "rescale"
SOURCE = "host_clock"
MOVES = "rescale_s"


def read(trace, spans, record):
    return record.get("parent", {}).get("save_exit_s")
