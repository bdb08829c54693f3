"""Percent of the profiled steady slice in which no op ran on the
device: 1 - union of device-op intervals / slice, averaged over the
chips. The headroom the host path leaves; should stay low in the LM cells."""

UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(trace, spans, record):
    if trace is None or not trace.busy_s():
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
