"""Peak device memory in GiB after the window
(``memory_stats()["peak_bytes_in_use"]``, the fullest of the cell's
chips): guards the memory contract that bounds the batch."""

UNIT = "GiB"
LAYER = "device"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(trace, spans, record):
    peak = record.get("memory_peak_bytes")
    return None if peak is None else peak / 2**30
