"""Seconds the window's worker spent in the program's own
``trainer.init_state`` spans (adaptdl_tpu.trace): building the fresh
TrainState and dispatching its placement on the mesh. Host time; in
``-rescale`` the same seconds lie inside ``rescale_s``."""

UNIT = "s"
LAYER = "trainer set-up"
SOURCE = "program_span"
MOVES = "setup_s"


def read(trace, spans, record):
    values = spans.get("trainer.init_state")
    return sum(values) if values else None
