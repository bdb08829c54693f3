"""Milliseconds of the host's OWN work a step: the loader between two
``yield``s, ``shard_batch``, ONE dispatch of the step program with room
in the runtime's queue, and what follows a pull (the ``float()``s, the
GNS and progress updates, the guard, the counters' journal) —
(``data_next_s`` + ``shard_s`` + ``after_pull_s``) / ``steps`` +
min(``dispatch_steps_s``) of the program's ``step.cycle`` spans
(``adaptdl_tpu.trace.StepCycle``, one a pull), median over the cycles
whose steps all lie in the window. Not the wait for the device
(``pull_s``) and not the caller's loop (``outside_s``). On the host's
clock, so it reads the same whether or not the device is kept busy:
what ``run_step_gap_ms`` (the device's idleness under the call) cannot
see in a device-bound cell. **Why the cheapest dispatch and not
``dispatch_s`` / ``steps``**: where the runtime keeps only a few steps
in flight (the ``gpt2-124m`` cells' AOT-cached non-donating step:
three) all but the first dispatches of a cycle wait a whole device
step each (``gpt2-124m-steady``: 15, 16, 14, then seven of ~197 ms: my
chip run, PR 52) and the mean reads the device's step time, 144 ms
there; a donating cell dispatches all ten in ~2.5 ms each. The span
keeps the whole list and ``dispatch_s``."""

UNIT = "ms"
LAYER = "step, host side"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(trace, spans, record, cycles=None, steps_total=None):
    from benchmark import step_cycles

    return step_cycles.host_ms_a_step(
        step_cycles.window_cycles(record, cycles, steps_total)
    )
