"""Milliseconds a step in which an EMPTY device waited for the PROGRAM,
by the program's own clock: (``exposed_s`` - ``exposed_outside_s``) /
``steps`` of the ``step.cycle`` spans (``adaptdl_tpu.trace.StepCycle``),
median over the cycles whose steps all lie in the window. ``exposed_s``
runs from the previous pull's return — the queue has just drained — to
the return of the cycle's first dispatch: what follows the pull, the
caller's loop, the loader, ``shard_batch`` and one dispatch;
``exposed_outside_s`` is the caller's loop's part of it, taken out
because in a traced run the profiler's start or stop stands there
(1.8 s in ``qwen3-next-80b-a3b-steady``'s one traced step: my chip
run, PR 52) and because ``run_step_gap_ms`` + ``data_next_gap_ms``,
whose reading from the device trace this is the program's own, leave
the caller's loop out too (``host:other``). The two differ by what the
host's clock cannot see (the dispatch returns before the device
starts) and by the gaps between a step's ops that the trace counts
under the call."""

UNIT = "ms"
LAYER = "step, host side"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(trace, spans, record, cycles=None, steps_total=None):
    from benchmark import step_cycles

    return step_cycles.exposed_ms_a_step(
        step_cycles.window_cycles(record, cycles, steps_total)
    )
