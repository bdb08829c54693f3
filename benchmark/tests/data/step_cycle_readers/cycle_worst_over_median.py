"""The longest over the median of the window's cycles, by their time
INSIDE the program: ``dur`` - ``outside_s`` of the ``step.cycle`` spans
(``adaptdl_tpu.trace.StepCycle``, one a pull of ``steps`` steps; cycles
of another ``steps`` than the window's most common are left out).
1.00-1.02 in a steady run; a run that loses a second of its window to
one stall (ROADMAP S14) reads 1.07-1.8, and the cycle's own attributes
(``<phase>_max_s``, ``cpu_s``, ``nivcsw``, ``majflt``, ``gc2``) say
whose second it was. The caller's loop is taken out so that the
profiler's start and stop, which run there in a traced run, are not
read as a stall. 1.0 where the window holds one whole cycle."""

UNIT = "x"
LAYER = "step, host side"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(trace, spans, record, cycles=None, steps_total=None):
    from benchmark import step_cycles

    return step_cycles.worst_over_median(
        step_cycles.window_cycles(record, cycles, steps_total)
    )
