"""What the program says of its own step path, for the five readers of
the layer "step, host side": ``host_step_ms``, ``host_exposed_ms``,
``cycle_worst_over_median`` (from the program's ``step.cycle`` spans)
and ``shard_dispatch_gap_ms``, ``after_pull_gap_ms`` (from its
``adaptdl.step.<phase>`` profiler annotations).

Two sources, both the program's own (``adaptdl_tpu/trace.py``:
``StepCycle``, PR 52):

- the ``step.cycle`` spans: one a gated pull (every tenth step), from
  the previous pull's return to this one's, with the sum and the
  largest of each of the host's phases (``data_next_s``, ``shard_s``,
  ``dispatch_s``, ``pull_s``, ``after_pull_s``, ``calibrate_s``,
  ``outside_s``), ``exposed_s``, ``steps`` and ``first_step``.
  ``harness.finish`` hands readers durations only, so the readers take
  the records from ``adaptdl_tpu.trace.snapshot_spans()`` themselves,
  as ``moe_load_max_over_mean`` takes its events;
- the ``adaptdl.step.<phase>`` annotations the same marks write onto
  the host plane of the run's trace. ``xplane.load`` keeps host events
  named ``bench.*`` only and ``Trace`` keeps no path, so they are read
  here, with JAX's own reader, from the ``.xplane.pb`` under the
  ``work_dir`` of the worker's spec.

A program without them (a parent commit, ``ADAPTDL_TRACE=off``) gives
nothing to read and every reader returns None: the metric is left out,
never 0.

**Not attached to a cell**: in this harness a cell reports a reader
only if its ``workloads/<cell>.json`` names it, an edit of an accepted
file, which is a ``benchmark`` PR's. The five reader files wait in
``benchmark/tests/data/step_cycle_readers/`` and
``benchmark/tests/attach_step_cycle_readers.py`` makes the whole edit
(data only) on a scratch copy of a checkout: PERF.md section 7.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import statistics
import sys

from benchmark import xplane

ANNOTATION_PREFIX = "adaptdl."
PULL = ANNOTATION_PREFIX + "step.pull"
# The host's own work a step: not the wait for the device (``pull_s``),
# not the caller's loop (``outside_s``), not a new batch size's
# first-time work (``calibrate_s``: no part of a steady cycle) — and
# the dispatch at what it costs when the runtime's queue has room.
HOST_PHASES = ("data_next_s", "shard_s", "after_pull_s")


def program_cycles() -> tuple[list[dict], int]:
    """(this process's ``step.cycle`` records, its count of
    ``run_step`` calls); ([], 0) for a program that has no such clock
    or had tracing off."""
    try:
        from adaptdl_tpu import trace
    except ImportError:
        return [], 0
    clock = getattr(trace, "step_cycle", None)
    if clock is None:
        return [], 0
    own = os.getpid()  # a successor's ring holds its predecessor's too
    return [
        rec for rec in trace.snapshot_spans()
        if rec.get("name") == "step.cycle" and rec.get("pid") == own
    ], clock.steps_total


def window_cycles(record: dict, cycles=None, steps_total=None) -> list[dict]:
    """The attributes (with ``dur``) of the ``step.cycle`` records whose
    steps ALL lie in the measured window: the window is the process's
    last ``record["steps"]`` calls of ``run_step``, and a cycle that
    began before it holds the warm-up's drain and ``quiesce``'s wait in
    its ``outside_s``. ``cycles`` and ``steps_total`` (the process's
    count of ``run_step`` calls) are the program's own unless a test
    hands them in."""
    if cycles is None or steps_total is None:
        cycles, steps_total = program_cycles()
    first_of_window = steps_total - int(record.get("steps", 0)) + 1
    return [
        dict(rec["attrs"], dur=float(rec["dur"]))
        for rec in cycles
        if rec["attrs"].get("steps", 0) > 0
        and rec["attrs"]["first_step"] >= first_of_window
    ]


def median_ms_a_step(cycles: list[dict], keys: tuple[str, ...]):
    """Median over the cycles of (the sum of ``keys``) / ``steps``, in
    milliseconds; None without cycles."""
    values = [
        1e3 * sum(c[k] for k in keys) / c["steps"] for c in cycles
    ]
    return statistics.median(values) if values else None


def exposed_ms_a_step(cycles: list[dict]):
    """Median over the cycles of (``exposed_s`` less the caller's
    loop's part of it, ``exposed_outside_s``) / ``steps``, in
    milliseconds; None without cycles."""
    values = [
        1e3 * (c["exposed_s"] - c.get("exposed_outside_s", 0.0)) / c["steps"]
        for c in cycles
    ]
    return statistics.median(values) if values else None


def host_ms_a_step(cycles: list[dict]):
    """Median over the cycles of the host's OWN work a step, in
    milliseconds: (``data_next_s`` + ``shard_s`` + ``after_pull_s``) /
    ``steps`` + the cycle's CHEAPEST dispatch. Where the runtime holds
    only a few steps in flight (three in the ``gpt2-124m`` cells, whose
    AOT-cached step does not donate: the first three dispatches after a
    pull take ~15 ms each, every later one waits a whole device step,
    ~197 ms; the donating cells dispatch all ten in ~2.5 ms each),
    ``dispatch_s`` / ``steps`` reads the device's step time; the
    cheapest of
    ``dispatch_steps_s`` is a dispatch that found room, which the
    first after a pull always does. A record without the list counts
    ``dispatch_s`` / ``steps``. None without cycles."""
    values = []
    for c in cycles:
        per_step = c.get("dispatch_steps_s") or [c["dispatch_s"] / c["steps"]]
        values.append(
            1e3 * (sum(c[k] for k in HOST_PHASES) / c["steps"] + min(per_step))
        )
    return statistics.median(values) if values else None


def worst_over_median(cycles: list[dict]):
    """The longest over the median of ``dur`` - ``outside_s`` among the
    cycles of the usual ``steps``; None without cycles."""
    if not cycles:
        return None
    usual = statistics.mode(c["steps"] for c in cycles)
    inside = [
        c["dur"] - c["outside_s"] for c in cycles if c["steps"] == usual
    ]
    median = statistics.median(inside)
    return max(inside) / median if median > 0 else None


def trace_file() -> str | None:
    """The run's ``.xplane.pb``, found as ``harness.SliceTracer.
    trace_file`` finds it, under the ``work_dir`` of this worker's own
    spec (``python benchmark/worker.py <spec.json> <fd>``)."""
    try:
        with open(sys.argv[1], encoding="utf-8") as f:
            work_dir = json.load(f)["work_dir"]
    except (IndexError, OSError, ValueError, KeyError, TypeError):
        return None
    found = glob.glob(
        os.path.join(
            work_dir, "trace", "plugins", "profile", "*", "*.xplane.pb"
        )
    )
    return found[0] if found else None


def program_annotations() -> list[xplane.Event]:
    """The program's own annotations (names starting ``adaptdl.``) on
    the host plane of the run's trace, on the device events' clock."""
    path = trace_file()
    return list(annotations_in(path)) if path else []


@functools.lru_cache(maxsize=1)  # two readers read one file
def annotations_in(path: str) -> tuple[xplane.Event, ...]:
    from jax.profiler import ProfileData

    events: list[xplane.Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            events.extend(
                xplane.Event(
                    ev.name,
                    float(ev.start_ns),
                    float(ev.start_ns + ev.duration_ns),
                )
                for ev in line.events
                if ev.name.startswith(ANNOTATION_PREFIX)
            )
    return tuple(events)


def pull_tails(
    idle: list[xplane.Interval], pulls: list[xplane.Event]
) -> list[xplane.Event]:
    """Each ``adaptdl.step.pull`` cut down to its tail: from the end of
    the device's last op to the return of ``block_until_ready``. While
    the host waits in the pull the device works through its queue, and
    the short gaps between its ops there are not the host's; the tail
    is."""
    tails = []
    for pull in pulls:
        for start, end in idle:
            if start < pull.end <= end:
                tails.append(
                    xplane.Event(
                        pull.name, max(start, pull.start), pull.end
                    )
                )
                break
    return tails


def idle_by_program(trace, annotations=None) -> dict[str, float] | None:
    """Nanoseconds of chip 0's idleness in the profiled slice by what
    the host was doing, the program's annotations beside the
    benchmark's (``xplane.gaps`` and ``xplane.attribute`` as they are:
    the innermost span wins, so a gap under ``adaptdl.step.shard``
    inside ``bench.run_step`` is counted once, under the program's
    name); of ``adaptdl.step.pull`` only its tail counts. None where
    there is no trace, no device plane or no annotation of the
    program."""
    if trace is None or not trace.devices or trace.window() is None:
        return None
    if annotations is None:
        annotations = program_annotations()
    if not annotations:
        return None
    lo, hi = trace.window()
    idle = xplane.gaps(trace.busy(trace.devices[0]), lo, hi)
    spans = (
        [e for e in trace.host if e.name != xplane.SLICE_SPAN]
        + [e for e in annotations if e.name != PULL]
        + pull_tails(idle, [e for e in annotations if e.name == PULL])
    )
    return xplane.attribute(idle, spans)


def gap_ms_a_step(trace, names: tuple[str, ...], annotations=None):
    """Milliseconds a step of that idleness under the program's
    annotations ``names``; None where there is nothing to read or no
    step program."""
    program = trace.step_program() if trace is not None else None
    if program is None:
        return None
    by_name = idle_by_program(trace, annotations)
    if by_name is None:
        return None
    return 1e3 * sum(by_name.get(n, 0.0) for n in names) / 1e9 / program[1]
