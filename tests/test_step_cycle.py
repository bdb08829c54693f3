"""The step cycle: the step path marks its phases on one clock and
every gated pull writes ONE ``step.cycle`` span (``trace.StepCycle``,
marked by ``AdaptiveDataLoader``'s iterator and ``ElasticTrainer.
run_step``); the same marks are profiler annotations.
"""

from __future__ import annotations

import glob
import time

import numpy as np
import pytest

from adaptdl_tpu import metrics, trace
from adaptdl_tpu.data import AdaptiveDataLoader

PHASES = trace.CYCLE_PHASES


@pytest.fixture(autouse=True)
def _clean_metrics(monkeypatch):
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    metrics._reset_state()
    yield
    metrics._reset_state()


def _cycles():
    return [
        r for r in trace.snapshot_spans() if r["name"] == "step.cycle"
    ]


class _SlowRows(dict):
    """A dataset whose gather sleeps: the loader's own work."""

    delay = 0.0

    def items(self):
        time.sleep(self.delay)
        return super().items()


def _job(samples=8 * 40, dataset_type=dict):
    from tests.test_compile_cache import _linear_trainer

    trainer = _linear_trainer()[0]
    rng = np.random.default_rng(0)
    dataset = dataset_type(
        x=rng.normal(size=(samples, 4)).astype(np.float32),
        y=rng.normal(size=(samples,)).astype(np.float32),
    )
    loader = AdaptiveDataLoader(dataset, batch_size=8, seed=0)
    return trainer, loader, trainer.init_state()


def _run(trainer, loader, state, steps, after_step=None):
    done = 0
    for batch in loader:
        state, _ = trainer.run_step(state, batch, loader)
        done += 1
        if after_step is not None:
            after_step(done)
        if done == steps:
            break
    return state


# What float64 loses over a cycle's few dozen differences of a clock
# that reads up to 1e7 s: a bound on rounding, not on elapsed time.
ROUNDING_S = 1e-6


def _identity_gap(rec):
    """The phases against ``dur``: both are sums of differences of
    the SAME clock reads (every mark ends one phase and starts the
    next), so they differ by rounding alone, whatever the load."""
    attrs = rec["attrs"]
    return abs(sum(attrs[f"{p}_s"] for p in PHASES) - rec["dur"])


def test_a_loop_of_25_steps_writes_two_whole_cycles(monkeypatch):
    """The trainer pulls early once, then every tenth step: pulls at
    steps 1, 11 and 21, so one cycle of the first step alone and two
    whole ones; steps 22-25 close none."""
    reads, clock = [], trace._clock

    def read():
        reads.append(clock())
        return reads[-1]

    monkeypatch.setattr(trace, "_clock", read)
    trainer, loader, state = _job()
    _run(trainer, loader, state, 25)
    cycles = _cycles()
    assert [c["attrs"]["first_step"] for c in cycles] == [1, 2, 12]
    assert [c["attrs"]["steps"] for c in cycles] == [1, 10, 10]
    assert trace.step_cycle.steps_total == 25
    for rec in cycles:
        attrs = rec["attrs"]
        assert _identity_gap(rec) < ROUNDING_S, rec
        for name in PHASES:
            assert 0.0 <= attrs[f"{name}_max_s"] <= attrs[f"{name}_s"]
        assert len(attrs["dispatch_steps_s"]) == attrs["steps"]
        assert sum(attrs["dispatch_steps_s"]) == pytest.approx(
            attrs["dispatch_s"]
        )
        assert 0.0 < attrs["exposed_s"] <= rec["dur"]
        assert 0.0 <= attrs["exposed_outside_s"] <= min(
            attrs["exposed_s"], attrs["outside_s"]
        )
        assert attrs["cpu_s"] >= 0.0 and attrs["threads"] >= 1
        for count in ("nivcsw", "majflt", "gc2"):
            assert isinstance(attrs[count], int) and attrs[count] >= 0
    whole = cycles[1:]
    for rec in whole:
        assert len(rec["attrs"]["data_next_steps_s"]) == 10
        assert rec["attrs"]["calibrate_s"] == 0.0
    # The batch size's first-time work (calibration, the program's
    # build) is named, and is the first cycle's alone.
    assert cycles[0]["attrs"]["calibrate_s"] > 0.0
    # A cycle runs from the previous pull's return to this one's: laid
    # end to end from the first mark, each cycle ends ON a read of the
    # marks' clock (the mark that closed it and started the next), so
    # no time between two cycles is lost or counted twice. (``ts`` is
    # another clock, read when the record is written: it places a
    # cycle for a viewer and is held to nothing here.)
    end = reads[0]
    for rec in cycles:
        assert rec["dur"] > 0.0
        end += rec["dur"]
        assert min(abs(end - at) for at in reads) < ROUNDING_S
    # One record a pull: the wait is the cycle's pull_s, no span of
    # its own.
    assert not [
        r for r in trace.snapshot_spans() if r["name"] == "step.pull"
    ]
    assert all(c["attrs"]["pull_s"] > 0.0 for c in cycles)
    assert len({c["trace"] for c in cycles}) == 1


def test_a_sleep_in_the_callers_loop_is_outside_and_one_in_the_loader_data_next():
    trainer, loader, state = _job(dataset_type=_SlowRows)

    def after_step(done):
        if done == 5:
            time.sleep(0.05)
        if done == 11:  # right after a pull: the device is empty
            time.sleep(0.03)
        # The loader gathers step 16's batch when it is asked again.
        loader.dataset.delay = 0.08 if done == 15 else 0.0

    _run(trainer, loader, state, 21, after_step)
    _, second, third = _cycles()
    assert second["attrs"]["outside_max_s"] >= 0.05
    assert second["attrs"]["data_next_max_s"] < 0.05
    assert third["attrs"]["data_next_max_s"] >= 0.08
    assert max(third["attrs"]["data_next_steps_s"]) >= 0.08
    assert third["attrs"]["outside_max_s"] < 0.05
    # The caller's part of the stretch on an empty device is told apart.
    assert 0.03 <= third["attrs"]["exposed_outside_s"] < 0.05
    assert second["attrs"]["exposed_outside_s"] < 0.03
    for rec in (second, third):
        assert _identity_gap(rec) < ROUNDING_S
        hosts = sum(
            rec["attrs"][f"{p}_s"]
            for p in ("shard", "dispatch", "after_pull")
        )
        assert hosts < 0.05  # neither sleep leaked into run_step's phases


def test_leaving_the_loader_hands_the_clock_to_the_caller():
    """An epoch's end and a ``break`` both leave ``data_next``; a late
    ``finally`` (a generator collected long after) moves nothing."""
    trainer, loader, state = _job(samples=8 * 3)
    _run(trainer, loader, state, 99)  # the data ends after 3 steps
    assert trace.step_cycle.phase == trace.OUTSIDE
    trace.step_cycle.mark(trace.DISPATCH)
    trace.step_cycle.leave(trace.DATA_NEXT)
    assert trace.step_cycle.phase == trace.DISPATCH


def test_tracing_off_records_nothing_and_reads_no_clock(monkeypatch):
    monkeypatch.setenv("ADAPTDL_TRACE", "off")
    trace._reset_state()

    def never():
        raise AssertionError("a mark read the clock with tracing off")

    monkeypatch.setattr(trace, "_clock", never)
    monkeypatch.setattr(trace, "_new_annotation", lambda name: never())
    trainer, loader, state = _job()
    _run(trainer, loader, state, 12)
    assert not trace.snapshot_spans()
    assert trace.step_cycle.steps_total == 0
    assert trace.step_cycle.phase is None


def test_the_marks_add_nothing_to_the_traced_step(monkeypatch):
    """The marks stand around the jitted call: the step's jaxpr is the
    same with tracing on and off."""
    import jax

    from tests.test_compile_cache import _linear_trainer

    def jaxpr():
        trace._reset_state()
        trainer = _linear_trainer()[0]
        state = trainer.init_state()
        batch = trainer.shard_batch(
            {
                "x": np.zeros((8, 4), np.float32),
                "y": np.zeros((8,), np.float32),
            }
        )
        return str(jax.make_jaxpr(trainer.train_step(8, 0))(state, batch))

    on = jaxpr()
    monkeypatch.setenv("ADAPTDL_TRACE", "off")
    off = jaxpr()
    assert on == off
    assert "adaptdl.step" not in on


def test_the_marks_are_profiler_annotations(tmp_path):
    """Under a profiler session each phase is a ``TraceAnnotation``
    ``adaptdl.step.<phase>`` on the host plane, one after the other;
    the caller's loop has none."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for phase in (trace.DATA_NEXT, trace.OUTSIDE, trace.SHARD,
                      trace.DISPATCH, trace.PULL, trace.AFTER_PULL,
                      trace.OUTSIDE):
            trace.step_cycle.mark(phase)
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    events = sorted(
        (ev.start_ns, ev.duration_ns, ev.name)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("adaptdl.")
    )
    assert [name for _, _, name in events] == [
        "adaptdl.step.data_next", "adaptdl.step.shard",
        "adaptdl.step.dispatch", "adaptdl.step.pull",
        "adaptdl.step.after_pull",
    ]
    assert all(dur >= 2e6 for _, dur, _ in events)
    for (start, dur, _), (following, _, _) in zip(events, events[1:]):
        assert start + dur <= following + 1e3
    (cycle,) = _cycles()
    assert cycle["attrs"]["steps"] == 1 and _identity_gap(cycle) < 1e-3


def test_the_cycle_is_journalled_observed_and_rendered(tmp_path, monkeypatch):
    from tests.promcheck import validate_exposition

    monkeypatch.setenv("ADAPTDL_TRACE_DIR", str(tmp_path))
    trace._reset_state()
    trainer, loader, state = _job()

    def after_step(done):
        if done == 15:
            time.sleep(0.03)

    _run(trainer, loader, state, 21, after_step)
    (path,) = glob.glob(str(tmp_path / "trace-*.jsonl"))
    journalled = [
        r for r in trace.read_journal(path) if r["name"] == "step.cycle"
    ]
    assert [r["attrs"]["first_step"] for r in journalled] == [1, 2, 12]
    assert all(_identity_gap(r) < 1e-3 for r in journalled)
    exposition = trace.prometheus_lines()
    validate_exposition(exposition)
    assert 'adaptdl_trace_phase_seconds_count{phase="step.cycle"} 3' in (
        exposition
    )
    table = trace.render_cycles(trace.read_journal(path))
    lines = table.splitlines()
    assert lines[0].startswith("3 cycle(s), 3 shown")
    assert "nivcsw" in lines[1] and "after_pul" in lines[1]
    assert len(lines) == 5
    assert sum(line.startswith("*") for line in lines) == 1
    assert lines[-1].split()[0:2] == ["12", "10"]
    assert "outside" in lines[-1].split("  ")[-1]  # the planted sleep
    assert trace.render_cycles([]) == "(no cycles)"
