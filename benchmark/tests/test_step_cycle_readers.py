"""The five readers of the program's own step-path names (PR 52;
``benchmark/step_cycles.py``, the reader files staged in
``tests/data/step_cycle_readers/``): on hand-made ``step.cycle``
records and hand-made host / device intervals, the annotations read
back from a real profile, the edit that attaches them
(``attach_step_cycle_readers.py``) on a scratch copy of the manifest,
and one attached cell through the harness on the CPU."""

import argparse
import glob
import json
import math
import os
import shutil

import pytest

import attach_step_cycle_readers as attach_tool
from benchmark import manifest, step_cycles
from benchmark.xplane import DevicePlane, Event, Trace

ROOT = manifest.ROOT
CYCLE_READERS = ("host_step_ms", "host_exposed_ms", "cycle_worst_over_median")
GAP_READERS = ("shard_dispatch_gap_ms", "after_pull_gap_ms")


def _reader(name):
    return manifest.load_module(
        os.path.join(attach_tool.STAGED, f"{name}.py")
    )


def _cycle(first_step, dur, outside=0.010, exposed=0.004, steps=10,
           data_next=0.002, shard=0.003, dispatch=0.005, after_pull=0.001,
           pid=None):
    pull = dur - outside - data_next - shard - dispatch - after_pull
    return {
        "name": "step.cycle",
        "dur": dur,
        "pid": os.getpid() if pid is None else pid,
        "attrs": {
            "steps": steps, "first_step": first_step,
            # The caller's loop before the first dispatch: a tenth of
            # the cycle's (the profiler's stop where it is large).
            "exposed_s": exposed + outside / 10,
            "exposed_outside_s": outside / 10,
            "data_next_s": data_next,
            "shard_s": shard, "dispatch_s": dispatch, "pull_s": pull,
            "after_pull_s": after_pull, "calibrate_s": 0.0,
            "outside_s": outside,
            # One dispatch with room in the queue, the rest waiting.
            "dispatch_steps_s": [0.0004] + [
                (dispatch - 0.0004) / max(steps - 1, 1)
            ] * (steps - 1),
        },
    }


# Warm-up: the early pull, then a cycle that holds quiesce()'s wait; the
# window is the last 40 of 58 steps: the cycles at 22, 32 and 42 lie in
# it whole, the one at 12 began before it, steps 52-58 closed no cycle.
CYCLES = [
    _cycle(1, 5.0, steps=1),
    _cycle(2, 0.9, outside=0.6),
    _cycle(12, 0.9, outside=0.6),
    _cycle(22, 0.300),
    _cycle(32, 0.420, outside=0.020, exposed=0.110, after_pull=0.101),
    _cycle(42, 0.302, exposed=0.006),
]
RECORD = {"steps": 40}


@pytest.mark.parametrize(
    "name,want",
    [
        # (2 + 3 + 1) ms in ten steps and the cheapest dispatch, 0.4;
        # the nine that waited for the device (4.6 ms) are not the
        # host's.
        ("host_step_ms", 1.0),
        ("host_exposed_ms", 0.6),
        # In the program: 0.290, 0.400, 0.292.
        ("cycle_worst_over_median", 0.400 / 0.292),
    ],
)
def test_cycle_reader_on_hand_made_records(name, want):
    read = _reader(name).read
    assert read(None, {}, RECORD, CYCLES, 58) == pytest.approx(want)


@pytest.mark.parametrize("name", CYCLE_READERS)
def test_cycle_reader_without_records_reads_nothing(name):
    read = _reader(name).read
    assert read(None, {}, RECORD, [], 58) is None
    # Every cycle began before the window: nothing to read, not 0.
    assert read(None, {}, {"steps": 5}, CYCLES, 58) is None


def test_window_cycles_are_those_whose_steps_all_lie_in_the_window():
    window = step_cycles.window_cycles(RECORD, CYCLES, 58)
    assert [c["first_step"] for c in window] == [22, 32, 42]
    assert window[0]["dur"] == 0.300
    assert [
        c["first_step"]
        for c in step_cycles.window_cycles({"steps": 58}, CYCLES, 58)
    ] == [1, 2, 12, 22, 32, 42]


def test_worst_over_median_leaves_out_cycles_of_another_length():
    """A loader re-entry or the first pull makes a short cycle: its
    time is no stall and no median. One whole cycle reads 1.0."""
    cycles = CYCLES[3:] + [_cycle(52, 0.05, steps=3)]
    read = _reader("cycle_worst_over_median").read
    assert read(None, {}, {"steps": 43}, cycles, 64) == pytest.approx(
        0.400 / 0.292
    )
    assert read(None, {}, {"steps": 17}, CYCLES, 58) == 1.0


def test_the_program_of_this_process_is_read_when_nothing_is_handed_in():
    """Through ``adaptdl_tpu.trace``: a process that ran no step reads
    nothing; a predecessor's handed-over cycles (another pid in the
    ring) are not this process's."""
    from adaptdl_tpu import trace

    trace._reset_state()
    try:
        for name in CYCLE_READERS:
            assert _reader(name).read(None, {}, RECORD) is None
        trace.step_cycle.steps_total = 58
        for rec in CYCLES:
            trace.record_span("step.cycle", rec["dur"], **rec["attrs"])
        theirs = _cycle(30, 9.0, pid=1)
        trace.record_span("step.cycle", theirs["dur"], **theirs["attrs"])
        with trace._buffer_lock:
            trace._buffer_locked()[-1]["pid"] = 1
        cycles, total = step_cycles.program_cycles()
        assert total == 58 and len(cycles) == len(CYCLES)
        assert _reader("host_step_ms").read(
            None, {}, RECORD
        ) == pytest.approx(1.0)
        # A record without the per-step list counts the mean dispatch.
        bare = [dict(c, attrs={
            k: v for k, v in c["attrs"].items() if k != "dispatch_steps_s"
        }) for c in CYCLES]
        assert _reader("host_step_ms").read(
            None, {}, RECORD, bare, 58
        ) == pytest.approx(1.1)
    finally:
        trace._reset_state()


# ---- the device-trace pair ---------------------------------------------

MS = 1e6  # ns


def _slice():
    """Two steps of a profiled slice 0-100 ms on one chip. Device busy
    10-40 and 42-44 (one program, a 2 ms gap between its ops), 60-90.
    Host: step 1 run_step 0-12 = shard 0-4, dispatch 4-10 (the device
    starts at 10), then nothing named to 12; step 2 run_step 14-58 =
    shard 14-16, dispatch 16-18, pull 18-50 (the device's last op ends
    at 44: its tail is 44-50), after_pull 50-58; the third dispatch
    58.5-60."""
    ops = [
        Event("fusion.1", 10 * MS, 40 * MS),
        Event("fusion.2", 42 * MS, 44 * MS),
        Event("fusion.1", 60 * MS, 90 * MS),
    ]
    modules = [
        Event("jit_step", 10 * MS, 44 * MS),
        Event("jit_step", 60 * MS, 90 * MS),
    ]
    host = [
        Event("bench.slice", 0, 100 * MS),
        Event("bench.run_step", 0, 12 * MS),
        Event("bench.data_next", 12 * MS, 14 * MS),
        Event("bench.run_step", 14 * MS, 58 * MS),
        Event("bench.run_step", 58.5 * MS, 62 * MS),
    ]
    annotations = [
        Event("adaptdl.step.shard", 0, 4 * MS),
        Event("adaptdl.step.dispatch", 4 * MS, 10 * MS),
        Event("adaptdl.step.data_next", 12 * MS, 13.5 * MS),
        Event("adaptdl.step.shard", 14 * MS, 16 * MS),
        Event("adaptdl.step.dispatch", 16 * MS, 18 * MS),
        Event("adaptdl.step.pull", 18 * MS, 50 * MS),
        Event("adaptdl.step.after_pull", 50 * MS, 58 * MS),
        Event("adaptdl.step.dispatch", 58.5 * MS, 60 * MS),
    ]
    trace = Trace(
        devices=[DevicePlane(0, ops=ops, modules=modules)],
        host=host,
        lines_seen={},
    )
    return trace, annotations


@pytest.mark.parametrize(
    "name,want_ms",
    [
        # Idle under shard 0-4 and dispatch 4-10 and 58.5-60: 11.5 ms.
        ("shard_dispatch_gap_ms", 11.5 / 2),
        # The pull's tail 44-50 (not the gap 40-42 between the step's
        # ops) and after_pull 50-58: 14 ms.
        ("after_pull_gap_ms", 14.0 / 2),
    ],
)
def test_gap_reader_on_hand_made_intervals(name, want_ms):
    trace, annotations = _slice()
    read = _reader(name).read
    assert read(trace, {}, {}, annotations) == pytest.approx(want_ms)


def test_a_gap_is_counted_once_under_the_innermost_name():
    """The program's annotations lie inside the benchmark's: what they
    cover leaves ``bench.run_step``, and the parts add to what the
    accepted reader reads under the call from outside."""
    trace, annotations = _slice()
    outside = manifest.load_module(
        manifest.reader_path(ROOT, "run_step_gap_ms")
    ).read(trace, {}, {})
    # 0-10, 40-42 (under the call, in the pull), 44-58, 58.5-60.
    assert outside == pytest.approx((10 + 2 + 14 + 1.5) / 2)
    inside = sum(
        _reader(name).read(trace, {}, {}, annotations)
        for name in GAP_READERS
    )
    assert inside == pytest.approx(outside - 2 / 2)  # less the op gap
    by = step_cycles.idle_by_program(trace, annotations)
    assert by["adaptdl.step.after_pull"] == pytest.approx(8 * MS)
    assert by["adaptdl.step.pull"] == pytest.approx(6 * MS)
    # bench.run_step keeps only what no name of the program covers:
    # the gap between the step's ops while the host waited, 40-42.
    assert by["bench.run_step"] == pytest.approx(2 * MS)
    # The device was busy while the loader ran: no idleness is its.
    assert "adaptdl.step.data_next" not in by and "bench.data_next" not in by
    assert sum(by.values()) == pytest.approx(
        (10 + 2 + 16 + 10) * MS  # every idle ns of the slice, once
    )


@pytest.mark.parametrize("name", GAP_READERS)
def test_gap_reader_without_the_programs_names_reads_nothing(name):
    """A parent commit, ``ADAPTDL_TRACE=off``, an untraced run, the CPU
    (no device plane): left out, never 0."""
    trace, annotations = _slice()
    read = _reader(name).read
    assert read(trace, {}, {}, []) is None
    assert read(None, {}, {}, annotations) is None
    no_device = Trace(devices=[], host=trace.host, lines_seen={})
    assert read(no_device, {}, {}, annotations) is None
    # Nothing handed in and no spec on the command line: no file.
    assert read(trace, {}, {}) is None


def test_pull_tail_is_the_gap_that_reaches_the_pulls_return():
    idle = [(0.0, 10.0), (40.0, 42.0), (44.0, 60.0)]
    pulls = [Event("adaptdl.step.pull", 18.0, 50.0),
             Event("adaptdl.step.pull", 70.0, 80.0)]  # device still busy
    assert step_cycles.pull_tails(idle, pulls) == [
        Event("adaptdl.step.pull", 44.0, 50.0)
    ]


def test_annotations_are_read_back_from_a_real_profile(tmp_path, monkeypatch):
    """The program's marks under a profiler session: the file the
    harness's tracer would leave holds every phase, on one clock with
    ``bench.*``, and the helper finds it from the worker's spec."""
    import sys

    import jax

    from adaptdl_tpu import trace

    trace._reset_state()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(
        str(tmp_path / "trace"), profiler_options=options
    )
    try:
        with jax.profiler.TraceAnnotation("bench.run_step"):
            for phase in (trace.SHARD, trace.DISPATCH, trace.PULL,
                          trace.AFTER_PULL, trace.OUTSIDE):
                trace.step_cycle.mark(phase)
    finally:
        jax.profiler.stop_trace()
        trace._reset_state()
    (path,) = glob.glob(
        str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    events = step_cycles.annotations_in(path)
    assert [e.name for e in sorted(events, key=lambda e: e.start)] == [
        "adaptdl.step.shard", "adaptdl.step.dispatch",
        "adaptdl.step.pull", "adaptdl.step.after_pull",
    ]
    for first, second in zip(events, events[1:]):
        assert first.end <= second.start + 1e3  # one after the other
    spec = tmp_path / "spec-steady.json"
    spec.write_text(json.dumps({"work_dir": str(tmp_path)}))
    monkeypatch.setattr(sys, "argv", ["worker.py", str(spec), "7"])
    assert step_cycles.trace_file() == path
    assert step_cycles.program_annotations() == list(events)


# ---- the edit that attaches them ---------------------------------------


@pytest.fixture
def attached(tmp_path):
    """A scratch copy of the manifest with the readers attached."""
    root = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmark"),
        root / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    cells = attach_tool.attach(str(root))
    return str(root), cells


def test_attaching_only_appends(attached):
    """What a ``benchmark`` PR would commit: five files, five entries
    at the end of ``per_layer``, five names at the end of every cell's
    ``metrics``; nothing else of any accepted file differs, and the
    manifest's own rules hold on the result."""
    root, cells = attached
    before = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    after = manifest.load_json(os.path.join(root, "BENCHMARK.json"))
    names = list(CYCLE_READERS + GAP_READERS)
    assert after["per_layer"][: -len(names)] == before["per_layer"]
    assert [m["name"] for m in after["per_layer"][-len(names):]] == names
    assert {k: v for k, v in after.items() if k != "per_layer"} == {
        k: v for k, v in before.items() if k != "per_layer"
    }
    assert len(json.dumps(after)) < 64 * 1024
    assert cells == [w["name"] for w in before["workloads"]]
    assert len(cells) >= 8  # every cell there is, however many
    for cell in cells:
        old = manifest.load_json(
            manifest.bench_path(ROOT, "workloads", f"{cell}.json")
        )
        new = manifest.load_json(
            manifest.bench_path(root, "workloads", f"{cell}.json")
        )
        assert new["metrics"] == old["metrics"] + names
        assert dict(new, metrics=None) == dict(old, metrics=None)
        loaded = manifest.load_cell(cell, root)
        assert names == [m["name"] for m in loaded.per_layer][-len(names):]
    # test_manifest.py's rules for readers, on the attached copy.
    readers = {
        os.path.splitext(f)[0]
        for f in os.listdir(manifest.bench_path(root, "layer_metrics"))
        if f.endswith(".py")
    }
    assert readers == {m["name"] for m in after["per_layer"]}
    for metric in after["per_layer"][-len(names):]:
        assert set(metric) == {
            "name", "unit", "better", "source", "layer", "moves"
        }
        reader = manifest.load_module(
            manifest.reader_path(root, metric["name"])
        )
        assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
            metric["unit"], metric["layer"], metric["source"],
            metric["moves"],
        ), metric["name"]
    assert any(
        m["layer"] == "step, host side" for m in before["per_layer"]
    )  # the layer's name is the accepted one, letter for letter
    with pytest.raises(SystemExit, match="attached already"):
        attach_tool.attach(root)


def test_an_attached_cell_rehearsed_on_the_cpu_prints_the_new_metrics(
    attached, tmp_path, monkeypatch
):
    """``gpt2-124m-steady`` through the harness at a tiny size, traced,
    from the attached copy: the three program-span readers print a
    number beside the accepted metrics; the device-trace pair needs a
    device plane, which XLA:CPU's trace has not (like the accepted
    ``run_step_gap_ms``), and is left out, not 0."""
    import rehearse

    from benchmark import run

    root, _ = attached
    work = tmp_path / "tmp"
    work.mkdir()
    monkeypatch.setenv("TMPDIR", str(work))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
    )
    monkeypatch.setenv("PYTHONPATH", ROOT)  # adaptdl_tpu, for the worker
    monkeypatch.delenv("ADAPTDL_TRACE", raising=False)
    cell = manifest.load_cell("gpt2-124m-steady", root)
    rehearse.shrink(cell)
    args = argparse.Namespace(
        workload="gpt2-124m-steady", seed=2**31 + 52, seconds=2.0, trace=1
    )
    line = run.run_cell(cell, args, root=root)
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    values = {
        name: line["metrics"][name]["value"] for name in CYCLE_READERS
    }
    assert all(math.isfinite(v) and v > 0 for v in values.values()), values
    assert values["cycle_worst_over_median"] >= 1.0
    assert values["host_exposed_ms"] <= 10 * values["host_step_ms"] + 50
    assert not set(GAP_READERS + ("run_step_gap_ms",)) & set(line["metrics"])
    assert {"restart_span_s", "trace_lower_s", "compiles_in_window"} <= set(
        line["metrics"]
    )
