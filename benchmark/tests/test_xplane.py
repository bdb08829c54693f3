"""The trace reduction: interval arithmetic on hand-made intervals, and
the whole reduction on a small trace recorded on a TPU v5e."""

import os

import pytest

from benchmark import manifest, xplane
from benchmark.xplane import DevicePlane, Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "recorded_v5e.xplane.pb")


def test_union_and_total():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)])
    assert merged == [(0, 4), (5, 7)]
    assert xplane.total(merged) == 6


def test_subtract_clip_gaps():
    a, b = [(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 40)]
    assert xplane.subtract(a, b) == [(0, 2), (3, 8), (22, 29)]
    assert xplane.subtract(a, []) == a
    assert xplane.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    assert xplane.gaps([(2, 4), (6, 7)], 0, 10) == [
        (0, 2), (4, 6), (7, 10)
    ]


def test_self_times_of_nested_ops():
    events = [
        Event("while", 0, 100),
        Event("fusion.1", 10, 30),
        Event("fusion.2", 40, 60),
        Event("fusion.1", 70, 80),
        Event("copy", 110, 120),
    ]
    assert xplane.self_times(events) == {
        "while": 50, "fusion.1": 30, "fusion.2": 20, "copy": 10,
    }


def _trace(ops, host, modules=()):
    return Trace(
        devices=[DevicePlane(0, ops=list(ops), modules=list(modules))],
        host=list(host),
        lines_seen={},
    )


def test_busy_idle_and_gap_attribution():
    ops = [Event("a", 10, 30), Event("b", 25, 40), Event("c", 70, 90)]
    host = [
        Event("bench.slice", 0, 100),
        Event("bench.data_next", 40, 50),
        Event("bench.run_step", 50, 75),
    ]
    trace = _trace(ops, host)
    assert trace.window() == (0, 100)
    assert trace.busy_s() == pytest.approx(50e-9)
    assert trace.window_s() == pytest.approx(100e-9)
    # idle: [0,10] other, [40,50] data_next, [50,70] run_step,
    # [90,100] other
    gaps = dict(trace.idle_gaps())
    assert gaps["bench.run_step"] == pytest.approx(20e-9)
    assert gaps["bench.data_next"] == pytest.approx(10e-9)
    assert gaps["host:other"] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx(50e-9)
    # The gap readers: the same attribution per execution of the step
    # program, in ms; nothing to read without a step program.
    for name, ms in (("run_step_gap_ms", 10e-6), ("data_next_gap_ms", 5e-6)):
        reader = manifest.load_module(
            manifest.bench_path(manifest.ROOT, "layer_metrics", name + ".py")
        )
        assert reader.read(trace, {}, {}) is None
        stepped = _trace(
            ops, host, [Event("jit_step", 10, 40), Event("jit_step", 70, 90)]
        )
        assert reader.read(stepped, {}, {}) == pytest.approx(ms)
        assert reader.read(None, {}, {}) is None


def test_window_falls_back_to_device_events():
    trace = _trace([Event("a", 10, 30), Event("c", 70, 90)], [])
    assert trace.window() == (10, 90)
    assert trace.busy_s() == pytest.approx(40e-9)


def test_exposed_collective_arithmetic():
    ops = [
        Event("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 100),
        Event("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 20),
        Event(
            "%psum.7 = f32[8]{0:T(8)S(1)} all-reduce(f32[8]{0} %fusion.3),"
            " channel_id=1, replica_groups={{0,1,2,3}}",
            10, 50,
        ),
        Event("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p)", 30, 40),
        Event(
            "%all-reduce-start.2 = (f32[8]{0}, f32[8]{0}) "
            "all-reduce-start(f32[8]{0} %fusion.4)",
            120, 125,
        ),
        # Names an all-reduce among its operands; is none.
        Event(
            "%fusion.5 = f32[8]{0} fusion(f32[8]{0} %all-reduce.9, "
            "f32[8]{0} %all-reduce-done.1)",
            125, 150,
        ),
        Event(
            "%all-reduce-done.2 = f32[8]{0} all-reduce-done("
            "(f32[8]{0}, f32[8]{0}) %all-reduce-start.2)",
            150, 160,
        ),
    ]
    trace = _trace(ops, [], modules=[Event("jit_step", 0, 160)])
    seconds, exposed, events = trace.matching_s(xplane.ALL_REDUCE)
    assert events == 3
    assert seconds == pytest.approx(55e-9)  # 40 + 5 + 10
    # hidden: [10,20] under fusion.3, [30,40] under fusion.4; the
    # while that contains the collective hides nothing.
    assert exposed == pytest.approx(35e-9)
    assert trace.step_program() == ("jit_step", 1, pytest.approx(160e-9))
    for name in ("allreduce_ms", "allreduce_exposed_ms"):
        reader = manifest.load_module(
            manifest.bench_path(manifest.ROOT, "layer_metrics", name + ".py")
        )
        assert reader.read(trace, {}, {}) == pytest.approx(
            {"allreduce_ms": 55e-6, "allreduce_exposed_ms": 35e-6}[name]
        )


def test_all_reduce_pattern_on_the_names_of_a_real_trace():
    """Every all-reduce event name of the four-chip cell's own trace:
    three of the four are combined ops whose tuple type carries
    ``/*index=5*/`` comments, which a pattern that stops at the first
    ``=`` misses (it then read 2.7 ms of the 8.8)."""
    import json

    with open(os.path.join(HERE, "data", "dp4_allreduce_names.json")) as f:
        names = json.load(f)["all_reduce_ops"]
    assert len(names) == 4 and any("/*index=" in n for n in names)
    for name in names:
        assert xplane.ALL_REDUCE.search(name), name[:80]
        consumer = (
            "%fusion.1 = f32[8]{0} fusion(f32[8]{0} "
            + name.split(" = ")[0] + ")"
        )
        assert not xplane.ALL_REDUCE.search(consumer)


def test_step_program_is_the_one_with_most_device_time():
    modules = [
        Event("jit_step", 0, 100), Event("jit_step", 120, 200),
        Event("jit_convert", 100, 101), Event("jit_convert", 201, 202),
        Event("jit_convert", 203, 204),
    ]
    trace = _trace([Event("a", 0, 10)], [], modules)
    name, runs, mean_s = trace.step_program()
    assert (name, runs) == ("jit_step", 2)
    assert mean_s == pytest.approx(90e-9)


def test_recorded_v5e_trace():
    """Five steps of a tiny data-parallel program (matmul, gradient
    ``pmean``, update) recorded on four TPU v5 lite chips with
    ``record_trace.py`` (PR 22), the benchmark's annotations around a
    sleeping "loader" and the dispatch: the reduction finds the four
    device planes, the host annotations, the step program and the
    all-reduces, and reads the numbers it read when the trace was
    recorded."""
    import json

    with open(os.path.join(HERE, "data", "recorded_v5e.expected.json")) as f:
        expected = json.load(f)
    trace = xplane.load(RECORDED)
    assert len(trace.devices) == expected["chips"] == 4
    assert {e.name for e in trace.host} == {
        "bench.slice", "bench.data_next", "bench.run_step"
    }
    assert trace.window_s() == pytest.approx(expected["window_s"])
    assert trace.busy_s() == pytest.approx(expected["busy_s"])
    assert 0 < trace.busy_s() < trace.window_s()
    name, runs, mean_s = trace.step_program()
    assert name == expected["step_program"]
    assert runs == expected["step_runs"] == 5
    assert mean_s == pytest.approx(expected["step_mean_s"])
    # Idle gaps are attributed on the first chip's timeline, most of
    # them to the 2 ms sleeps inside bench.data_next.
    gaps = dict(trace.idle_gaps())
    lo, hi = trace.window()
    first_chip_busy = xplane.total(trace.busy(trace.devices[0]))
    assert sum(gaps.values()) == pytest.approx(
        (hi - lo - first_chip_busy) / 1e9
    )
    assert gaps["bench.data_next"] == pytest.approx(
        dict(expected["idle_gaps"])["bench.data_next"]
    )
    assert gaps["bench.data_next"] > 5 * 0.002 * 0.9
    assert trace.top_ops(3)[0][0] == expected["top_op"]
    # The all-reduce is synchronous here: nothing runs beside it, so
    # all of it is exposed.
    seconds, exposed, events = trace.matching_s(xplane.ALL_REDUCE)
    assert events == expected["allreduce_events"] == 10
    assert seconds == pytest.approx(expected["allreduce_s"])
    assert exposed == pytest.approx(expected["allreduce_exposed_s"])
    assert exposed == pytest.approx(seconds)
