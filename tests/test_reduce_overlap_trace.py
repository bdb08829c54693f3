"""``tools/reduce_overlap_trace.py`` on hand-made intervals: what it
counts as a reduce in flight and as its exposed part, beside what the
benchmark's accepted pair reads from the same events."""

import pytest

from benchmark import xplane
from tools import reduce_overlap_trace as reader

START = "%async-collective-start{n} = (f32[8]{{0}}, s32[2]{{0}}) fusion(%g)"
UNDER = (
    "%fusion.5 = f32[8,8]{1,0} fusion(%a, %b), kind=kOutput, "
    "calls=%async_collective_fusion.5"
)
DONE = "%async-collective-done{n} = f32[8]{{0}} fusion(%s)"
SYNC = "%all-reduce.3 = f32[4]{0} all-reduce(%x), replica_groups={{0,1}}"
PLAIN = "%fusion.9 = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused.9"
WHILE = "%while.1 = (f32[2]{0}) while(%t), body=%b, condition=%c"


def _trace(ops):
    events = [xplane.Event(name, lo, hi) for name, lo, hi in ops]
    plane = xplane.DevicePlane(
        ordinal=0, ops=events,
        modules=[xplane.Event("jit_step", 0.0, 1e6)] * 2,
    )
    return xplane.Trace(devices=[plane], host=[], lines_seen={})


@pytest.mark.parametrize("number", ["", ".7"])
def test_a_pair_is_in_flight_from_start_to_done(number):
    """One hidden reduce (10 ns to start, 90 under a product, 4 to
    finish), one synchronous (30), a loop that holds them all: over
    the trace's two steps 134 ns in flight, 44 with nothing beside
    them; the accepted pair sees the synchronous 30 alone."""
    read = reader.read(_trace([
        (WHILE, 0, 300),
        (START.format(n=number), 0, 10),
        (UNDER, 10, 100),
        (DONE.format(n=number), 100, 104),
        (PLAIN, 104, 200),
        (SYNC, 200, 230),
    ]))
    assert read["steps"] == 2
    assert read["async_pairs"] == 0.5
    assert read["synchronous_all_reduces"] == 0.5
    assert read["in_flight_ms"] == pytest.approx(134e-6 / 2)
    assert read["exposed_ms"] == pytest.approx(44e-6 / 2)
    assert read["start_done_ms"] == pytest.approx(14e-6 / 2)
    assert read["under"] == {"fusion f32[8,8]": pytest.approx(90e-6 / 2)}
    assert read["accepted_allreduce_ms"] == pytest.approx(30e-6 / 2)
    assert read["accepted_allreduce_exposed_ms"] == pytest.approx(30e-6 / 2)


def test_a_step_with_no_pair_reads_what_the_accepted_pair_reads():
    """The all-in-scan step: every reduce synchronous and in the
    open; a done with no start is no pair."""
    read = reader.read(_trace([
        (PLAIN, 0, 50),
        (DONE.format(n=".2"), 50, 54),
        (SYNC, 60, 90),
    ]))
    assert read["async_pairs"] == 0
    for mine, accepted in (
        ("in_flight_ms", "accepted_allreduce_ms"),
        ("exposed_ms", "accepted_allreduce_exposed_ms"),
    ):
        assert read[mine] == pytest.approx(read[accepted])
        assert read[mine] == pytest.approx(30e-6 / 2)
    assert reader.read(xplane.Trace([], [], {})) is None
