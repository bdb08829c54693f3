"""What a kept trace of a step holds about the data-axis reduces,
the ones that run under another op included.

    BENCHMARK_KEEP_TRACE=DIR python benchmark/run.py \
        --workload gpt2-124m-dp4 --trace 1 ...
    python tools/reduce_overlap_trace.py DIR/gpt2-124m-dp4.xplane.pb

The benchmark's pair ``allreduce_ms`` / ``allreduce_exposed_ms`` finds
an all-reduce by its opcode (``benchmark/xplane.py``: ``ALL_REDUCE``).
The TPU compiler writes one that runs beside another op as three
``fusion``s — ``%async-collective-start.N``, the op it runs under
(``calls=%async_collective_fusion``) and ``%async-collective-done.N``
— which that pattern does not match, so since PR 43 the pair reads
the synchronous remainder of a step of several replicas. This reads
both kinds with the benchmark's own interval arithmetic: a reduce in
flight from its start's begin to its done's end, the op between them
other work like any other. It is the reader a ``benchmark`` PR can
attach (PERF.md section 7); until then it is how the numbers in
PERF.md section 5 are read from a trace. One JSON line; times in
milliseconds a step, averaged over the chips.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import xplane  # noqa: E402

HALF = re.compile(r"^%?async-collective-(start|done)((?:\.\d+)?) = ")
UNDER = "calls=%async_collective_fusion"


def pairs(ops):
    """-> [(start event, done event)] of one chip's ops, a start with
    the next done of its number."""
    out, open_starts = [], {}
    for ev in sorted(ops, key=lambda e: e.start):
        half = HALF.match(ev.name)
        if not half:
            continue
        if half.group(1) == "start":
            open_starts[half.group(2)] = ev
        elif half.group(2) in open_starts:
            out.append((open_starts.pop(half.group(2)), ev))
    return out


def _holds_one_of(intervals):
    """-> whether ``(lo, hi)`` spans one of ``intervals`` whole."""
    intervals = sorted(intervals)
    begins = [a for a, _ in intervals]
    first_end = [b for _, b in intervals]  # the least end from i on
    for i in range(len(first_end) - 2, -1, -1):
        first_end[i] = min(first_end[i], first_end[i + 1])

    def holds(lo, hi):
        i = bisect.bisect_left(begins, lo)
        return i < len(begins) and first_end[i] <= hi

    return holds


def read(trace) -> dict | None:
    program = trace.step_program()
    if program is None:
        return None
    steps, chips = program[1], len(trace.devices)
    acc = {
        "async_pairs": 0.0, "synchronous_all_reduces": 0.0,
        "in_flight_ms": 0.0, "exposed_ms": 0.0,
        "synchronous_ms": 0.0, "start_done_ms": 0.0, "under_ms": 0.0,
    }
    under_kinds: dict[str, float] = {}
    for d in trace.devices:
        paired = pairs(d.ops)
        sync = [e for e in d.ops if xplane.ALL_REDUCE.search(e.name)]
        flights = [(s.start, dn.end) for s, dn in paired] + [
            (e.start, e.end) for e in sync
        ]
        holds = _holds_one_of(flights)
        other = [
            (e.start, e.end) for e in d.ops
            if not HALF.match(e.name)
            and not xplane.ALL_REDUCE.search(e.name)
            # (a ``while`` that holds a reduce is no work beside it.)
            and not holds(e.start, e.end)
        ]
        mine = xplane.union(flights)
        acc["async_pairs"] += len(paired)
        acc["synchronous_all_reduces"] += len(sync)
        acc["in_flight_ms"] += xplane.total(mine) / 1e6
        acc["exposed_ms"] += xplane.total(xplane.subtract(mine, other)) / 1e6
        acc["synchronous_ms"] += sum(e.duration for e in sync) / 1e6
        acc["start_done_ms"] += sum(
            s.duration + dn.duration for s, dn in paired
        ) / 1e6
        for ev in d.ops:
            if UNDER in ev.name:
                acc["under_ms"] += ev.duration / 1e6
                kind = xplane.op_kind(ev.name)
                under_kinds[kind] = under_kinds.get(kind, 0.0) + ev.duration
    out = {k: v / (steps * chips) for k, v in acc.items()}
    out["under"] = {
        k: v / (1e6 * steps * chips)
        for k, v in sorted(under_kinds.items(), key=lambda kv: -kv[1])
    }
    out["step_device_ms"] = 1e3 * program[2]
    out["steps"] = steps
    accepted, exposed, _ = trace.matching_s(xplane.ALL_REDUCE)
    out["accepted_allreduce_ms"] = 1e3 * accepted / steps
    out["accepted_allreduce_exposed_ms"] = 1e3 * exposed / steps
    return out


def main() -> None:
    path = sys.argv[1]
    if path.endswith(".gz"):
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as plain:
            with gzip.open(path, "rb") as packed:
                shutil.copyfileobj(packed, plain)
            plain.flush()
            trace = xplane.load(plain.name)
    else:
        trace = xplane.load(path)
    print(json.dumps(read(trace)))


if __name__ == "__main__":
    main()
