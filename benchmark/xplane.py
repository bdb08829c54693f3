"""Reduction of a profiler trace (``.xplane.pb``) to numbers.

Part of the yardstick: every PR computes the same number the same way.
``load`` reads the file with nothing but JAX's own reader
(``jax.profiler.ProfileData``); everything after it is arithmetic on
``(start, end)`` intervals in nanoseconds, checked in
``benchmark/tests`` on hand-made intervals and on a small trace
recorded on the chip.

What a TPU trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per
executed HLO op (a ``while``/``conditional`` op spans the ops of its
body, hence ``self_times``) and whose line ``XLA Modules`` carries one
event per program execution (``Async XLA Ops`` carries one event per
asynchronous op, from its start to its done); the plane ``/host:CPU`` carries the
``TraceAnnotation`` spans the benchmark's worker wrote
(``bench.slice``, ``bench.data_next``, ``bench.run_step``) on the same
clock.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"  # one event per async op, start to done
HOST_PLANE = "/host:CPU"
SLICE_SPAN = "bench.slice"
HOST_SPAN_PREFIX = "bench."

Interval = tuple[float, float]


# ---- interval arithmetic --------------------------------------------


def union(intervals: list[Interval]) -> list[Interval]:
    """Merged, sorted, non-overlapping."""
    merged: list[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(intervals: list[Interval]) -> float:
    return sum(end - start for start, end in intervals)


def clip(intervals: list[Interval], lo: float, hi: float) -> list[Interval]:
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi
    ]


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """The parts of ``a`` that ``b`` does not cover (both merged)."""
    out: list[Interval] = []
    b = union(b)
    j = 0
    for start, end in union(a):
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(busy: list[Interval], lo: float, hi: float) -> list[Interval]:
    return subtract([(lo, hi)], busy)


@dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def duration(self) -> float:
        return self.end - self.start


def self_times(events: list[Event]) -> dict[str, float]:
    """Nanoseconds per op name, a container op (``while``) counted
    without the ops nested inside its interval."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [event, child ns]

    def close(entry):
        ev, children = entry
        out[ev.name] = out.get(ev.name, 0.0) + ev.duration - children
        if stack:
            stack[-1][1] += ev.duration

    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= ev.start:
            close(stack.pop())
        stack.append([ev, 0.0])
    while stack:
        close(stack.pop())
    return out


def attribute(
    idle: list[Interval], host_spans: list[Event]
) -> dict[str, float]:
    """Nanoseconds of device idleness by what the host was doing: each
    idle interval is split among the host spans overlapping it, the
    innermost span winning; the rest is ``host:other``."""
    out: dict[str, float] = {}
    for name in {e.name for e in host_spans}:
        own = [(e.start, e.end) for e in host_spans if e.name == name]
        inner = [
            (e.start, e.end)
            for e in host_spans
            if e.name != name
            and any(s <= e.start and e.end <= t for s, t in own)
        ]
        covered = total(
            subtract(_intersect(idle, union(own)), union(inner))
        )
        if covered > 0:
            out[name] = covered
    out["host:other"] = max(total(union(idle)) - sum(out.values()), 0.0)
    return out


def _intersect(a: list[Interval], b: list[Interval]) -> list[Interval]:
    a = union(a)
    return subtract(a, subtract(a, b))


# ---- the trace ------------------------------------------------------


@dataclass
class DevicePlane:
    ordinal: int
    ops: list[Event] = field(default_factory=list)
    modules: list[Event] = field(default_factory=list)
    async_ops: list[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: list[DevicePlane]
    host: list[Event]  # the benchmark's annotations
    lines_seen: dict[str, list[str]]  # plane -> line names (for debug)

    def window(self) -> Interval | None:
        """The profiled slice on the trace's clock: the benchmark's
        ``bench.slice`` annotation, else the span of device events."""
        for ev in self.host:
            if ev.name == SLICE_SPAN:
                return (ev.start, ev.end)
        spans = [
            (e.start, e.end) for d in self.devices for e in d.ops
        ]
        if not spans:
            return None
        return (min(s for s, _ in spans), max(e for _, e in spans))

    def busy(self, device: DevicePlane) -> list[Interval]:
        lo, hi = self.window()
        return clip(union([(e.start, e.end) for e in device.ops]), lo, hi)

    def busy_s(self) -> float | None:
        """Seconds in which an op ran, averaged over the chips."""
        if not self.devices or self.window() is None:
            return None
        return sum(total(self.busy(d)) for d in self.devices) / (
            len(self.devices) * 1e9
        )

    def window_s(self) -> float | None:
        w = self.window()
        return None if w is None else (w[1] - w[0]) / 1e9

    def top_ops(self, n: int = 10) -> list[list]:
        """[op kind, seconds] of the ops with most self time in the
        trace, averaged over the chips. An event's name is the whole
        HLO instruction; ops are grouped by ``op_kind`` (instruction
        name without its number, first result shape) and the group
        carries its number of executions."""
        acc: dict[str, float] = {}
        runs: dict[str, int] = {}
        for d in self.devices:
            for name, ns in self_times(d.ops).items():
                acc[op_kind(name)] = acc.get(op_kind(name), 0.0) + ns
            for ev in d.ops:
                runs[op_kind(ev.name)] = runs.get(op_kind(ev.name), 0) + 1
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        chips = len(self.devices)
        return [
            [f"{k} x{runs[k] // chips}", v / (chips * 1e9)]
            for k, v in ranked
        ]

    def idle_by_host(self) -> dict[str, float]:
        """Seconds of device idleness in the slice by what the host
        was doing (``attribute``), first chip's timeline."""
        if not self.devices or self.window() is None:
            return {}
        lo, hi = self.window()
        spans = [e for e in self.host if e.name != SLICE_SPAN]
        by = attribute(gaps(self.busy(self.devices[0]), lo, hi), spans)
        return {k: v / 1e9 for k, v in by.items()}

    def idle_gaps(self, n: int = 10) -> list[list]:
        """[what the host was doing, seconds of device idleness], the
        longest first."""
        ranked = sorted(self.idle_by_host().items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:n] if v > 0]

    def step_program(self) -> tuple[str, int, float] | None:
        """(name, executions, mean seconds) of the program with most
        device time in the slice: the train step."""
        if not self.devices or not self.devices[0].modules:
            return None
        acc: dict[str, list[float]] = {}
        for ev in self.devices[0].modules:
            acc.setdefault(ev.name, []).append(ev.duration)
        name, runs = max(acc.items(), key=lambda kv: sum(kv[1]))
        return name, len(runs), sum(runs) / len(runs) / 1e9

    def matching_s(self, pattern: re.Pattern) -> tuple[float, float, int]:
        """(seconds, exposed seconds, events) of the ops whose name
        matches, averaged over the chips; exposed is the part of their
        union during which no other op ran on that chip. An async op
        (``all-reduce-start`` .. ``-done``) counts from start to
        done."""
        if not self.devices:
            return 0.0, 0.0, 0
        seconds = exposed = 0.0
        events = 0
        for d in self.devices:
            hit = [
                e for e in d.ops + d.async_ops if pattern.search(e.name)
            ]
            rest = [
                (e.start, e.end)
                for e in d.ops
                if not pattern.search(e.name) and not _is_container(e, hit)
            ]
            mine = union([(e.start, e.end) for e in hit])
            seconds += total(mine)
            exposed += total(subtract(mine, rest))
            events += len(hit)
        n = len(self.devices)
        return seconds / (n * 1e9), exposed / (n * 1e9), events // n


# An op whose OPCODE is an all-reduce (synchronous, or the start / done
# halves of an asynchronous one). An event's name is the whole HLO
# instruction, operands included, so the opcode is what stands between
# the result type and the operand list, before any ``attribute=``: a
# fusion that merely names ``%all-reduce.5`` among its operands is no
# collective. A combined all-reduce has a tuple type with
# ``/*index=5*/`` comments in it (looked at by hand, PR 22).
ALL_REDUCE = re.compile(
    r"^%?[\w.\-]+ = (?:[^=]|/\*index=\d+\*/)*? "
    r"all-reduce(?:-start|-done)?\("
)

_HLO = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])?")


def op_kind(name: str) -> str:
    """``%fusion.3287 = (f32[16,1024,50257]{...}, ...) fusion(...)`` ->
    ``fusion f32[16,1024,50257]``; a name that is no HLO instruction
    stays as it is (cut to 80 characters)."""
    match = _HLO.match(name)
    if not match:
        return name[:80]
    return " ".join(part for part in match.groups() if part)


def _is_container(ev: Event, inner: list[Event]) -> bool:
    """An op that spans one of ``inner`` from outside (a ``while`` body
    holding a collective) is no evidence of overlapping compute."""
    return any(ev.start <= i.start and i.end <= ev.end for i in inner)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: list[DevicePlane] = []
    host: list[Event] = []
    lines_seen: dict[str, list[str]] = {}
    for plane in data.planes:
        lines = list(plane.lines)
        lines_seen[plane.name] = [ln.name for ln in lines]
        match = DEVICE_PLANE.match(plane.name)
        if match:
            dev = DevicePlane(ordinal=int(match.group(1)))
            for line in lines:
                if line.name == OPS_LINE:
                    dev.ops = _events(line)
                elif line.name == MODULES_LINE:
                    dev.modules = _events(line)
                elif line.name == ASYNC_LINE:
                    dev.async_ops = _events(line)
            if dev.ops:
                devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in lines:
                host.extend(
                    e
                    for e in _events(line)
                    if e.name.startswith(HOST_SPAN_PREFIX)
                )
    devices.sort(key=lambda d: d.ordinal)
    return Trace(devices=devices, host=host, lines_seen=lines_seen)


def _events(line) -> list[Event]:
    return [
        Event(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
        for ev in line.events
    ]
