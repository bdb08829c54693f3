"""graftscope: end-to-end rescale tracing and telemetry.

The rescale fast path (PR 1) and the transactional control plane
(PR 5) made rescales fast and safe, but left them unobservable: there
was no way to follow ONE rescale from the allocator's decision through
prepare→commit, checkpoint snapshot/write, worker exit, restart, AOT
cache hit, and first step. This module is that measurement layer —
the instrumentation substrate Pollux's (OSDI'21) evaluation and
CheckFreq's (FAST'21) snapshot/write/stall breakdowns are built on:

- **Spans** — ``with trace.span("ckpt.snapshot"): ...`` records a
  monotonic-clock duration plus a wall-clock start (cross-process
  alignment), nested parent/child ids per thread, and arbitrary
  attributes. ``trace.event(...)`` records a zero-duration point (and
  bumps a Prometheus counter). Disabled (``ADAPTDL_TRACE=off``) both
  cost one global read and an immediate return.
- **The step cycle** — the step path marks its phase boundaries on
  one clock (:class:`StepCycle`: the loader, ``shard_batch``, the
  dispatch, the gated pull, what follows it, the caller's loop) and
  every pull writes ONE span, ``step.cycle``, with each phase's sum
  and largest, and what tells whose second a stall was (CPU seconds,
  involuntary context switches, major faults, full collections). The
  same marks are ``jax.profiler`` annotations ``adaptdl.step.<phase>``
  on the device trace's clock.
- **Trace context** — W3C-style ``traceparent``
  (``00-<32hex>-<16hex>-01``). The allocator mints a fresh context per
  rescale decision; it propagates through ``rpc.py`` request headers
  and the ``ADAPTDL_TRACEPARENT`` environment variable across the
  checkpoint-restart boundary, so one trace id stitches the doomed
  incarnation's final save, the supervisor's epoch lifecycle, and the
  successor's restore/first-step into one timeline.
- **Bounded ring buffer** — finished spans land in a lock-guarded
  deque of ``BUFFER_SIZE`` capacity; a runaway producer can
  evict history but never grow memory.
- **Three exporters**:

  1. a per-job JSONL *structured event journal*
     (``ADAPTDL_TRACE_DIR/trace-<job>.jsonl``, one finished span per
     line) — durable across kills, which is what lets a chaos test
     prove trace-context survival through a mid-rescale worker death;
  2. Chrome/Perfetto ``trace_event`` JSON (:func:`to_perfetto`) for
     visual timelines (``chrome://tracing`` / ui.perfetto.dev);
  3. Prometheus histograms with per-phase buckets
     (:func:`prometheus_lines`), merged into the supervisor's
     ``/metrics`` exposition.

Workers flush their buffered spans to the supervisor (piggybacked on
the sched-hints cadence) via ``PUT /trace/{job}``; the supervisor
serves the stitched per-job view on ``GET /trace/{job}`` and the
``adaptdl-tpu trace`` CLI renders the phase waterfall. The final save
and the exit come after a doomed worker's last flush, so its records
from the termination signal on cross the restart through one file of
the checkpoint directory (:func:`write_handover` /
:func:`adopt_handover`): the successor's own buffer then holds the
rescale from the signal to its first step, journal or no journal.
"""

from __future__ import annotations

import gc
import json
import logging
import os
import random
import resource
import threading
import time
import zlib
from bisect import bisect_left
from collections import deque
from contextlib import contextmanager

from adaptdl_tpu import env

LOG = logging.getLogger(__name__)

# ---- trace context (W3C traceparent) ---------------------------------

_TRACEPARENT_VERSION = "00"
_SAMPLED_FLAGS = "01"

# Span/trace ids are identifiers, not secrets: a per-thread PRNG
# seeded once from os.urandom generates them at ~0.7us instead of
# paying the ~15us urandom syscall on every span (the overhead gate
# holds recording under 1% of step time). The state is keyed by pid
# so a fork (the elastic test harness launches replicas that way)
# reseeds in the child — otherwise every forked rank would emit the
# parent's id sequence and collide.
_rng_local = threading.local()


def _rand_hex(nbytes: int) -> str:
    state = getattr(_rng_local, "state", None)
    pid = os.getpid()
    if state is None or state[0] != pid:
        state = (
            pid,
            random.Random(int.from_bytes(os.urandom(16), "big")),
        )
        _rng_local.state = state
    return "%0*x" % (nbytes * 2, state[1].getrandbits(nbytes * 8))


def format_traceparent(trace_id: str, span_id: str) -> str:
    return (
        f"{_TRACEPARENT_VERSION}-{trace_id}-{span_id}-{_SAMPLED_FLAGS}"
    )


def parse_traceparent(header: str | None) -> tuple[str, str] | None:
    """(trace_id, span_id) from a W3C traceparent header, or None for
    anything malformed — a garbled inherited context must degrade to a
    fresh trace, never crash a restarting worker."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, _flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def new_traceparent() -> str:
    """A fresh trace context (NOT installed as this process's current
    one) — what the allocator mints per rescale decision."""
    return format_traceparent(_rand_hex(16), _rand_hex(8))


# Process-level root context: every span without an explicit
# traceparent (and without an enclosing span on its thread) parents
# here. Lazily initialized from ADAPTDL_TRACEPARENT so a restarted
# incarnation lands in the trace of the decision that restarted it.
_ctx_lock = threading.Lock()  # lock-order: 70
_trace_id: str | None = None  # guarded-by: _ctx_lock
_root_span_id: str | None = None  # guarded-by: _ctx_lock


def init_from_env(force: bool = False) -> None:
    """Adopt ``ADAPTDL_TRACEPARENT`` as this process's root context
    (or mint a fresh one when unset/malformed). Idempotent unless
    ``force``."""
    global _trace_id, _root_span_id
    with _ctx_lock:
        if _trace_id is not None and not force:
            return
        parsed = parse_traceparent(env.traceparent())
        if parsed is not None:
            _trace_id, _root_span_id = parsed
        else:
            _trace_id, _root_span_id = _rand_hex(16), _rand_hex(8)


def set_traceparent(header: str | None) -> bool:
    """Adopt an explicit trace context (e.g. from a /config snapshot:
    the live worker joins the rescale trace that is about to replace
    it). Returns False (context unchanged) on a malformed header."""
    global _trace_id, _root_span_id
    parsed = parse_traceparent(header)
    if parsed is None:
        return False
    with _ctx_lock:
        _trace_id, _root_span_id = parsed
    return True


def _root_context() -> tuple[str, str]:
    init_from_env()
    with _ctx_lock:
        return _trace_id, _root_span_id  # type: ignore[return-value]


def current_traceparent() -> str:
    """The context to propagate outward right now: the innermost open
    span on this thread, else the process root."""
    stack = getattr(_tls, "stack", None)
    if stack:
        return format_traceparent(stack[-1][0], stack[-1][1])
    trace_id, span_id = _root_context()
    return format_traceparent(trace_id, span_id)


# ---- enablement ------------------------------------------------------

_enabled: bool | None = None


def enabled() -> bool:
    global _enabled
    if _enabled is None:
        _enabled = env.trace_enabled()
    return _enabled


# This process's restart count, read once (it cannot change within an
# incarnation); stamped on every record so a cross-restart journal
# attributes spans to incarnations.
_incarnation: int | None = None


def _inc() -> int:
    global _incarnation
    if _incarnation is None:
        _incarnation = env.num_restarts()
    return _incarnation


# ---- the span record + ring buffer -----------------------------------

# Per-thread stack of (trace_id, span_id) for parent/child nesting.
_tls = threading.local()

# Capacity of the in-memory span ring (oldest spans evicted first).
BUFFER_SIZE = 4096

_buffer_lock = threading.Lock()  # lock-order: 72
_buffer: deque | None = None  # guarded-by: _buffer_lock
_seq = 0  # guarded-by: _buffer_lock
_flushed_seq = 0  # guarded-by: _buffer_lock


def _buffer_locked() -> deque:  # holds-lock: _buffer_lock
    global _buffer
    if _buffer is None:
        _buffer = deque(maxlen=BUFFER_SIZE)
    return _buffer


def buffer_seq() -> int:
    """Monotonic sequence of the newest recorded span (0 when none) —
    lets a caller bracket a window of interest."""
    with _buffer_lock:
        return _seq


def snapshot_spans() -> list[dict]:
    """A consistent copy of the ring buffer's current contents."""
    with _buffer_lock:
        return list(_buffer_locked())


def _record(rec: dict) -> None:
    """Export one finished span/event: ring buffer + histogram (+ the
    JSONL journal when configured)."""
    global _seq
    with _buffer_lock:
        _seq += 1
        rec["seq"] = _seq
        _buffer_locked().append(rec)
    _observe(rec)
    _journal_write(rec)


def _observe(rec: dict) -> None:
    """Feed one span record into the Prometheus registry (shared by
    locally recorded spans and worker spans absorbed by the
    supervisor)."""
    if rec.get("kind") == "event":
        with _metrics_lock:
            _counters[rec["name"]] = _counters.get(rec["name"], 0) + 1
    else:
        observe_phase(rec["name"], float(rec.get("dur", 0.0)))


def absorb(records: list[dict]) -> None:
    """Observe worker-posted span records into THIS process's
    Prometheus registry (the supervisor calls this on PUT /trace so
    its /metrics covers both sides of the rescale) without
    re-buffering or re-journaling them."""
    for rec in records:
        if isinstance(rec, dict) and "name" in rec:
            _observe(rec)


def _parent_context(traceparent: str | None) -> tuple[str, str]:
    """(trace_id, parent span id) of a record made now: the explicit
    foreign context when one is given, else this thread's innermost
    open span, else the process root."""
    parsed = parse_traceparent(traceparent) if traceparent else None
    if parsed is not None:
        return parsed
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1]
    return _root_context()


@contextmanager
def span(  # wire: produces=trace_span
    name: str, traceparent: str | None = None, **attrs
):
    """Record a monotonic-clock span around the ``with`` body.

    ``traceparent`` pins the span to an explicit foreign context (the
    supervisor recording epoch spans under a job's rescale trace);
    otherwise the span nests under this thread's innermost open span,
    else the process root. Yields a mutable attrs dict so the body can
    annotate outcomes (hit/miss, status, attempts). Exceptions
    propagate; the span still records, flagged ``error``."""
    if not enabled():
        yield attrs
        return
    trace_id, parent_id = _parent_context(traceparent)
    span_id = _rand_hex(8)
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    _tls.stack.append((trace_id, span_id))
    wall = time.time()
    start = time.monotonic()
    try:
        yield attrs
    except BaseException:
        attrs["error"] = True
        raise
    finally:
        dur = time.monotonic() - start
        _tls.stack.pop()
        _record(
            {
                "name": name,
                "trace": trace_id,
                "span": span_id,
                "parent": parent_id,
                "ts": wall,
                "dur": dur,
                "attrs": dict(attrs),
                "pid": os.getpid(),
                "tid": threading.current_thread().name,
                "inc": _inc(),
            }
        )


def record_span(  # wire: produces=trace_span
    name: str,
    duration_s: float,
    traceparent: str | None = None,
    ts: float | None = None,
    **attrs,
) -> str | None:
    """Record an already-measured span (the supervisor's epoch
    prepare→commit window is timed by the state layer, not a ``with``
    block; jax reports a compile phase when it has ended). Parents
    like :func:`span`: a ``jit.lower`` recorded while ``aot.compile``
    is open on the thread is its child. Returns the span's own
    traceparent (None when tracing is off), so that a caller can record
    a child of it (``boot.import`` under ``boot.process``)."""
    if not enabled():
        return None
    trace_id, parent_id = _parent_context(traceparent)
    span_id = _rand_hex(8)
    _record(
        {
            "name": name,
            "trace": trace_id,
            "span": span_id,
            "parent": parent_id,
            "ts": time.time() - duration_s if ts is None else ts,
            "dur": max(float(duration_s), 0.0),
            "attrs": dict(attrs),
            "pid": os.getpid(),
            "tid": threading.current_thread().name,
            "inc": _inc(),
        }
    )
    return format_traceparent(trace_id, span_id)


def event(  # wire: produces=trace_span
    name: str, traceparent: str | None = None, **attrs
) -> None:
    """Record a zero-duration point event and bump its Prometheus
    counter (``adaptdl_trace_events_total{event=...}``) — retries,
    circuit opens, cache hits/misses, epoch prepares."""
    if not enabled():
        return
    trace_id, parent_id = _parent_context(traceparent)
    _record(
        {
            "name": name,
            "kind": "event",
            "trace": trace_id,
            "span": _rand_hex(8),
            "parent": parent_id,
            "ts": time.time(),
            "dur": 0.0,
            "attrs": dict(attrs),
            "pid": os.getpid(),
            "tid": threading.current_thread().name,
            "inc": _inc(),
        }
    )


# ---- pending spans (cross-callsite: restart -> first step) -----------

_pending_lock = threading.Lock()  # lock-order: 71
# name -> (wall_start, monotonic_start, attrs)
_pending: dict[str, tuple[float, float, dict]] = {}  # guarded-by: _pending_lock


def begin_pending(name: str, **attrs) -> None:
    """Open a span whose end lives at a different callsite (bootstrap
    opens ``restart.first_step``; the first ``metrics.profile_step``
    closes it)."""
    if not enabled():
        return
    with _pending_lock:
        _pending[name] = (time.time(), time.monotonic(), dict(attrs))


def end_pending(name: str, **attrs) -> bool:
    """Close a :func:`begin_pending` span; False when none is open
    (every later step hits this cheap path)."""
    if not enabled():
        return False
    # Lock-free emptiness probe: this runs once per TRAINING STEP
    # (metrics.profile_step), and after the first step there is never
    # a pending span — don't pay a lock acquisition per step for it.
    # The race is benign: a begin_pending concurrent with this read
    # only delays the close to the next step.
    # graftcheck: disable=GC101 (lock-free emptiness probe by design;
    # the mutation path below re-checks under the lock)
    if not _pending:
        return False
    with _pending_lock:
        opened = _pending.pop(name, None)
    if opened is None:
        return False
    wall, start, open_attrs = opened
    open_attrs.update(attrs)
    # A pending span was opened at another callsite, so whatever span
    # is open on the closing thread is not its parent: the root is.
    record_span(
        name,
        time.monotonic() - start,
        traceparent=format_traceparent(*_root_context()),
        ts=wall,
        **open_attrs,
    )
    return True


# ---- the step cycle: the host's phases of the step path --------------

# The step path (``AdaptiveDataLoader``'s iterator -> ``ElasticTrainer.
# run_step``) pulls from the device every ``metrics_every``-th step and
# runs ahead of it in between, so a span a step would be ten records
# where one says as much. The path marks its phase boundaries instead
# (a clock read and an add each), and the pull writes ONE span,
# ``step.cycle``, from the previous pull's return to this one's, with
# where the host spent it. The same marks enter and leave a
# ``jax.profiler.TraceAnnotation`` ``adaptdl.step.<phase>``, so a
# profile of any job shows the host's phases over the device's ops on
# the profiler's own clock.
CYCLE_PHASES = (
    "data_next",  # the loader: ``__next__`` entered -> batch yielded
    "shard",  # ``shard_batch``: the batch's ``device_put``
    "dispatch",  # the call of the step program (a full queue's
    # back-pressure too: the per-step list shows which)
    "pull",  # ``block_until_ready``: the device finishing its queue
    "after_pull",  # the ``float()``s, GNS, progress, guard, counters
    "calibrate",  # a NEW batch size's first-time work in ``run_step``:
    # calibration (or its reuse) and the step program's build
    "outside",  # the caller's loop, ``run_step`` returned -> loader asked
)
(
    DATA_NEXT, SHARD, DISPATCH, PULL, AFTER_PULL, CALIBRATE, OUTSIDE,
) = range(len(CYCLE_PHASES))
_PHASE_ANNOTATIONS = tuple(
    None if name == "outside" else f"adaptdl.step.{name}"
    for name in CYCLE_PHASES
)

_clock = time.perf_counter


def _first_annotation(name: str):
    """jax's ``TraceAnnotation``, looked up at the first mark (the
    control plane imports this module without jax); it starts at its
    construction and costs ~0.3 us outside a profiler session."""
    global _new_annotation
    try:
        from jax.profiler import TraceAnnotation as made
    except ImportError:
        def made(_name):
            return None
    _new_annotation = made
    return made(name)


_new_annotation = _first_annotation


def _process_counts() -> tuple[float, int, int, int]:
    """(CPU seconds, involuntary context switches, major faults,
    generation-2 collections) of this process so far."""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return (
        usage.ru_utime + usage.ru_stime,
        usage.ru_nivcsw,
        usage.ru_majflt,
        gc.get_stats()[2]["collections"],
    )


class StepCycle:
    """The step path's clock. ``mark(phase)`` ends the running phase
    and starts ``phase``; ``mark(AFTER_PULL)`` — the pull has returned
    — also closes the cycle and records its ``step.cycle`` span. One
    thread marks (the training loop's); no lock. With tracing off a
    mark is one global read."""

    __slots__ = (
        "steps_total", "phase", "_since", "_start", "_annotation",
        "_sums", "_maxes", "_dispatch", "_data_next", "_steps",
        "_exposed", "_exposed_outside", "_counts",
    )

    def __init__(self):
        # ``run_step`` calls of this process so far; a cycle's
        # ``first_step`` counts in the same numbers, so a reader can
        # tell the cycles of its last N steps from those before.
        self.steps_total = 0
        self.phase = None  # the running phase; None before the first mark
        self._annotation = None
        self._reset(0.0)

    def _reset(self, now: float) -> None:
        self._start = now
        self._sums = [0.0] * len(CYCLE_PHASES)
        self._maxes = [0.0] * len(CYCLE_PHASES)
        self._dispatch = []
        self._data_next = []
        self._steps = 0
        self._exposed = None
        self._exposed_outside = 0.0

    def mark(self, phase: int) -> None:
        if not enabled():
            return
        now = _clock()
        running = self.phase
        if running is None:  # the first mark starts the clock
            self._reset(now)
            self._counts = _process_counts()
        else:
            spent = now - self._since
            self._sums[running] += spent
            if spent > self._maxes[running]:
                self._maxes[running] = spent
            if running == DISPATCH:
                self._dispatch.append(spent)
                if self._exposed is None:
                    self._exposed = now - self._start
                    self._exposed_outside = self._sums[OUTSIDE]
            elif running == DATA_NEXT:
                self._data_next.append(spent)
        self.phase = phase
        self._since = now
        if phase == DISPATCH:
            self._steps += 1
            self.steps_total += 1
        annotation = self._annotation
        if annotation is not None:
            annotation.__exit__(None, None, None)
        name = _PHASE_ANNOTATIONS[phase]
        self._annotation = (
            None if name is None else _new_annotation(name)
        )
        if phase == AFTER_PULL:
            self._close(now)

    def leave(self, phase: int) -> None:
        """``mark(OUTSIDE)`` if ``phase`` still runs: for an exit that
        may come late (a generator's ``finally``), when the path has
        long marked something else."""
        if self.phase == phase:
            self.mark(OUTSIDE)

    def _close(self, now: float) -> None:
        counts = _process_counts()
        attrs = {
            "steps": self._steps,
            "first_step": self.steps_total - self._steps + 1,
            "exposed_s": self._exposed or 0.0,
            "exposed_outside_s": self._exposed_outside,
            "dispatch_steps_s": self._dispatch,
            "data_next_steps_s": self._data_next,
            "cpu_s": counts[0] - self._counts[0],
            "nivcsw": counts[1] - self._counts[1],
            "majflt": counts[2] - self._counts[2],
            "gc2": counts[3] - self._counts[3],
            "threads": threading.active_count(),
        }
        for index, name in enumerate(CYCLE_PHASES):
            attrs[f"{name}_s"] = self._sums[index]
            attrs[f"{name}_max_s"] = self._maxes[index]
        dur = now - self._start
        self._reset(now)
        self._counts = counts
        record_span("step.cycle", dur, **attrs)


step_cycle = StepCycle()


# ---- jax.monitoring bridge: jit.trace / jit.lower / jit.compile ------

# jax reports each phase of making a program runnable as a duration
# event when the phase has ended; the bridge turns the outermost ones
# into spans. ``jit.compile`` is the backend compile OR the load from
# the persistent compile cache, whichever served the request.
_JAX_PHASE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
_JAX_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jit.cache_hit",
    "/jax/compilation_cache/cache_misses": "jit.cache_miss",
}
_jax_bridge_lock = threading.Lock()  # lock-order: 75
_jax_bridge_installed = False  # guarded-by: _jax_bridge_lock
_jax_cache_hits = 0  # guarded-by: _jax_bridge_lock


def _on_jax_phase_start(jax_event: str, _value, **_kwargs) -> None:
    # jax announces a phase's start as a scalar (its wall clock) and
    # its end as a duration: the pair gives the nesting depth.
    if jax_event in _JAX_PHASE_SPANS:
        _tls.jax_depth = getattr(_tls, "jax_depth", 0) + 1


def _on_jax_phase_end(
    jax_event: str, duration_s: float, **kwargs
) -> None:
    name = _JAX_PHASE_SPANS.get(jax_event)
    if name is None:
        return
    depth = max(getattr(_tls, "jax_depth", 0) - 1, 0)
    _tls.jax_depth = depth
    if depth:
        # Tracing one program traces every jitted function it calls
        # (each jnp operation is one): hundreds of events per program,
        # all inside the outermost one's interval. Recording them
        # would count their time twice and flood the ring buffer.
        return
    record_span(name, duration_s, fun=str(kwargs.get("fun_name", "")))


def _on_jax_event(jax_event: str, **_kwargs) -> None:
    global _jax_cache_hits
    name = _JAX_CACHE_EVENTS.get(jax_event)
    if name is None:
        return
    if name == "jit.cache_hit":
        with _jax_bridge_lock:
            _jax_cache_hits += 1
    event(name)


def install_jax_bridge() -> None:
    """Register this module's ``jax.monitoring`` listeners, once per
    process: the only ones the program registers. Imports jax here,
    not at module level: the control plane imports this module and
    must not pay for (or need) jax."""
    global _jax_bridge_installed
    with _jax_bridge_lock:
        if _jax_bridge_installed:
            return
        _jax_bridge_installed = True
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_on_jax_phase_start)
    jax.monitoring.register_event_duration_secs_listener(
        _on_jax_phase_end
    )
    jax.monitoring.register_event_listener(_on_jax_event)


def jax_cache_hits() -> int:
    """Programs jax's persistent compile cache has served in this
    process since the bridge was installed. A plain count, kept with
    tracing on or off: ``aot_cache.load_or_compile`` decides from it
    whether an executable may be serialized."""
    with _jax_bridge_lock:
        return _jax_cache_hits


# ---- exporter 1: per-job JSONL structured event journal --------------

_journal_lock = threading.Lock()  # lock-order: 74
_journal_fh = None  # guarded-by: _journal_lock
_journal_target: str | None = None  # guarded-by: _journal_lock
# Lock-free latch: once the journal is known to be unconfigured, every
# later record skips the env lookups entirely (set once, cleared only
# by _reset_state — a benign single-assignment race).
_journal_disabled = False


def _sanitize(job: str) -> str:
    return "".join(
        c if c.isalnum() or c in "-_." else "-" for c in job
    )


def journal_path() -> str | None:
    """The trace journal file this process appends to, or None when
    ``ADAPTDL_TRACE_DIR`` is unset."""
    directory = env.trace_dir()
    if not directory:
        return None
    job = env.job_id() or f"proc-{os.getpid()}"
    return os.path.join(directory, f"trace-{_sanitize(job)}.jsonl")


def _journal_write(rec: dict) -> None:
    """Append one finished span to the JSONL journal (flush per line,
    no fsync — the journal is observability, not a durability
    contract; a span lost to a power cut is not a torn checkpoint).
    Best-effort: a full disk must never fail training."""
    global _journal_fh, _journal_target, _journal_disabled
    if _journal_disabled:
        return
    path = journal_path()
    if path is None:
        _journal_disabled = True
        return
    try:
        with _journal_lock:
            if _journal_fh is None or _journal_target != path:
                if _journal_fh is not None:
                    _journal_fh.close()
                os.makedirs(os.path.dirname(path), exist_ok=True)
                # A killed predecessor may have left a torn final
                # line; start ours on a fresh line so its partial
                # record can't swallow our first one.
                needs_newline = False
                try:
                    with open(path, "rb") as existing:
                        existing.seek(0, os.SEEK_END)
                        if existing.tell() > 0:
                            existing.seek(-1, os.SEEK_END)
                            needs_newline = existing.read(1) != b"\n"
                except OSError:
                    needs_newline = False
                _journal_fh = open(path, "a", encoding="utf-8")
                _journal_target = path
                if needs_newline:
                    _journal_fh.write("\n")
            _journal_fh.write(json.dumps(rec, sort_keys=True) + "\n")
            _journal_fh.flush()
    except OSError:  # noqa: BLE001 - observability is best-effort
        LOG.debug("trace journal append failed", exc_info=True)


def read_journal(path: str) -> list[dict]:
    """Parse a trace journal. A torn final line (the process died
    mid-append) is dropped; a torn line mid-file (a killed
    incarnation's partial record, with later incarnations' records
    after it) is skipped so the successors' spans still read back —
    the file is append-only and shared across incarnations."""
    records: list[dict] = []
    try:
        with open(path, "rb") as f:
            for raw in f:
                if not raw.endswith(b"\n"):
                    break  # torn tail: nothing follows
                line = raw.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line.decode("utf-8"))
                except (ValueError, UnicodeDecodeError):
                    continue  # torn mid-file record: skip, keep going
                if isinstance(rec, dict):
                    records.append(rec)
    except OSError:
        return []
    return records


# ---- the hand-over: a dying worker's last spans -> its successor ------

# Workers flush to the supervisor on the heartbeat cadence; the final
# save and the exit come after the last beat, and the ring buffer dies
# with the process. So the exiting rank 0 leaves its records from the
# signal on in ONE fixed file of the checkpoint directory (the volume
# both incarnations share), and the successor puts them into its own
# ring buffer: its snapshot, its first flush and ``adaptdl-tpu trace``
# then show the rescale from the signal to the first step. The name
# matches neither ``checkpoint-*`` nor ``_tmp-checkpoint-*``, which the
# checkpoint layer scans and prunes.
HANDOVER_FILE = "trace-handover.jsonl"
HANDOVER_MAX_RECORDS = 256


def handover_path() -> str | None:
    root = env.checkpoint_path()
    return os.path.join(root, HANDOVER_FILE) if root else None


def write_handover(since: float) -> bool:
    """Write the ring buffer's records that started at ``since`` (wall
    clock) or later, the newest ``HANDOVER_MAX_RECORDS`` of them, to the
    hand-over file: whole to a temporary name, then renamed.
    Best-effort, like the journal; nothing with tracing off or without
    a checkpoint directory."""
    path = handover_path() if enabled() else None
    if path is None:
        return False
    records = [
        rec for rec in snapshot_spans() if rec.get("ts", 0.0) >= since
    ][-HANDOVER_MAX_RECORDS:]
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            for rec in records:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
        os.replace(tmp, path)
    except OSError:  # noqa: BLE001 - observability is best-effort
        LOG.debug("trace hand-over write failed", exc_info=True)
        return False
    return True


def adopt_handover() -> int:
    """Put the predecessor's handed-over records (those whose ``inc`` is
    one below this incarnation's: an older incarnation's file is stale)
    into the ring buffer under their own ``pid`` / ``inc`` / ``ts``,
    ahead of anything this process records next. Not re-journalled and
    not observed into this process's histograms: where a journal is
    configured the predecessor wrote them itself. Returns how many were
    adopted. The writer renames a whole file into place, so a file with
    a torn or unparsable line is not its work: like a missing one it
    adopts nothing, without error."""
    global _seq
    path = handover_path() if enabled() else None
    if path is None:
        return 0
    try:
        with open(path, "rb") as f:
            lines = sum(1 for raw in f if raw.strip())
    except OSError:
        return 0
    records = read_journal(path)
    if len(records) != lines:
        return 0
    want = _inc() - 1
    adopted = [
        rec
        for rec in records[-HANDOVER_MAX_RECORDS:]
        if rec.get("inc") == want and "name" in rec and "ts" in rec
    ]
    with _buffer_lock:
        for rec in adopted:
            _seq += 1
            rec["seq"] = _seq
            _buffer_locked().append(rec)
    return len(adopted)


# ---- exporter 2: Chrome/Perfetto trace_event JSON --------------------


def _tid_int(name: str) -> int:
    """Stable small integer for a thread name (trace_event wants
    numeric tids)."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def to_perfetto(records: list[dict]) -> dict:
    """Chrome ``trace_event`` JSON (the object form) from span
    records: complete ("X") events for spans, instant ("i") for
    events, plus process/thread-name metadata — loadable in
    chrome://tracing and ui.perfetto.dev."""
    events: list[dict] = []
    named: set[tuple[int, int]] = set()
    for rec in records:
        pid = int(rec.get("pid", 0))
        tid = _tid_int(str(rec.get("tid", "main")))
        if (pid, tid) not in named:
            named.add((pid, tid))
            events.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": str(rec.get("tid", "main"))},
                }
            )
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "args": {
                        "name": f"pid {pid} (inc {rec.get('inc', 0)})"
                    },
                }
            )
        args = dict(rec.get("attrs") or {})
        args["trace_id"] = rec.get("trace", "")
        args["span_id"] = rec.get("span", "")
        base = {
            "name": rec["name"],
            "cat": "adaptdl",
            "pid": pid,
            "tid": tid,
            "ts": float(rec.get("ts", 0.0)) * 1e6,
            "args": args,
        }
        if rec.get("kind") == "event":
            base["ph"] = "i"
            base["s"] = "p"
        else:
            base["ph"] = "X"
            base["dur"] = max(float(rec.get("dur", 0.0)), 0.0) * 1e6
        events.append(base)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---- exporter 3: Prometheus histograms + counters --------------------

# Per-phase latency buckets. RPC attempts live in the millisecond
# band; checkpoint/restore/compile phases in the 10ms-60s band.
_DEFAULT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)
_RPC_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
    0.5, 1.0, 2.5, 5.0, 10.0,
)


def _buckets_for(phase: str) -> tuple[float, ...]:
    return _RPC_BUCKETS if phase.startswith("rpc.") else _DEFAULT_BUCKETS


class Histogram:
    """One Prometheus histogram series: cumulative bucket counts, sum,
    count. Mutated under the registry lock."""

    __slots__ = ("buckets", "counts", "total", "count")

    def __init__(self, buckets: tuple[float, ...]):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # +Inf tail
        self.total = 0.0
        self.count = 0

    def observe_locked(self, value: float) -> None:  # holds-lock: _metrics_lock
        self.counts[bisect_left(self.buckets, value)] += 1
        self.total += value
        self.count += 1


_metrics_lock = threading.Lock()  # lock-order: 73
_histograms: dict[str, Histogram] = {}  # guarded-by: _metrics_lock
_counters: dict[str, int] = {}  # guarded-by: _metrics_lock


def observe_phase(phase: str, seconds: float) -> None:
    with _metrics_lock:
        hist = _histograms.get(phase)
        if hist is None:
            hist = Histogram(_buckets_for(phase))
            _histograms[phase] = hist
        hist.observe_locked(max(float(seconds), 0.0))


def escape_label_value(value: str) -> str:
    """Prometheus exposition-format label escaping: backslash, double
    quote, and newline."""
    return (
        str(value)
        .replace("\\", r"\\")
        .replace('"', r"\"")
        .replace("\n", r"\n")
    )


def _fmt_value(value) -> str:
    if isinstance(value, bool):
        return str(int(value))
    if isinstance(value, int):
        return str(value)
    as_float = float(value)
    if as_float == int(as_float) and abs(as_float) < 1e15:
        return str(int(as_float))
    return repr(as_float)


def _fmt_le(bound: float) -> str:
    return "+Inf" if bound == float("inf") else _fmt_value(bound)


class PromBuilder:
    """Prometheus text-exposition builder that conformance comes free
    from: every family gets exactly one ``# HELP`` and ``# TYPE``
    line, samples sit under their family, and label values are
    escaped. The supervisor's /metrics is assembled with this, so a
    malformed series cannot be emitted by construction."""

    def __init__(self):
        self._order: list[str] = []
        # family -> (type, help, [sample lines])
        self._families: dict[str, tuple[str, str, list[str]]] = {}

    def family(self, name: str, mtype: str, help_text: str) -> None:
        if name not in self._families:
            self._order.append(name)
            self._families[name] = (mtype, help_text, [])

    def sample(
        self,
        family: str,
        labels: dict | None = None,
        value=0,
        suffix: str = "",
    ) -> None:
        if family not in self._families:
            raise ValueError(
                f"sample for undeclared family {family!r} — declare "
                "it with family() first (HELP/TYPE are mandatory)"
            )
        label_text = ""
        if labels:
            inner = ",".join(
                f'{key}="{escape_label_value(val)}"'
                for key, val in labels.items()
            )
            label_text = "{" + inner + "}"
        self._families[family][2].append(
            f"{family}{suffix}{label_text} {_fmt_value(value)}"
        )

    def histogram(
        self, family: str, labels: dict, hist: Histogram
    ) -> None:
        cumulative = 0
        for bound, count in zip(
            tuple(hist.buckets) + (float("inf"),), hist.counts
        ):
            cumulative += count
            self.sample(
                family,
                dict(labels, le=_fmt_le(bound)),
                cumulative,
                suffix="_bucket",
            )
        self.sample(family, labels, hist.total, suffix="_sum")
        self.sample(family, labels, hist.count, suffix="_count")

    def render(self) -> str:
        lines: list[str] = []
        for name in self._order:
            mtype, help_text, samples = self._families[name]
            lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} {mtype}")
            lines.extend(samples)
        return "\n".join(lines) + "\n"


def render_into(builder: PromBuilder) -> None:
    """Add the trace registry's histogram + counter families to a
    metrics exposition (the supervisor's /metrics calls this)."""
    builder.family(
        "adaptdl_trace_phase_seconds",
        "histogram",
        "Duration of traced rescale-lifecycle phases, by span name.",
    )
    builder.family(
        "adaptdl_trace_events_total",
        "counter",
        "Traced point events (retries, circuit opens, cache "
        "hits/misses, epoch transitions), by event name.",
    )
    with _metrics_lock:
        hists = {
            phase: (
                hist.buckets, list(hist.counts), hist.total, hist.count
            )
            for phase, hist in _histograms.items()
        }
        counters = dict(_counters)
    for phase in sorted(hists):
        buckets, counts, total, count = hists[phase]
        snap = Histogram(buckets)
        snap.counts, snap.total, snap.count = counts, total, count
        builder.histogram(
            "adaptdl_trace_phase_seconds", {"phase": phase}, snap
        )
    for name in sorted(counters):
        builder.sample(
            "adaptdl_trace_events_total",
            {"event": name},
            counters[name],
        )


def prometheus_lines() -> str:
    """The trace families as a standalone exposition (tests; embedded
    use goes through :func:`render_into`)."""
    builder = PromBuilder()
    render_into(builder)
    return builder.render()


# ---- worker -> supervisor flush --------------------------------------


def flush_to_supervisor(  # wire: produces=trace_payload
    job_id: str | None = None,
) -> bool:
    """Best-effort PUT of this process's not-yet-flushed spans to the
    supervisor's per-job trace store (piggybacked on the sched-hints
    cadence). The flush request itself is untraced — tracing the
    flush would generate a span per flush, forever."""
    global _flushed_seq
    if not enabled():
        return False
    url = env.supervisor_url()
    job_id = job_id if job_id is not None else env.job_id()
    if not url or not job_id:
        return False
    with _buffer_lock:
        pending = [
            rec
            for rec in _buffer_locked()
            if rec["seq"] > _flushed_seq
        ]
    if not pending:
        return True
    from adaptdl_tpu import rpc

    try:
        response = rpc.default_client().put(
            f"{url}/trace/{job_id}",
            endpoint=f"trace/{job_id}",
            json={"spans": pending},
            timeout=(0.5, 5),
            attempts=1,
            circuit_threshold=3,
            circuit_cooldown=60.0,
            traced=False,
        )
        response.raise_for_status()
    except Exception as exc:  # noqa: BLE001 - best effort by design
        LOG.debug("trace flush failed: %s", exc)
        return False
    with _buffer_lock:
        _flushed_seq = max(
            _flushed_seq, max(rec["seq"] for rec in pending)
        )
    return True


# ---- waterfall / summaries -------------------------------------------


def phase_summary(records: list[dict]) -> dict[str, float]:
    """name -> median duration (seconds) over span records — the
    per-phase breakdown ``adaptdl-tpu trace`` prints."""
    by_name: dict[str, list[float]] = {}
    for rec in records:
        if rec.get("kind") == "event":
            continue
        by_name.setdefault(rec["name"], []).append(
            float(rec.get("dur", 0.0))
        )
    summary = {}
    for name, durs in by_name.items():
        durs.sort()
        mid = len(durs) // 2
        if len(durs) % 2:
            summary[name] = durs[mid]
        else:
            summary[name] = (durs[mid - 1] + durs[mid]) / 2.0
    return summary


def self_times(records: list[dict]) -> dict[str, float]:
    """span id -> self time (seconds): the span's duration less the
    part of its interval that its child spans (by ``parent`` id)
    cover. Summed over a span and all its descendants, self times
    give the span's duration: the non-overlapping account of it."""
    spans = [r for r in records if r.get("kind") != "event"]
    children: dict[str, list[tuple[float, float]]] = {}
    for rec in spans:
        start = float(rec.get("ts", 0.0))
        children.setdefault(rec.get("parent", ""), []).append(
            (start, start + float(rec.get("dur", 0.0)))
        )
    out = {}
    for rec in spans:
        start = float(rec.get("ts", 0.0))
        end = start + float(rec.get("dur", 0.0))
        covered, reach = 0.0, start
        for c_start, c_end in sorted(children.get(rec["span"], ())):
            c_start, c_end = max(c_start, reach), min(c_end, end)
            if c_end > c_start:
                covered += c_end - c_start
                reach = c_end
        out[rec["span"]] = max(end - start - covered, 0.0)
    return out


def render_waterfall(records: list[dict], width: int = 32) -> str:
    """ASCII phase waterfall of one trace's spans, ordered by wall
    start, children indented under their parents, each with its self
    time (``adaptdl-tpu trace`` prints this)."""
    spans = [r for r in records if r.get("kind") != "event"]
    if not spans:
        return "(no spans)"
    spans.sort(key=lambda r: float(r.get("ts", 0.0)))
    t0 = float(spans[0]["ts"])
    horizon = max(
        float(r["ts"]) + float(r.get("dur", 0.0)) for r in spans
    ) - t0 or 1e-9
    own = self_times(spans)
    parent_of = {r["span"]: r.get("parent") for r in spans}

    def depth(span_id: str) -> int:
        n = 0  # bounded: a garbled journal's parent cycle must end
        while (
            span_id := parent_of.get(span_id)
        ) in parent_of and n < len(parent_of):
            n += 1
        return n

    lines = [
        f"{'PHASE':<28} {'SIDE':<12} {'START(ms)':>10} "
        f"{'DUR(ms)':>10} {'SELF(ms)':>10}  TIMELINE"
    ]
    for rec in spans:
        offset = float(rec["ts"]) - t0
        dur = float(rec.get("dur", 0.0))
        lead = int(width * offset / horizon)
        bar = max(int(width * dur / horizon), 1)
        side = f"pid{rec.get('pid', '?')}/i{rec.get('inc', 0)}"
        name = "  " * depth(rec["span"]) + rec["name"]
        lines.append(
            f"{name:<28} {side:<12} {offset * 1e3:>10.2f} "
            f"{dur * 1e3:>10.2f} {own[rec['span']] * 1e3:>10.2f}  "
            f"{' ' * lead}{'#' * min(bar, width - lead or 1)}"
        )
    return "\n".join(lines)


def render_cycles(records: list[dict], limit: int = 20) -> str:
    """The ``step.cycle`` spans as a table, one row a pull, in time
    order (of more than ``limit`` the longest ``limit``); ``*`` marks
    the longest, ``xMED`` is ``dur`` over the median cycle's.
    Milliseconds a cycle; ``WORST`` is the phase that held
    the longest single stretch, which is where a lost second shows
    (``adaptdl-tpu trace`` prints this)."""
    cycles = [r for r in records if r.get("name") == "step.cycle"]
    if not cycles:
        return "(no cycles)"
    longest = max(cycles, key=lambda r: float(r.get("dur", 0.0)))
    shown = sorted(
        cycles, key=lambda r: -float(r.get("dur", 0.0))
    )[:limit]
    shown.sort(key=lambda r: r.get("attrs", {}).get("first_step", 0))
    median = phase_summary(cycles)["step.cycle"] or 1e-9
    lines = [
        f"{len(cycles)} cycle(s), {len(shown)} shown; dur median "
        f"{median * 1e3:.1f} ms, longest "
        f"{float(longest.get('dur', 0.0)) * 1e3:.1f} ms",
        f"  {'FIRST':>7} {'STEPS':>5} {'DUR':>9} {'xMED':>6} "
        + " ".join(f"{name[:9]:>9}" for name in CYCLE_PHASES)
        + f" {'exposed':>8} {'cpu':>8} {'nivcsw':>6} {'majflt':>6} "
        f"{'gc2':>3} {'thr':>3}  WORST",
    ]
    for rec in shown:
        attrs = rec.get("attrs", {})
        worst = max(
            CYCLE_PHASES, key=lambda n: attrs.get(f"{n}_max_s", 0.0)
        )
        lines.append(
            f"{'*' if rec is longest else ' '} "
            f"{attrs.get('first_step', 0):>7} {attrs.get('steps', 0):>5} "
            f"{float(rec.get('dur', 0.0)) * 1e3:>9.1f} "
            f"{float(rec.get('dur', 0.0)) / median:>6.2f} "
            + " ".join(
                f"{attrs.get(f'{name}_s', 0.0) * 1e3:>9.2f}"
                for name in CYCLE_PHASES
            )
            + f" {attrs.get('exposed_s', 0.0) * 1e3:>8.2f} "
            f"{attrs.get('cpu_s', 0.0) * 1e3:>8.1f} "
            f"{attrs.get('nivcsw', 0):>6} {attrs.get('majflt', 0):>6} "
            f"{attrs.get('gc2', 0):>3} {attrs.get('threads', 0):>3}  "
            f"{worst} {attrs.get(f'{worst}_max_s', 0.0) * 1e3:.2f}"
        )
    return "\n".join(lines)


# ---- test isolation --------------------------------------------------


def _reset_state() -> None:
    """Drop all trace state (tests): buffer, registry, context,
    journal handle, enablement cache."""
    global _buffer, _seq, _flushed_seq, _enabled, _incarnation
    global _trace_id, _root_span_id, _journal_fh, _journal_target
    global _journal_disabled, step_cycle
    step_cycle = StepCycle()
    with _buffer_lock:
        _buffer = None
        _seq = 0
        _flushed_seq = 0
    with _metrics_lock:
        _histograms.clear()
        _counters.clear()
    with _ctx_lock:
        _trace_id = None
        _root_span_id = None
    with _pending_lock:
        _pending.clear()
    with _journal_lock:
        if _journal_fh is not None:
            _journal_fh.close()
        _journal_fh = None
        _journal_target = None
    _journal_disabled = False
    _enabled = None
    _incarnation = None
    if hasattr(_tls, "stack"):
        _tls.stack = []
