"""Preemption-notice survival for spot/preemptible TPU VMs.

The reference polls the EC2 spot-termination metadata endpoint and
triggers the graceful checkpoint-exit path (reference:
ray/adaptdl_ray/aws/worker.py:33-70). GCE exposes the same signal at
the instance metadata server: ``/computeMetadata/v1/instance/preempted``
flips to TRUE when the VM is being reclaimed (and ACPI G2 follows).

A notice here is not just a graceful-exit flag: it opens the **urgent
drain** path —

1. :func:`deliver_notice` stamps a drain deadline (the notice window
   minus a margin), mints a fresh trace context for the survival arc
   (``preempt.notice`` → ``drain.save`` → successor
   ``restart.first_step`` share one trace id), raises the graceful
   exit flag, and notifies the supervisor via ``POST /preempt/{job}``
   (resilient rpc, idempotent server-side) so re-placement overlaps
   the drain instead of waiting for lease expiry;
2. the training loop's graceful-exit path runs :func:`urgent_drain` —
   a bounded blocking checkpoint that *joins* any in-flight async
   write (``checkpoint.save_all_states`` serializes saves), budgeted
   against the measured ``restart_stats`` so "will the save fit the
   window" is known, not hoped — then exits 143 as usual.

The listener itself is hardened for off-GCE runs: the poll interval
is jittered, and after ``BACKOFF_AFTER`` consecutive
*unreachable* polls (no metadata server at all — a dev box, a CI
runner) it backs off to ``SLOW_POLL_S`` instead of
hammering a dead endpoint every few seconds; one reachable poll
restores the base cadence.
"""

from __future__ import annotations

import logging
import random
import threading
import time

from adaptdl_tpu import _signal, checkpoint, env, faults, rpc, trace

LOG = logging.getLogger(__name__)

GCE_PREEMPTED_URL = (
    "http://metadata.google.internal/computeMetadata/v1/instance/preempted"
)
_HEADERS = {"Metadata-Flavor": "Google"}

# Off GCE: consecutive unreachable polls before the listener backs
# off, and the cadence (seconds) it idles at from then on.
BACKOFF_AFTER = 12
SLOW_POLL_S = 60.0

# Poll outcomes (tri-state: "reachable but not preempted" must reset
# the off-GCE backoff streak, while "unreachable" must grow it).
POLL_PREEMPTED = "preempted"
POLL_OK = "ok"
POLL_UNREACHABLE = "unreachable"

_notice_lock = threading.Lock()
# The one notice this incarnation may receive: set by deliver_notice,
# read by the drain/notify paths and tests. None = no notice yet.
_notice: dict | None = None  # guarded-by: _notice_lock
_listener_stop: threading.Event | None = None  # guarded-by: _notice_lock
# Handles of the background threads this module starts, kept so
# stop_listener() can prove them drained (tests, explicit teardown).
_listener_thread: threading.Thread | None = None
_notify_thread: threading.Thread | None = None


def poll_status(
    url: str = GCE_PREEMPTED_URL, timeout: float = 2.0
) -> str:
    """One metadata poll, tri-state: :data:`POLL_PREEMPTED` when the
    server reports the VM reclaimed, :data:`POLL_OK` when it answered
    anything else, :data:`POLL_UNREACHABLE` when nothing answered at
    all (off GCE, DNS dead, injected drop).

    Rides the rpc client with a single attempt and no circuit breaker:
    the listener's own interval IS the retry loop, and skipping polls
    during a breaker cooldown could delay a real preemption notice —
    on GCE the metadata server is local and reliable, and off GCE
    every poll fails identically either way."""
    try:
        response = rpc.default_client().get(
            url,
            headers=_HEADERS,
            timeout=timeout,
            attempts=1,
            use_circuit=False,
        )
    except Exception:  # noqa: BLE001 - metadata server unreachable
        return POLL_UNREACHABLE
    if response.status_code == 200 and (
        response.text.strip().upper() == "TRUE"
    ):
        return POLL_PREEMPTED
    return POLL_OK


def poll_once(url: str = GCE_PREEMPTED_URL, timeout: float = 2.0) -> bool:
    """True if the metadata server reports this VM as preempted."""
    return poll_status(url, timeout) == POLL_PREEMPTED


def _poll_for_notice(
    url: str = GCE_PREEMPTED_URL, timeout: float = 2.0
) -> str:
    """One listener poll cycle. The ``preempt.notice`` injection point
    SIMULATES a reclaim notice (like ``alloc.commit_timeout``
    suppresses a commit): an injected fault here is a notice, so chaos
    runs exercise the whole drain path without a metadata server."""
    try:
        faults.maybe_fail("preempt.notice")
    except faults.InjectedFault:
        return POLL_PREEMPTED
    return poll_status(url, timeout)


# ---- notice state ----------------------------------------------------


def notice_active() -> bool:
    """Whether this incarnation has received a preemption notice."""
    with _notice_lock:
        return _notice is not None


def notice_state() -> dict | None:
    """Snapshot of the active notice (None before any): source,
    notice window, drain budget/deadline, trace parent, whether the
    supervisor acknowledged the report and whether the drain ran."""
    with _notice_lock:
        return dict(_notice) if _notice is not None else None


def drain_remaining_s() -> float | None:  # wire: consumes=preempt_notice
    """Seconds left in the drain budget (None without a notice)."""
    with _notice_lock:
        if _notice is None:
            return None
        deadline = _notice["deadline"]
    return max(deadline - time.monotonic(), 0.0)


def reset_notice() -> None:
    """Clear notice state (tests; a real process dies with its
    notice)."""
    global _notice
    with _notice_lock:
        _notice = None


def deliver_notice(  # wire: produces=preempt_notice
    source: str = "metadata",
    notice_s: float | None = None,
    notify: bool = True,
) -> bool:
    """Record a preemption notice for this incarnation (idempotent:
    False when one is already active). Mints a fresh trace context for
    the survival arc, raises the graceful-exit flag so the training
    loop checkpoints and exits 143 at the next step boundary, and —
    with ``notify`` — reports the notice to the supervisor in the
    background so the successor's re-placement overlaps the drain."""
    global _notice, _notify_thread
    if notice_s is None:
        notice_s = env.preempt_notice_s()
    budget = max(float(notice_s) - env.preempt_margin_s(), 1.0)
    traceparent = trace.new_traceparent()
    with _notice_lock:
        if _notice is not None:
            return False
        _notice = {
            "source": source,
            "noticeS": float(notice_s),
            "budgetS": budget,
            "deadline": time.monotonic() + budget,
            "traceParent": traceparent,
            "reported": False,
            "drained": False,
        }
    # The survival arc's trace root: the drain save and (via the
    # supervisor's re-placement decision) the successor's restore/
    # first-step spans all stitch onto this id.
    trace.set_traceparent(traceparent)
    trace.event(
        "preempt.notice",
        traceparent=traceparent,
        source=source,
        noticeS=float(notice_s),
    )
    LOG.warning(
        "preemption notice (%s): draining within %.1fs "
        "(notice window %.1fs)",
        source, budget, notice_s,
    )
    _signal.set_exit_flag(True)
    if notify:
        _notify_thread = threading.Thread(
            target=notify_supervisor,
            name="adaptdl-preempt-notify",
            daemon=True,
        )
        _notify_thread.start()
    return True


def notify_supervisor(  # wire: produces=preempt,preempt_notice # wire: consumes=preempt_notice
    job_id: str | None = None,
) -> bool:
    """POST the active notice to the supervisor (idempotent there: one
    drain per incarnation no matter how many replicas report). Best
    effort with retries bounded well inside the notice window — the
    drain save must never starve behind a dead supervisor."""
    url = env.supervisor_url()
    job_id = job_id if job_id is not None else env.job_id()
    with _notice_lock:
        notice = dict(_notice) if _notice is not None else None
    if not url or not job_id or notice is None:
        return False
    try:
        response = rpc.default_client().post(
            f"{url}/preempt/{job_id}",
            endpoint=f"preempt/{job_id}",
            json={
                "group": env.num_restarts(),
                "rank": env.process_rank(),
                "noticeS": notice["noticeS"],
                "traceParent": notice["traceParent"],
            },
            timeout=(2, 5),
            attempts=3,
            deadline=min(notice["budgetS"] / 2.0, 10.0),
            use_circuit=False,
        )
        response.raise_for_status()
    except Exception as exc:  # noqa: BLE001 - drain must not block
        LOG.warning("failed to report preemption notice: %s", exc)
        return False
    with _notice_lock:
        if _notice is not None:
            _notice["reported"] = True
    return True


# ---- urgent drain ----------------------------------------------------


def urgent_drain(  # wire: produces=preempt_notice,drain_report
    # wire: consumes=preempt_notice
) -> dict:
    """The notice-driven final checkpoint: join any in-flight async
    write (``save_all_states`` waits for it before starting — two
    saves can never race into one version dir), then run the blocking
    save, all budgeted against the drain deadline. Returns a summary:
    whether the measured ``restart_stats`` predicted the save would
    fit, whether an in-flight write was joined, and whether the
    deadline was actually met (a miss records a
    ``drain.deadline_exceeded`` trace event — the signal the margin
    or the checkpoint cadence needs tuning)."""
    with _notice_lock:
        notice = dict(_notice) if _notice is not None else None
    deadline = notice["deadline"] if notice else None
    traceparent = (
        notice["traceParent"] if notice else trace.current_traceparent()
    )
    remaining = (
        None
        if deadline is None
        else max(deadline - time.monotonic(), 0.0)
    )
    expected = _expected_save_s()
    fits = (
        None
        if expected is None or remaining is None
        else expected <= remaining
    )
    if fits is False:
        LOG.warning(
            "urgent drain may miss the notice window: measured save "
            "cost %.2fs vs %.2fs remaining",
            expected, remaining,
        )
    inflight = checkpoint.inflight_save()
    joined = inflight is not None and not inflight.done()
    # Chaos hook: fail → the drain save never starts (previous
    # checkpoint stays newest); exit → the VM dies mid-drain, the
    # notice-window-expires-mid-save scenario.
    faults.maybe_fail("preempt.drain_save")
    start = time.monotonic()
    with trace.span(
        "drain.save",
        traceparent=traceparent,
        joined_inflight=joined,
    ) as attrs:
        if remaining is not None:
            attrs["budget_s"] = round(remaining, 4)
        # Forced FULL: the save a successor's life depends on must
        # restore standalone — never as a delta riding a chain whose
        # base lives on a VM about to vanish or a disk mid-flush.
        checkpoint.save_all_states(wait=True, force_full=True)
    duration = time.monotonic() - start
    met = deadline is None or time.monotonic() <= deadline
    if not met:
        trace.event(
            "drain.deadline_exceeded",
            traceparent=traceparent,
            overrun_s=round(
                duration - (remaining or 0.0), 4
            ),
        )
        LOG.warning(
            "urgent drain overran the notice window by %.2fs",
            duration - (remaining or 0.0),
        )
    with _notice_lock:
        if _notice is not None:
            _notice["drained"] = True
            _notice["drainS"] = duration
    # The drain spans must reach the supervisor BEFORE exit 143: this
    # process is about to die, and the survival trace's worker half
    # lives only in its buffer.
    trace.flush_to_supervisor()
    return {
        "durationS": duration,
        "deadlineMet": met,
        "fitPredicted": fits,
        "joinedInflight": joined,
    }


def _expected_save_s() -> float | None:  # wire: consumes=restart_stats
    """Measured blocking-save cost (snapshot + write of the last
    save) from the metrics engine, None until one was measured."""
    try:
        from adaptdl_tpu import metrics

        stats = metrics.restart_stats()
    except Exception:  # noqa: BLE001 - budgeting is best-effort
        return None
    if not stats or stats.get("snapshotS") is None:
        return None
    return float(stats.get("snapshotS") or 0.0) + float(
        stats.get("writeS") or 0.0
    )


# ---- listener --------------------------------------------------------


def _next_interval(
    streak: int,
    base: float,
    slow: float,
    backoff_after: int,
    jitter: float,
) -> float:
    """The wait before the next poll: the base cadence, or the slow
    cadence once ``backoff_after`` consecutive polls found no metadata
    server at all; ±20% jitter (``jitter`` in [0, 1)) so a fleet's
    workers don't poll in lockstep."""
    cadence = slow if streak >= backoff_after else base
    return cadence * (0.8 + 0.4 * jitter)


def start_listener(
    url: str = GCE_PREEMPTED_URL,
    interval: float | None = None,
    slow_interval: float = SLOW_POLL_S,
    backoff_after: int = BACKOFF_AFTER,
) -> threading.Event:
    """Poll for preemption in the background; on notice, run
    :func:`deliver_notice` (graceful-exit flag + supervisor report)
    and stop. Returns a stop event for tests/teardown."""
    if interval is None:
        interval = env.preempt_poll_s() or 5.0
    stop = threading.Event()
    rng = random.Random()

    def loop():
        streak = 0
        while True:
            status = _poll_for_notice(url)
            if status == POLL_PREEMPTED:
                deliver_notice(source="metadata")
                return
            if status == POLL_UNREACHABLE:
                streak += 1
                if streak == backoff_after:
                    LOG.info(
                        "metadata endpoint unreachable %d times; "
                        "backing preemption polls off to %.0fs",
                        streak, slow_interval,
                    )
            else:
                streak = 0
            wait = _next_interval(
                streak, interval, slow_interval, backoff_after,
                rng.random(),
            )
            if stop.wait(wait):
                return

    global _listener_thread, _listener_stop
    with _notice_lock:
        # Record the stop event for stop_listener() even when the
        # caller bypassed ensure_listener(): every started poller must
        # be stoppable through the module-level teardown path.
        _listener_stop = stop
    _listener_thread = threading.Thread(
        target=loop, name="adaptdl-preemption", daemon=True
    )
    _listener_thread.start()
    return stop


def ensure_listener() -> threading.Event | None:
    """Start the notice listener once per process when the deployment
    opted in (``ADAPTDL_PREEMPT_POLL_S > 0`` — spot pools set it; the
    default 0 keeps dev boxes and CI free of background metadata
    polls). Idempotent; returns the stop event or None."""
    global _listener_stop
    if env.preempt_poll_s() <= 0:
        return None
    with _notice_lock:
        if _listener_stop is not None and not _listener_stop.is_set():
            return _listener_stop
    stop = start_listener()
    with _notice_lock:
        _listener_stop = stop
    return stop


def stop_listener(timeout: float | None = 5.0) -> None:
    """Stop the notice listener and join the background threads this
    module started — the poll loop and any in-flight notify post.
    Safe when nothing is running; tests and explicit worker teardown
    call this so no poller outlives its process's useful life."""
    with _notice_lock:
        stop = _listener_stop
    if stop is not None:
        stop.set()
    if _listener_thread is not None:
        _listener_thread.join(timeout)
    if _notify_thread is not None:
        _notify_thread.join(timeout)
