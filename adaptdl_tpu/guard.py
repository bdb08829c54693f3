"""Numeric-health sentinel: NaN/loss-spike detection, last-known-good
rollback, and incident reporting (graftguard).

Every robustness layer below this one hardens the control plane
against *fail-stop* faults — crashes, kills, partitions, preemptions.
This module defends the data plane against *fail-corrupt*: a job that
keeps heartbeating while NaN gradients, a loss spike, or a flaky
device silently destroys model state, and whose still-reported
throughput poisons the Pollux goodput fit every allocation decision
rests on.

Detection piggybacks on values the step already computes — the loss
and the GNS machinery's gradient statistics pulled to the host by
``ElasticTrainer.run_step``'s gated metrics sync — so a healthy step
pays nothing beyond a handful of float comparisons:

- **NaN/Inf**: loss or gradient statistics non-finite -> ``nan_loss``
  / ``nan_grad``. Always armed.
- **Spike**: a finite loss farther than ``MAD_K``
  robust sigmas (1.4826 x MAD) above the rolling median of the last
  ``WINDOW`` *healthy* losses -> ``loss_spike``. Arms
  once ``MIN_SAMPLES`` healthy samples exist; only the
  upper side fires (a sudden improvement is not a failure). Unhealthy
  samples never enter the window, so a NaN burst cannot drag the
  baseline with it.

Policy (``ADAPTDL_GUARD_POLICY``) decides the response: ``warn`` logs
and reports, ``skip`` additionally records the poisoned batch range so
the deterministic sampler never re-feeds it, ``rollback`` (default)
restores the newest *good*-marked checkpoint
(``checkpoint.rollback_to_good``) and then records the skip range so
the same poison pill cannot re-trigger on resume. A checkpoint earns
its good marker only after ``ADAPTDL_GUARD_CONFIRM_STEPS`` subsequent
healthy observations (``checkpoint.note_healthy_step``) — an
unhealthy step clears all pending candidates, because corruption
precedes detection and a snapshot taken in the gap must never be
trusted. Note the detection latency: ``run_step`` syncs metrics every
``metrics_every`` steps, so CONFIRM_STEPS should comfortably exceed
that gate for the marker to mean anything.

Every incident is also reported (best-effort, like hint posting) to
the supervisor's ``POST /incident/{job}`` route, which journals it and
charges blame: recurring incidents on the *same slot* across
different data strike the slot toward quarantine; recurring incidents
on the *same data* across slots blame the data (no hardware
quarantine). The worker sends its rank — the supervisor resolves the
occupied slot from the job's allocation, so workers stay ignorant of
slot naming.

Thread model: ``observe_step`` runs on the training thread only (the
same thread that drives ``run_step`` and the dataloader); the guard
keeps no lock of its own. ``guard_stats()`` reads plain ints/floats
(GIL-atomic) and may be called from the hint-posting path.
"""

from __future__ import annotations

import logging
import math
from typing import Any

from adaptdl_tpu import env, faults

LOG = logging.getLogger(__name__)

# Incident kinds (the wire vocabulary of the `incident` family).
KIND_NAN_LOSS = "nan_loss"
KIND_NAN_GRAD = "nan_grad"
KIND_LOSS_SPIKE = "loss_spike"

# Consistency constant: scaled median-absolute-deviation estimates the
# standard deviation of a normal distribution.
_MAD_SIGMA = 1.4826

# The spike detector: healthy losses kept for the rolling median + MAD,
# how many of them arm it (NaN/Inf detection is always on), and the
# threshold in robust sigmas above the median.
WINDOW = 32
MIN_SAMPLES = 8
MAD_K = 8.0


def _finite(value: Any) -> bool:
    try:
        return math.isfinite(float(value))
    except (TypeError, ValueError):
        return False


class NumericGuard:
    """Per-process health sentinel. One instance per training process
    (module singleton below); all state is training-thread-local."""

    def __init__(self) -> None:
        self.policy = env.guard_policy()
        self.confirm_steps = env.guard_confirm_steps()
        self._window: list[float] = []  # healthy losses, newest last
        self._observations = 0
        self.healthy_streak = 0
        self.unhealthy_steps = 0
        self.rollbacks = 0
        self.skipped_batches = 0
        self.incidents_by_kind: dict[str, int] = {}
        self.last_incident: dict[str, Any] | None = None

    # -- detection ----------------------------------------------------

    def _spike_bound(self) -> float | None:
        """Upper loss bound before a sample counts as a spike, or None
        while the detector is still collecting its baseline."""
        if len(self._window) < MIN_SAMPLES:
            return None
        ordered = sorted(self._window)
        n = len(ordered)
        median = (
            ordered[n // 2]
            if n % 2
            else 0.5 * (ordered[n // 2 - 1] + ordered[n // 2])
        )
        devs = sorted(abs(x - median) for x in ordered)
        mad = (
            devs[n // 2]
            if n % 2
            else 0.5 * (devs[n // 2 - 1] + devs[n // 2])
        )
        # A flat-lined window (MAD 0) still needs a usable bound:
        # fall back to a small fraction of the median's magnitude.
        scale = _MAD_SIGMA * mad or 0.01 * abs(median) or 1e-8
        return median + MAD_K * scale

    def _classify(
        self, loss: Any, grad_sqr: Any, grad_var: Any
    ) -> str | None:
        if loss is not None and not _finite(loss):
            return KIND_NAN_LOSS
        for stat in (grad_sqr, grad_var):
            if stat is not None and not _finite(stat):
                return KIND_NAN_GRAD
        if loss is not None:
            bound = self._spike_bound()
            if bound is not None and float(loss) > bound:
                return KIND_LOSS_SPIKE
        return None

    # -- the per-step entry point -------------------------------------

    def observe(
        self,
        loss: Any,
        grad_sqr: Any = None,
        grad_var: Any = None,
        dataloader: Any = None,
        step: int | None = None,
        data_id: str | None = None,
        job_id: str | None = None,
    ) -> dict[str, Any]:
        """Grade one step's health and apply the configured policy.

        Returns a verdict dict ``{"healthy", "kind", "action",
        "restored"}``. ``dataloader`` (an ``AdaptiveDataLoader``)
        supplies the poisoned batch span and receives the skip range;
        ``data_id``/``step`` override the span-derived identity for
        callers without a loader (the chaos sim).
        """
        if self.policy == "off":
            return {
                "healthy": True, "kind": None,
                "action": "off", "restored": None,
            }
        self._observations += 1
        if step is None:
            step = self._observations
        # Deterministic chaos injection: a fault here SIMULATES the
        # corruption — the guard consumes it as a poisoned observation
        # instead of crashing the training loop.
        try:
            faults.maybe_fail("guard.corrupt_grad")
        except faults.InjectedFault:
            grad_sqr = float("nan")
        try:
            faults.maybe_fail("guard.loss_spike")
        except faults.InjectedFault:
            loss = (abs(float(loss)) + 1.0) * 1e6 if _finite(loss) else loss

        kind = self._classify(loss, grad_sqr, grad_var)
        if kind is None:
            self.healthy_streak += 1
            if loss is not None:
                self._window.append(float(loss))
                if len(self._window) > WINDOW:
                    del self._window[: -WINDOW]
            from adaptdl_tpu import checkpoint

            checkpoint.note_healthy_step()
            return {
                "healthy": True, "kind": None,
                "action": None, "restored": None,
            }
        return self._handle_incident(
            kind, step, dataloader, data_id, job_id
        )

    def _handle_incident(
        self,
        kind: str,
        step: int,
        dataloader: Any,
        data_id: str | None,
        job_id: str | None,
    ) -> dict[str, Any]:
        from adaptdl_tpu import checkpoint, metrics

        self.healthy_streak = 0
        self.unhealthy_steps += 1
        self.incidents_by_kind[kind] = (
            self.incidents_by_kind.get(kind, 0) + 1
        )
        # A corrupt step means every not-yet-confirmed checkpoint may
        # already carry the corruption — none of them may ever earn
        # the good marker.
        checkpoint.reset_health_confirmation()
        # Goodput hygiene: this step (and the profile sample the
        # dataloader is about to record for it) must not feed the
        # throughput EWMA or the perf fit.
        metrics.note_unhealthy_step()
        span = None
        if dataloader is not None:
            span = dataloader.current_batch_span()
        if data_id is None and span is not None:
            data_id = "{}:{}-{}".format(*span)
        action = self.policy
        restored = None
        if self.policy == "rollback":
            restored = self._rollback(dataloader, span)
            if restored is None:
                # No good checkpoint exists yet — degrade to skip so
                # the poison pill at least never re-feeds.
                action = "skip"
        if action in ("skip", "rollback") and span is not None:
            # After a rollback the restore just rewound the loader's
            # skip table, so the range must be (re-)recorded now.
            dataloader.add_skip_range(*span)
            self.skipped_batches += 1
        self.last_incident = {
            "kind": kind, "step": int(step),
            "data": data_id, "action": action,
        }
        LOG.warning(
            "numeric-health incident: kind=%s step=%d data=%s "
            "action=%s restored=%s",
            kind, step, data_id, action, restored,
        )
        post_incident(
            kind, step=step, data_id=data_id, action=action,
            job_id=job_id,
        )
        return {
            "healthy": False, "kind": kind,
            "action": action, "restored": restored,
        }

    def _rollback(self, dataloader: Any, span: Any) -> str | None:
        from adaptdl_tpu import checkpoint

        restored = checkpoint.rollback_to_good()
        if restored is None:
            LOG.warning(
                "guard rollback requested but no good-marked "
                "checkpoint exists; skipping the poisoned batch only"
            )
            return None
        self.rollbacks += 1
        # The rolled-back-to weights are known good; detection resumes
        # against a fresh spike baseline (the old window described a
        # trajectory that no longer exists).
        self._window.clear()
        self.healthy_streak = 0
        return restored


_guard: NumericGuard | None = None


def _get_guard() -> NumericGuard:
    global _guard
    if _guard is None:
        _guard = NumericGuard()
    return _guard


def observe_step(
    loss: Any,
    grad_sqr: Any = None,
    grad_var: Any = None,
    dataloader: Any = None,
    step: int | None = None,
    data_id: str | None = None,
    job_id: str | None = None,
) -> dict[str, Any]:
    """Module-level convenience over the process guard singleton."""
    return _get_guard().observe(
        loss, grad_sqr=grad_sqr, grad_var=grad_var,
        dataloader=dataloader, step=step, data_id=data_id,
        job_id=job_id,
    )


def guard_stats() -> dict[str, Any] | None:  # wire: produces=guard_stats
    """The guard's health summary, camelCase for the ``guardStats``
    sched-hints sub-payload (schema: the ``guard_stats`` wire family).
    None when the guard is disabled."""
    g = _get_guard()
    if g.policy == "off":
        return None
    from adaptdl_tpu import checkpoint, metrics

    return {
        "policy": g.policy,
        "incidents": int(sum(g.incidents_by_kind.values())),
        "incidentsByKind": dict(g.incidents_by_kind),
        "rollbacks": int(g.rollbacks),
        "skippedBatches": int(g.skipped_batches),
        "unhealthySteps": int(g.unhealthy_steps),
        "healthyStreak": int(g.healthy_streak),
        "lastGoodAge": checkpoint.last_good_age(),
        "rawGoodput": metrics.raw_goodput(),
    }


def post_incident(  # wire: produces=incident
    kind: str,
    step: int | None = None,
    data_id: str | None = None,
    action: str | None = None,
    rank: int | None = None,
    job_id: str | None = None,
    group: int | None = None,
) -> bool:
    """POST one incident to the supervisor; False on any failure.

    Best-effort like hint posting: recovery never blocks on the
    scheduler being reachable. The worker sends its rank — the
    supervisor resolves which slot it occupies from the job's
    current allocation.
    """
    from adaptdl_tpu import rpc

    url = env.supervisor_url()
    job_id = job_id if job_id is not None else env.job_id()
    if not url or not job_id:
        return False
    payload: dict[str, Any] = {"kind": kind}
    if step is not None:
        payload["step"] = int(step)
    if data_id is not None:
        payload["data"] = str(data_id)
    if action is not None:
        payload["action"] = action
    payload["rank"] = env.process_rank() if rank is None else rank
    try:
        response = rpc.default_client().post(
            f"{url}/incident/{job_id}",
            endpoint=f"incident/{job_id}",
            json=payload,
            # Same stale-incarnation guard as heartbeats/hints.
            params={
                "group": (
                    env.num_restarts() if group is None else group
                )
            },
            timeout=(2, 10),
            attempts=2,
            deadline=30.0,
        )
        response.raise_for_status()
        return True
    except Exception as exc:  # noqa: BLE001 - best effort by design
        LOG.warning("failed to post incident: %s", exc)
        return False


def _reset_state() -> None:
    """Drop the process guard singleton (test isolation)."""
    global _guard
    _guard = None
