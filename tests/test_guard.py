"""Unit coverage for the numeric-health guard's detector and policy
ladder (guard.py). The end-to-end rollback/blame arcs live in
tests/test_chaos_guard.py (`make guardgate`); this file pins the
detection math and the cheap policy behaviors the chaos suite doesn't
isolate."""

from __future__ import annotations

import pytest

from adaptdl_tpu import guard


@pytest.fixture(autouse=True)
def _fresh_guard(monkeypatch):
    # No supervisor in play: post_incident must degrade to a no-op.
    monkeypatch.delenv("ADAPTDL_SUPERVISOR_URL", raising=False)
    monkeypatch.delenv("ADAPTDL_JOB_ID", raising=False)
    guard._reset_state()
    yield
    guard._reset_state()


class _Loader:
    """Minimal AdaptiveDataLoader face: span out, skip ranges in."""

    def __init__(self):
        self.span = (0, 8, 16)
        self.skips = []

    def current_batch_span(self):
        return self.span

    def add_skip_range(self, epoch, start, end):
        self.skips.append((epoch, start, end))


def test_policy_off_observes_nothing(monkeypatch):
    monkeypatch.setenv("ADAPTDL_GUARD_POLICY", "off")
    verdict = guard.observe_step(float("nan"))
    assert verdict == {
        "healthy": True, "kind": None,
        "action": "off", "restored": None,
    }
    assert guard.guard_stats() is None


def test_nan_classification_precedence(monkeypatch):
    monkeypatch.setenv("ADAPTDL_GUARD_POLICY", "warn")
    g = guard.NumericGuard()
    assert not g.observe(float("inf"))["healthy"]
    assert g.observe(1.0, grad_sqr=float("nan"))["kind"] == "nan_grad"
    assert g.observe(float("nan"), grad_sqr=float("nan"))[
        "kind"
    ] == "nan_loss", "a NaN loss outranks the grad statistic"
    assert g.observe(1.0, grad_var=float("inf"))["kind"] == "nan_grad"
    assert g.observe(1.0, grad_sqr=1.0, grad_var=1.0)["healthy"]


def test_spike_detector_arms_after_min_samples(monkeypatch):
    monkeypatch.setenv("ADAPTDL_GUARD_POLICY", "warn")
    monkeypatch.setattr(guard, "MIN_SAMPLES", 4)
    g = guard.NumericGuard()
    # Below min_samples even an absurd loss passes (no baseline yet).
    assert g.observe(1.0)["healthy"]
    assert g.observe(1e9)["healthy"]
    g = guard.NumericGuard()
    for loss in (1.0, 1.1, 0.9, 1.05):
        assert g.observe(loss)["healthy"]
    verdict = g.observe(1e6)
    assert verdict["kind"] == "loss_spike"
    # Only the upper side fires: a sudden improvement is not a fault.
    assert g.observe(1e-6)["healthy"]
    # The spike never entered the window: the baseline held.
    assert g.observe(1.02)["healthy"]


def test_flat_window_uses_relative_fallback_bound(monkeypatch):
    monkeypatch.setenv("ADAPTDL_GUARD_POLICY", "warn")
    monkeypatch.setattr(guard, "MIN_SAMPLES", 4)
    g = guard.NumericGuard()
    for _ in range(4):
        assert g.observe(2.0)["healthy"]
    # MAD is 0; the bound falls back to median + k * 1% of |median|.
    assert g.observe(2.1)["healthy"]
    assert g.observe(2.2)["kind"] == "loss_spike"


def test_skip_policy_records_range_without_rollback(monkeypatch):
    monkeypatch.setenv("ADAPTDL_GUARD_POLICY", "skip")
    loader = _Loader()
    verdict = guard.observe_step(
        float("nan"), dataloader=loader
    )
    assert verdict["action"] == "skip"
    assert verdict["restored"] is None
    assert loader.skips == [(0, 8, 16)]
    stats = guard.guard_stats()
    assert stats["rollbacks"] == 0
    assert stats["skippedBatches"] == 1
    assert stats["incidentsByKind"] == {"nan_loss": 1}


def test_warn_policy_counts_but_never_touches_the_loader(monkeypatch):
    monkeypatch.setenv("ADAPTDL_GUARD_POLICY", "warn")
    loader = _Loader()
    verdict = guard.observe_step(float("nan"), dataloader=loader)
    assert verdict["action"] == "warn"
    assert loader.skips == []
    assert guard.guard_stats()["unhealthySteps"] == 1


def test_rollback_degrades_to_skip_without_good_checkpoint(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("ADAPTDL_GUARD_POLICY", "rollback")
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    loader = _Loader()
    verdict = guard.observe_step(float("nan"), dataloader=loader)
    assert verdict["action"] == "skip"
    assert verdict["restored"] is None
    assert loader.skips == [(0, 8, 16)]


def test_healthy_streak_resets_on_incident(monkeypatch):
    monkeypatch.setenv("ADAPTDL_GUARD_POLICY", "warn")
    g = guard.NumericGuard()
    for _ in range(3):
        g.observe(1.0)
    assert g.healthy_streak == 3
    g.observe(float("nan"))
    assert g.healthy_streak == 0
