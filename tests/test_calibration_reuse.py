"""A restarted job reuses its predecessor's calibration.

``metrics.ensure_checkpoint_registered()`` restores the metrics state
it registers, and ``ElasticTrainer.run_step`` asks the restored
profile before it builds and times the compute-only program: under the
same layout and batch size it journals ``step.calibrate_reused`` and
goes on; under any other key, and in a fresh job, it calibrates as
before. With several processes the answer is rank 0's.
"""

from __future__ import annotations

import types

import numpy as np
import pytest

from adaptdl_tpu import checkpoint, collective, metrics, trace
from adaptdl_tpu.goodput import GradParams, PerfParams


@pytest.fixture(autouse=True)
def _clean_metrics():
    metrics._reset_state()
    yield
    metrics._reset_state()
    collective.teardown()


def _named(name):
    return [r for r in trace.snapshot_spans() if r["name"] == name]


# ---- (a) the state is restored where it is registered ----------------


def _learn_something():
    """What a predecessor knows when it saves: a calibrated and
    profiled configuration, a fit, the gradient statistics."""
    metrics.set_batch_size_config(32, 256, (8, 64), True)
    metrics.profile_accum_time(16, 0.25)
    metrics.profile_accum_time(16, 0.35)
    metrics.profile_step(16, 1, 0.75)
    if metrics._fit_thread is not None:
        metrics._fit_thread.join(timeout=120)
    metrics.fit_and_report_now()
    metrics.update_grad_params(0.5, 2.0)
    metrics.update_progress(12.5)
    assert isinstance(metrics.current_state().perf_params, PerfParams)
    return metrics._profile_key(16)


def _new_incarnation():
    checkpoint._reset_registry()
    metrics._reset_state()
    assert not metrics.current_state().profile


def test_registering_restores_profile_and_fitted_params(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    metrics.ensure_checkpoint_registered()
    key = _learn_something()
    perf = metrics.current_state().perf_params
    checkpoint.save_all_states()
    _new_incarnation()
    metrics.ensure_checkpoint_registered()  # alone: no load_state by hand
    state = metrics.current_state()
    entry = state.profile[key]
    assert (entry.accum_count, entry.optim_count) == (2, 1)
    assert entry.accum_time_sum == pytest.approx(0.6)
    assert state.perf_params == perf
    assert state.grad_params == GradParams(sqr=0.5, var=2.0)
    assert state.progress == 12.5
    assert metrics.get_goodput_fn() is not None
    assert metrics.accum_time_on_record(16) == (pytest.approx(0.3), 2)
    assert len(_named("ckpt.restore")) == 1


def test_registering_twice_neither_fails_nor_loads_twice(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    metrics.ensure_checkpoint_registered()
    key = _learn_something()
    checkpoint.save_all_states()
    _new_incarnation()
    metrics.ensure_checkpoint_registered()
    # What this incarnation learns after the restore is not undone by
    # a second registration.
    metrics.profile_accum_time(16, 0.3)
    metrics.ensure_checkpoint_registered()
    assert metrics.current_state().profile[key].accum_count == 3
    assert len(_named("ckpt.restore")) == 1
    # The e2e tests' explicit load after registering stays harmless.
    assert checkpoint.load_state(checkpoint._registry["adaptdl_metrics"])
    assert metrics.current_state().profile[key].accum_count == 2


def test_registering_without_a_checkpoint_only_registers(
    tmp_path, monkeypatch
):
    monkeypatch.delenv("ADAPTDL_CHECKPOINT_PATH", raising=False)
    metrics.profile_accum_time(16, 0.25)
    metrics.ensure_checkpoint_registered()
    assert "adaptdl_metrics" in checkpoint._registry
    assert metrics.accum_time_on_record(16) == (0.25, 1)
    # A fresh job WITH a checkpoint path and nothing saved under it.
    _new_incarnation()
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    metrics.ensure_checkpoint_registered()
    assert "adaptdl_metrics" in checkpoint._registry
    assert not metrics.current_state().profile
    assert not _named("ckpt.restore")


# ---- (b), (c) run_step asks the profile before it calibrates ---------


def _trainer_and_batch():
    from tests.test_compile_cache import _linear_trainer

    trainer = _linear_trainer()[0]
    rng = np.random.default_rng(0)
    host_batch = {
        "x": rng.normal(size=(8, 4)).astype(np.float32),
        "y": rng.normal(size=(8,)).astype(np.float32),
    }
    return trainer, host_batch


_LOADER = types.SimpleNamespace(current_atomic_bsz=8, current_accum_steps=0)


def test_run_step_reuses_the_calibration_on_record(monkeypatch):
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    trainer, host_batch = _trainer_and_batch()
    state = trainer.init_state()
    metrics.profile_accum_time(8, 0.125)
    metrics.profile_accum_time(8, 0.25)
    key = metrics._profile_key(8)

    def never(*_a, **_kw):
        raise AssertionError("the compute-only program was built")

    monkeypatch.setattr(trainer, "_build_compute_only", never)
    # One process: the decision touches no collective.
    monkeypatch.setattr(collective, "broadcast", never)
    state, m = trainer.run_step(state, host_batch, _LOADER)
    assert np.isfinite(float(m["loss"])) and int(state.step) == 1
    (reused,) = _named("step.calibrate_reused")
    assert reused["kind"] == "event"
    assert reused["attrs"] == {
        "atomic_bsz": 8, "accum_time_s": 0.1875, "observations": 2,
    }
    assert not _named("step.calibrate")
    entry = metrics.current_state().profile[key]
    assert (entry.accum_count, entry.accum_time_sum) == (2, 0.375)
    # Asked once per batch size, like the calibration it stands for.
    assert 8 in trainer._calibrated
    trainer.run_step(state, host_batch, _LOADER)
    assert len(_named("step.calibrate_reused")) == 1


def _record_other_batch_size(monkeypatch):
    metrics.profile_accum_time(16, 0.5)


def _record_other_replica_count(monkeypatch):
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "2")
    metrics.profile_accum_time(8, 0.5)
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")


def _record_only_step_times(monkeypatch):
    # The key exists, but no calibration ever fed it.
    metrics.profile_step(8, 0, 0.5)


@pytest.mark.parametrize(
    "predecessor",
    [
        _record_other_batch_size,
        _record_other_replica_count,
        _record_only_step_times,
        lambda monkeypatch: None,
    ],
    ids=["other_atomic_bsz", "other_replicas", "no_accum_sample", "fresh"],
)
def test_run_step_calibrates_what_is_not_on_record(
    monkeypatch, predecessor
):
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    trainer, host_batch = _trainer_and_batch()
    state = trainer.init_state()
    predecessor(monkeypatch)
    before = len(metrics.current_state().profile)
    built = []
    build = trainer._build_compute_only
    monkeypatch.setattr(
        trainer, "_build_compute_only",
        lambda bsz: built.append(bsz) or build(bsz),
    )
    state, _ = trainer.run_step(state, host_batch, _LOADER)
    assert built == [8]
    (span,) = _named("step.calibrate")
    assert span["attrs"]["atomic_bsz"] == 8
    assert not _named("step.calibrate_reused")
    measured = metrics.accum_time_on_record(8)
    assert measured == (span["attrs"]["best_s"], 1)
    # The predecessor's other entries are kept beside the new one.
    new = 0 if predecessor is _record_only_step_times else 1
    assert len(metrics.current_state().profile) == before + new
    trainer.run_step(state, host_batch, _LOADER)
    assert built == [8]


# ---- (e) every process takes rank 0's answer --------------------------


@pytest.mark.parametrize(
    "holder_rank", [0, 1], ids=["rank0_has", "rank0_empty"]
)
def test_every_process_follows_rank_zero(
    elastic_multiprocessing, holder_rank
):
    """Two forked processes, only ``holder_rank`` has the entry. The
    compute-only program is SPMD: a process that skipped it while the
    other ran it would hang the job, so both do what rank 0 found."""

    def body():
        from adaptdl_tpu import env
        from adaptdl_tpu.trainer import _calibration_on_record

        metrics._reset_state()
        trace._reset_state()
        collective.initialize()
        try:
            if env.process_rank() == holder_rank:
                metrics.profile_accum_time(8, 0.5)
            assert (metrics.accum_time_on_record(8) is not None) == (
                env.process_rank() == holder_rank
            )
            reuse = _calibration_on_record(8)
            assert reuse == (holder_rank == 0), (env.process_rank(), reuse)
            events = _named("step.calibrate_reused")
            assert len(events) == int(reuse)
            if reuse:
                assert events[0]["attrs"]["accum_time_s"] == 0.5
            # Nobody's own profile was touched by the decision.
            assert (metrics.accum_time_on_record(8) is not None) == (
                env.process_rank() == holder_rank
            )
        finally:
            collective.teardown()
        return 0

    elastic_multiprocessing(body, num_replicas=2)
