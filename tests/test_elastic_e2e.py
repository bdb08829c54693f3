"""End-to-end elastic training slice.

The round-1 milestone test (SURVEY.md section 7 step 2): a full user
program — ElasticTrainer + AdaptiveDataLoader with
autoscale_batch_size + remaining_epochs_until + Accumulator — is
preempted mid-training, "restarted" with a different replica count,
resumes from the checkpoint, and converges. Replica rescale is
simulated in-process by rebuilding every component over a different
device mesh, exactly what a restarted process does.
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from adaptdl_tpu import (
    _signal,
    checkpoint,
    collective,
    epoch,
    metrics,
)
from adaptdl_tpu.accumulator import Accumulator
from adaptdl_tpu.data import AdaptiveDataLoader
from adaptdl_tpu.parallel import create_mesh
from adaptdl_tpu.scaling_rules import AdaScale
from adaptdl_tpu.trainer import ElasticTrainer

TRUE_W = np.array([1.0, -2.0, 3.0, 0.5], np.float32)
DATASET_SIZE = 512
EPOCHS = 10


@pytest.fixture(autouse=True)
def _clean():
    epoch._reset_state()
    metrics._reset_state()
    _signal.set_exit_flag(False)
    yield
    epoch._reset_state()
    metrics._reset_state()
    _signal.set_exit_flag(False)
    collective.teardown()


def _dataset():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(DATASET_SIZE, 4)).astype(np.float32)
    y = x @ TRUE_W + 0.05 * rng.normal(size=DATASET_SIZE).astype(
        np.float32
    )
    return {"x": x, "y": y}


def _loss_fn(params, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _incarnation(num_replicas, preempt_after_steps=None, autoscale=True):
    """One process incarnation of the user program.

    Returns (final_state, epochs_visited, losses) or raises SystemExit
    on simulated preemption.
    """
    checkpoint._reset_registry()
    epoch._reset_state()
    metrics._reset_state()
    mesh = create_mesh(devices=jax.devices()[:num_replicas])
    trainer = ElasticTrainer(
        loss_fn=_loss_fn,
        params={"w": jnp.zeros(4), "b": jnp.zeros(())},
        optimizer=optax.sgd(0.05),
        init_batch_size=32,
        scaling_rule=AdaScale(),
        mesh=mesh,
    )
    holder = {"state": trainer.init_state()}
    trainer.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
    )
    checkpoint.load_state(checkpoint._registry["elastic_trainer"])
    metrics.ensure_checkpoint_registered()
    checkpoint.load_state(checkpoint._registry["adaptdl_metrics"])

    dataset = _dataset()
    loader = AdaptiveDataLoader(dataset, batch_size=32, name="e2e-loader")
    if autoscale:
        loader.autoscale_batch_size(
            256, local_bsz_bounds=(8, 64), gradient_accumulation=True
        )
    accum = Accumulator(name="e2e-accum")

    epochs_visited = []
    losses = []
    steps = 0
    for e in epoch.remaining_epochs_until(EPOCHS):
        epochs_visited.append(e)
        for batch in loader:
            holder["state"], m = trainer.run_step(
                holder["state"], batch, loader
            )
            accum["loss_sum"] += float(m["loss"])
            accum["steps"] += 1
            steps += 1
            if (
                preempt_after_steps is not None
                and steps == preempt_after_steps
            ):
                _signal.set_exit_flag(True)
        with accum.synchronized():
            losses.append(accum["loss_sum"] / max(accum["steps"], 1))
        accum.reset()
    return holder["state"], epochs_visited, losses


def test_elastic_preempt_rescale_resume(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_NUM_NODES", "1")

    # Incarnation 0: 2 replicas, preempted after a few steps.
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "2")
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "0")
    with pytest.raises(SystemExit) as exc_info:
        _incarnation(2, preempt_after_steps=5)
    assert exc_info.value.code == 143
    assert checkpoint.latest_checkpoint_dir(str(tmp_path)) is not None

    # Incarnation 1: rescaled to 8 replicas, runs to completion.
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "8")
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "1")
    _signal.set_exit_flag(False)
    state, epochs_visited, losses = _incarnation(8)

    # Resumed at the interrupted epoch (0), finished all 6.
    assert epochs_visited[0] == 0
    assert epochs_visited[-1] == EPOCHS - 1
    # Converged to the true weights.
    w = np.asarray(state.params["w"])
    assert np.allclose(w, TRUE_W, atol=0.2), w
    assert losses[-1] < 0.1
    # Profiling survived and accumulated across both incarnations.
    assert metrics.current_state().max_profiled_replicas == 8


def test_same_layout_restart_reuses_the_calibration(
    tmp_path, monkeypatch
):
    """Preempted and restarted under the SAME layout and batch size,
    the successor does not time the compute-only program again: its
    journal holds ``step.calibrate_reused`` where the predecessor's
    holds ``step.calibrate``, and its first fit of the performance
    model is fed the predecessor's measurement."""
    from adaptdl_tpu import trace

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_NUM_NODES", "1")
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "2")
    fits = []
    fit_perf_params = metrics.fit_perf_params

    def recording_fit(nodes, replicas, bszs, accum_times, *args, **kw):
        fits.append((list(bszs), list(accum_times)))
        return fit_perf_params(
            nodes, replicas, bszs, accum_times, *args, **kw
        )

    monkeypatch.setattr(metrics, "fit_perf_params", recording_fit)

    def named(name):
        return [r for r in trace.snapshot_spans() if r["name"] == name]

    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "0")
    with pytest.raises(SystemExit):
        _incarnation(2, preempt_after_steps=5, autoscale=False)
    (measured,) = named("step.calibrate")
    assert not named("step.calibrate_reused")
    accum_time, count = metrics.accum_time_on_record(16)
    assert (accum_time, count) == (measured["attrs"]["best_s"], 1)

    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "1")
    _signal.set_exit_flag(False)
    trace._reset_state()
    del fits[:]
    with pytest.raises(SystemExit):
        _incarnation(2, preempt_after_steps=3, autoscale=False)
    metrics._fit_thread.join(timeout=120)
    (reused,) = named("step.calibrate_reused")
    assert reused["attrs"] == {
        "atomic_bsz": 16, "accum_time_s": accum_time, "observations": 1,
    }
    assert not named("step.calibrate")
    assert named("goodput.fit")
    assert fits[0] == ([16], [accum_time])
    # No sample was added for a measurement that was not made.
    assert metrics.accum_time_on_record(16) == (accum_time, 1)


def test_elastic_preempt_rescale_resume_zero3_blocks(
    tmp_path, monkeypatch
):
    """The same preempt -> rescale -> resume -> converge slice with
    the per-layer-FSDP storage mode: zero3_blocks rows save at dp=4,
    restore at dp=2, and training still converges — the elastic
    contract holds for the new flagship storage layout."""
    from adaptdl_tpu.parallel import zero3 as z3

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_NUM_NODES", "1")

    L, d, h = 2, 4, 8
    rng0 = np.random.default_rng(3)
    init_params = {
        "inp": jnp.asarray(np.eye(d, dtype=np.float32)),
        "blocks": {
            "w1": jnp.asarray(
                rng0.normal(size=(L, d, h)).astype(np.float32) * 0.1
            ),
            "w2": jnp.zeros((L, h, d), jnp.float32),
        },
        "out": jnp.asarray(np.eye(d, dtype=np.float32) * 0.1),
    }
    spec = z3.block_spec(init_params, "blocks")
    data = _dataset()
    # Targets for a d-dim regression: broadcast y over features.
    targets = np.stack([data["y"]] * d, axis=1)

    def z3b_loss(view, batch, rng):
        hid = batch["x"] @ view.other["inp"]

        def block_fn(p, hh):
            return hh + jnp.tanh(hh @ p["w1"]) @ p["w2"]

        hid = z3.scan_blocks(block_fn, view.blocks, hid, spec)
        return jnp.mean((hid @ view.other["out"] - batch["y_wide"]) ** 2)

    def incarnation(num_replicas, preempt_after_steps=None):
        checkpoint._reset_registry()
        epoch._reset_state()
        metrics._reset_state()
        mesh = create_mesh(devices=jax.devices()[:num_replicas])
        trainer = ElasticTrainer(
            loss_fn=z3b_loss,
            params=init_params,
            optimizer=optax.adam(2e-2),
            init_batch_size=32,
            mesh=mesh,
            zero3_blocks="blocks",
        )
        holder = {"state": trainer.init_state()}
        trainer.make_checkpoint_state(
            lambda: holder["state"],
            lambda s: holder.__setitem__("state", s),
        )
        checkpoint.load_state(
            checkpoint._registry["elastic_trainer"]
        )
        metrics.ensure_checkpoint_registered()
        checkpoint.load_state(
            checkpoint._registry["adaptdl_metrics"]
        )
        loader = AdaptiveDataLoader(
            {"x": data["x"], "y_wide": targets},
            batch_size=32,
            name="z3b-e2e-loader",
        )
        steps = 0
        last = None
        for e in epoch.remaining_epochs_until(6):
            for batch in loader:
                holder["state"], m = trainer.run_step(
                    holder["state"], batch, loader
                )
                last = float(m["loss"])
                steps += 1
                if (
                    preempt_after_steps is not None
                    and steps == preempt_after_steps
                ):
                    _signal.set_exit_flag(True)
        return holder["state"], trainer, last

    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "4")
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "0")
    with pytest.raises(SystemExit) as exc_info:
        incarnation(4, preempt_after_steps=5)
    assert exc_info.value.code == 143

    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "2")
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "1")
    _signal.set_exit_flag(False)
    state, trainer, last_loss = incarnation(2)
    assert int(state.step) > 5  # resumed past the preempted step
    assert last_loss < 0.1, last_loss  # converged after the rescale
    # Storage stayed rows-sharded through the whole run.
    assert set(state.params) == {"blocks", "other"}
    assert state.params["other"].shape[0] == 2


def test_live_retune_no_restart_matches_checkpoint_restart(
    tmp_path, monkeypatch
):
    """The live re-tune fast path: when the allocator changes only the
    per-replica batch configuration — not the device set — the job
    adopts it in-process. Must cost zero restarts, keep the dataloader
    position, and produce the IDENTICAL training trajectory to the
    checkpoint-restart path adopting the same configuration."""
    from adaptdl_tpu import sched_hints

    monkeypatch.setenv("ADAPTDL_NUM_NODES", "1")
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "4")
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "0")
    dataset = _dataset()
    new_config = {"atomicBsz": 16, "accumSteps": 1}

    # The allocator's published decision, faked at the client fetch
    # (the wire path — supervisor /config — is covered by the sched
    # services tests).
    remote = {"cfg": None}
    monkeypatch.setattr(
        sched_hints,
        "fetch_job_config",
        lambda job_id=None: (
            {"batchConfig": dict(remote["cfg"])}
            if remote["cfg"]
            else None
        ),
    )
    # Pin the LOCAL decision path to the initial split: this test is
    # about the re-tune mechanism, and a mid-run goodput fit would
    # move the batch size on wall-clock timing rather than on the
    # faked allocator decision.
    monkeypatch.setattr(metrics, "get_goodput_fn", lambda: None)

    def build(name):
        checkpoint._reset_registry()
        epoch._reset_state()
        metrics._reset_state()
        mesh = create_mesh(devices=jax.devices()[:4])
        trainer = ElasticTrainer(
            loss_fn=_loss_fn,
            params={"w": jnp.zeros(4), "b": jnp.zeros(())},
            optimizer=optax.sgd(0.05),
            init_batch_size=32,
            scaling_rule=AdaScale(),
            mesh=mesh,
        )
        holder = {"state": trainer.init_state()}
        ck = trainer.make_checkpoint_state(
            lambda: holder["state"],
            lambda s: holder.__setitem__("state", s),
        )
        checkpoint.load_state(ck)
        loader = AdaptiveDataLoader(dataset, batch_size=32, name=name)
        loader.autoscale_batch_size(
            256, local_bsz_bounds=(8, 64), gradient_accumulation=True
        )
        loader._reoptimize_every = 1
        return trainer, holder, loader

    def run_arm(name, live: bool):
        """Steps 1-5 at the initial config; the new config takes
        effect from step 6 — via in-process re-tune (live=True) or via
        preempt -> checkpoint-restart (live=False). Returns (losses
        from step 6 on, final w, final step count)."""
        remote["cfg"] = None
        _signal.set_exit_flag(False)
        trainer, holder, loader = build(name)
        losses, steps = [], 0

        def loop():
            nonlocal steps
            for _ in epoch.remaining_epochs_until(1):
                for batch in loader:
                    holder["state"], m = trainer.run_step(
                        holder["state"], batch, loader
                    )
                    steps += 1
                    if steps > 5:
                        losses.append(float(m["loss"]))
                    if live and steps == 5:
                        remote["cfg"] = new_config
                    if not live and steps == 4:
                        # Graceful preemption: the async exit-flag
                        # agreement lags one step, so a flag raised
                        # during step 4 exits after step 5 — aligning
                        # both arms' switch point at step 6.
                        _signal.set_exit_flag(True)

        if live:
            loop()
            assert metrics.current_state().num_retunes >= 1
            # Dataloader position continued mid-epoch (never reset).
            return losses, np.asarray(holder["state"].params["w"])
        with pytest.raises(SystemExit) as exc_info:
            loop()
        assert exc_info.value.code == 143
        position = (loader.sampler.epoch, loader.sampler.index)
        # Restarted incarnation: same replica count, allocator's new
        # batch config published; resumes mid-epoch.
        monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "1")
        _signal.set_exit_flag(False)
        remote["cfg"] = new_config
        trainer, holder, loader = build(name)
        assert (loader.sampler.epoch, loader.sampler.index) == position
        for _ in epoch.remaining_epochs_until(1):
            for batch in loader:
                holder["state"], m = trainer.run_step(
                    holder["state"], batch, loader
                )
                losses.append(float(m["loss"]))
        monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "0")
        return losses, np.asarray(holder["state"].params["w"])

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path / "live"))
    losses_live, w_live = run_arm("retune-live", live=True)
    monkeypatch.setenv(
        "ADAPTDL_CHECKPOINT_PATH", str(tmp_path / "restart")
    )
    losses_restart, w_restart = run_arm("retune-restart", live=False)

    # The re-tune actually changed the schedule (steps after 5 use the
    # new config) and both paths saw the same number of steps.
    assert losses_live, "no steps ran after the re-tune"
    assert len(losses_live) == len(losses_restart)
    # Identical trajectory: same losses, same final weights.
    np.testing.assert_allclose(
        losses_live, losses_restart, rtol=1e-6, atol=1e-7
    )
    np.testing.assert_allclose(w_live, w_restart, rtol=1e-6, atol=1e-7)


def test_fixed_batch_size_run(tmp_path, monkeypatch):
    """No autoscaling: plain elastic DP training end-to-end."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "4")
    checkpoint._reset_registry()
    mesh = create_mesh(devices=jax.devices()[:4])
    trainer = ElasticTrainer(
        loss_fn=_loss_fn,
        params={"w": jnp.zeros(4), "b": jnp.zeros(())},
        optimizer=optax.sgd(0.05),
        init_batch_size=32,
        mesh=mesh,
    )
    state = trainer.init_state()
    loader = AdaptiveDataLoader(
        _dataset(), batch_size=32, name="e2e-fixed"
    )
    for e in epoch.remaining_epochs_until(3):
        for batch in loader:
            state, m = trainer.run_step(state, batch, loader)
    assert float(m["loss"]) < 0.1
