"""Local elastic runner integration: the whole loop on one machine.

Job posts hints -> allocator re-optimizes -> runner SIGTERMs ->
job checkpoints, exits 143 -> runner relaunches at the new replica
count -> job resumes and finishes. This is the one-machine analog of
the reference's controller-driven rescale (reference:
sched/adaptdl_sched/controller.py lifecycle; test strategy mirrors
tests/testworkload.sh soak jobs in miniature).
"""

import os
import textwrap

import pytest

from adaptdl_tpu.sched.local_runner import LocalElasticRunner

TRAIN_SCRIPT = textwrap.dedent(
    """
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    from adaptdl_tpu import _signal, checkpoint, env, epoch, metrics
    from adaptdl_tpu.data import AdaptiveDataLoader
    from adaptdl_tpu.parallel import create_mesh
    from adaptdl_tpu.scaling_rules import AdaScale
    from adaptdl_tpu.trainer import ElasticTrainer

    _signal.install_handlers()
    TRUE_W = np.array([1.0, -2.0, 3.0, 0.5], np.float32)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(512, 4)).astype(np.float32)
    y = x @ TRUE_W + 0.05 * rng.normal(size=512).astype(np.float32)

    mesh = create_mesh(devices=jax.devices()[: env.num_replicas()])
    trainer = ElasticTrainer(
        loss_fn=lambda p, b, r: jnp.mean(
            (b["x"] @ p["w"] + p["b"] - b["y"]) ** 2
        ),
        params={"w": jnp.zeros(4), "b": jnp.zeros(())},
        optimizer=optax.sgd(0.05),
        init_batch_size=32,
        scaling_rule=AdaScale(),
        mesh=mesh,
    )
    trainer.metrics_every = 2
    holder = {"state": trainer.init_state()}
    ck = trainer.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
    )
    checkpoint.load_state(ck)
    metrics.ensure_checkpoint_registered()
    loader = AdaptiveDataLoader({"x": x, "y": y}, batch_size=32,
                                name="runner-loader")
    loader.autoscale_batch_size(256, local_bsz_bounds=(8, 64),
                                gradient_accumulation=True)
    import time as _time

    for e in epoch.remaining_epochs_until(40):
        for batch in loader:
            holder["state"], m = trainer.run_step(
                holder["state"], batch, loader
            )
        # Stand-in for a real epoch's wall-clock so the allocator gets
        # a chance to rescale the job mid-flight.
        _time.sleep(0.25)
    final_w = np.asarray(holder["state"].params["w"])
    assert np.allclose(final_w, TRUE_W, atol=0.25), final_w
    print("TRAINED", int(holder["state"].step), env.num_replicas())
    """
)


def test_local_elastic_runner_end_to_end(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(TRAIN_SCRIPT)
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    runner = LocalElasticRunner(
        str(script),
        num_chips=8,
        checkpoint_dir=str(ckpt),
        job_name="test/elastic-local",
        allocator_interval=1.0,
        extra_env={
            "PYTHONPATH": os.environ.get("PYTHONPATH", "")
            + os.pathsep
            + os.getcwd(),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "ADAPTDL_FIT_INTERVAL": "1",
        },
    )
    code = runner.run()
    assert code == 0
    record = runner.state.get_job("test/elastic-local")
    assert record.status == "Succeeded"
    assert record.hints is not None, "job posted sched hints"
    assert runner.restarts >= 1, "allocator rescaled the job at least once"
    # (The *final* allocation size is a policy outcome of this box's
    # noisy timings — growing and later shrinking back to 1 replica is
    # legitimate; the rescale itself is the behavior under test.)


LAUNCHERS_OFF_JAX = textwrap.dedent(
    """
    import subprocess, sys

    launched = []
    real_popen = subprocess.Popen

    class RecordingPopen(real_popen):
        def __init__(self, args, *a, **kw):
            # What this parent looked like at the moment it started a
            # child: a chip serves one process, so it must not have
            # touched jax (not imported is the strongest form).
            launched.append((list(args), "jax" in sys.modules))
            super().__init__(args, *a, **kw)

    subprocess.Popen = RecordingPopen

    from adaptdl_tpu import cli
    from adaptdl_tpu.sched import local_runner

    script, ckpt = sys.argv[1], sys.argv[2]
    # No --chips: the count comes from a short-lived child.
    sys.argv = ["local_runner", script, "--checkpoint-dir", ckpt + "/a"]
    assert local_runner.main() == 0
    assert cli.main(
        ["submit", script, "--checkpoint-dir", ckpt + "/b", "--chips", "1"]
    ) == 0
    workers = [cmd for cmd, _ in launched if cmd[-1] == script]
    counters = [cmd for cmd, _ in launched if "jax.devices()" in cmd[-1]]
    assert len(workers) == 2 and len(counters) == 1, launched
    assert not any(had_jax for _, had_jax in launched), launched
    assert "jax" not in sys.modules
    print("LAUNCHERS_OFF_JAX_OK")
    """
)


def test_launchers_reach_popen_without_touching_jax(tmp_path):
    """``python -m adaptdl_tpu.sched.local_runner`` and ``adaptdl-tpu
    submit`` start the worker (and, without ``--chips``, the chip
    counter before it — once here, it imports jax for seconds) from a
    parent that never imported jax — on a
    directly attached chip a parent holding the backend would leave
    its worker none. Runs in a subprocess: this pytest process has
    long since initialised a backend itself."""
    import subprocess
    import sys

    script = tmp_path / "worker.py"
    script.write_text("print('WORKER_RAN', flush=True)\n")
    driver = tmp_path / "driver.py"
    driver.write_text(LAUNCHERS_OFF_JAX)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [repo_root, env.get("PYTHONPATH")])
    )
    proc = subprocess.run(
        [sys.executable, str(driver), str(script), str(tmp_path)],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "LAUNCHERS_OFF_JAX_OK" in proc.stdout
    assert proc.stdout.count("WORKER_RAN") == 2
