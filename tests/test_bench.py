"""``python bench.py`` prints one parseable JSON line with the headline
schema and the platform exactly as jax reports it. Device metrics come
from a chip: without ``--quick`` the bench refuses any platform but a
TPU (non-zero exit, no JSON), and ``--quick`` is the explicit tiny
preset these tests run on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_quick_emits_headline_json():
    env = dict(os.environ)
    env.update(
        {
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "BENCH_BUDGET_SECONDS": "300",
        }
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--quick"],
        capture_output=True,
        text=True,
        timeout=540,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    json_lines = [
        line
        for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]
    assert json_lines, proc.stdout[-2000:]
    result = json.loads(json_lines[-1])
    for key in ("metric", "value", "unit", "vs_baseline", "platform"):
        assert key in result, (key, result)
    assert result["metric"] == (
        "elastic_goodput_retention_resnet18_cifar"
    )
    assert result["value"] > 0
    assert result["platform"] == "cpu"
    assert result["device_count"] == 8
    assert "failed_phases" not in result, result["failed_phases"]
    # The round-5 depth keys ride the same line when budget allows.
    assert "value_ci" in result
    assert "mem_z3b_temp_vs_lite" in result


def test_bench_refuses_to_measure_off_chip(capsys):
    """No chip, no ``--quick``: a non-zero exit and no result line —
    never a CPU measurement under a device metric's name."""
    import bench as bench_mod

    assert bench_mod.main(quick=False) == 2
    out, err = capsys.readouterr()
    assert "refusing to measure" in err
    assert "{" not in out


def test_failed_phase_is_named_and_fatal():
    """A phase that raises is recorded, not swallowed: ``_run_phase``
    returns None, names the phase in ``_FAILED_PHASES`` (which ``main``
    puts on the JSON line and turns into a non-zero exit)."""
    import bench as bench_mod

    def boom():
        raise RuntimeError("phase exploded")

    before = list(bench_mod._FAILED_PHASES)
    try:
        assert bench_mod._run_phase("ok", -1e9, lambda: 7) == 7
        assert bench_mod._run_phase("skipped", 1e9, boom) is None
        assert bench_mod._FAILED_PHASES == before
        assert bench_mod._run_phase("boom", -1e9, boom) is None
        assert bench_mod._FAILED_PHASES == before + ["boom"]
    finally:
        bench_mod._FAILED_PHASES[:] = before


def test_rescale_breakdown_sums_consistently(
    tmp_path, monkeypatch, compile_cache_config_restored
):
    """Fast smoke test of the rescale instrumentation: the breakdown
    (snapshot_s / write_s / handoff_s / restore_s / first_step_s /
    storage_p50_s) is emitted and internally consistent — the planned
    path's serial components are disjoint sub-segments of the
    measured total, the storage-path reference sums its own segments,
    and the overlapped write never reports negative time."""
    import jax
    import jax.numpy as jnp
    import optax

    import bench as bench_mod
    from adaptdl_tpu import metrics
    from adaptdl_tpu.trainer import ElasticTrainer

    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    # The bench names its compile cache through the existing knob (the
    # checkout's fixed directory unless one is already named): name a
    # throw-away one here so a test session leaves the checkout clean.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("ADAPTDL_COMPILE_CACHE", str(tmp_path / "cc"))
    metrics._reset_state()
    rng = np.random.default_rng(0)
    dataset = {
        "x": rng.normal(size=(64, 4)).astype(np.float32),
        "label": rng.normal(size=(64,)).astype(np.float32),
    }

    def loss_fn(params, batch, _rng):
        return jnp.mean((batch["x"] @ params["w"] - batch["label"]) ** 2)

    def make_trainer():
        from adaptdl_tpu.parallel import create_mesh

        return ElasticTrainer(
            loss_fn=loss_fn,
            params={"w": jnp.zeros(4)},
            optimizer=optax.sgd(0.1),
            init_batch_size=8,
            mesh=create_mesh(devices=jax.devices()[:1]),
        )

    p50, breakdown, trace_summary = bench_mod._bench_rescale_latency(
        make_trainer, dataset, 8, trials=1
    )
    assert p50 > 0
    for key in (
        "snapshot_s", "write_s", "handoff_s", "restore_s",
        "first_step_s", "storage_p50_s",
    ):
        assert key in breakdown, breakdown
        assert breakdown[key] >= 0, breakdown
    # snapshot/handoff/first-step are disjoint segments of the timed
    # planned-path window (the durable delta write overlaps other
    # work), so their sum bounds the total from below.
    serial = (
        breakdown["snapshot_s"]
        + breakdown["handoff_s"]
        + breakdown["first_step_s"]
    )
    assert serial <= p50 + 1e-6, (serial, p50, breakdown)
    # The storage-path reference sums its own disjoint segments.
    assert (
        breakdown["snapshot_s"] + breakdown["restore_s"]
        <= breakdown["storage_p50_s"] + 1e-6
    ), breakdown
    # The overlapped durable write was a DELTA against the
    # steady-state full snapshot, and its ratio was measured. For
    # this 4-float model every leaf changes each step, so the ratio
    # sits near 1 (the chunk-table overhead can push it slightly
    # over); the point here is that it is measured and sane.
    assert 0 < breakdown.get("delta_ratio", 1.0) < 2.0, breakdown
    # The graftscope view of the same trials rides alongside: the
    # instrumented pipeline recorded snapshot/write/restore spans AND
    # the planned path's peer fetch, and the two instruments agree on
    # the snapshot phase to within the span's own overhead.
    phases = trace_summary["phases"]
    assert trace_summary["span_count"] > 0
    for name in (
        "ckpt.snapshot", "ckpt.write", "ckpt.restore", "handoff.fetch",
    ):
        assert name in phases, phases
    assert phases["ckpt.snapshot"] == pytest.approx(
        breakdown["snapshot_s"], abs=0.05
    )
    # One fixed cache directory for the whole run — not a mkdtemp that
    # is deleted (and so never hit) — and it was written to.
    import jax

    cache_dir = tmp_path / "cc" / ".jax_compile_cache"
    assert jax.config.jax_compilation_cache_dir == str(cache_dir)
    assert any(cache_dir.iterdir())
