"""run.py as the driver calls it, where it must refuse; and the
job drivers end to end at a tiny size on the CPU (the rehearsals)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
RUN = [sys.executable, os.path.join("benchmark", "run.py")]


def _clean_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return env


def test_no_tpu_exits_nonzero_and_prints_no_metric():
    """Here JAX is held to the CPU: the command must fail, and nothing
    on stdout may carry a number under a device metric's name."""
    proc = subprocess.run(
        RUN + ["--workload", "gpt2-124m-steady", "--seed", "1",
               "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_clean_env(), capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "platform is 'cpu'" in proc.stderr


def test_unknown_workload_exits_nonzero():
    proc = subprocess.run(
        RUN + ["--workload", "nope", "--seconds", "1"],
        cwd=ROOT, env=_clean_env(), capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_benchmark_alone_has_nothing_to_measure(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files
    under ``paths`` there is no program: non-zero, no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    env = _clean_env()
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        RUN + ["--workload", "gpt2-124m-steady", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize(
    "cell, trace",
    [
        ("gpt2-124m-steady", 0),
        ("gpt2-124m-steady", 1),
        ("gpt2-124m-rescale", 0),
        ("gpt2-124m-dp4", 0),
    ],
)
def test_rehearsal_on_cpu(cell, trace, tmp_path, monkeypatch):
    """Control flow of each job driver: correct is true, the line has
    the contract's keys, the metrics are the cell's; no number here is
    a device metric (platform "cpu" is on the line)."""
    import rehearse

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    line = rehearse.rehearse(cell, seconds=2.0, trace=trace)
    assert set(line) <= {
        "correct", "attempted", "failed", "metrics", "device", "breakdown"
    }
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    declared = manifest.load_cell(cell)
    group = declared.per_layer if trace else declared.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in group}
    if not trace:
        assert set(line["metrics"]) == {m["name"] for m in group}
    json.dumps(line)
    assert not list(tmp_path.glob("adaptdl-bench-*")), "work dir left"
