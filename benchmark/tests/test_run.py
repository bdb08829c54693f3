"""run.py as the driver calls it, where it must refuse; and the
job drivers end to end at a tiny size on the CPU (the rehearsals)."""

import json
import os
import re
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
        "correct", "attempted", "failed", "metrics", "device", "breakdown",
        "compared",
    }
    assert list(line)[-1] == "compared"
    assert line["compared"]["checks"]["reference_agrees"] is True
    assert line["compared"]["reference"]["rel_diff"] <= (
        line["compared"]["reference"]["rtol"]
    )
    if "rescale" in cell:
        resume = line["compared"]["resume"]
        assert resume["restored"] == resume["saved"]
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


def _said_by(err: str, what: str) -> list[str]:
    """Pids of the workers whose stderr carries ``what``."""
    return re.findall(rf"\[bench-worker (\d+)\] {what}", err)


@pytest.mark.parametrize(
    "where, dtype, correct",
    [
        (None, "float32", True),
        ("successor", "float32", True),
        ("predecessor_fresh", "float32", True),
        # On the CPU a bfloat16 model rounds its logits
        # (test_references.py): the head comparison fails.
        ("predecessor_fresh", "bfloat16", False),
    ],
)
def test_kill_resume_holds_the_reference_where_the_job_says(
    where, dtype, correct, tmp_path, monkeypatch, capfd
):
    """``job.reference_check``: absent behaves as ``"successor"`` (the
    successor checks its RESTORED weights after its first step);
    ``"predecessor_fresh"`` checks in the predecessor before it
    trains, the result arrives in the successor's record, and a
    failing predecessor check makes the run not ``correct``."""
    import argparse

    import rehearse
    import timeline_run

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cell = manifest.load_cell("gpt2-124m-rescale")
    assert "reference_check" not in cell.workload["job"]
    rehearse.shrink(cell)
    cell.sizes["compute_dtype"] = dtype
    if where is not None:
        cell.workload["job"]["reference_check"] = where
    args = argparse.Namespace(
        workload=cell.name, seed=3, seconds=2.0, trace=0
    )
    out, line = timeline_run.run_job(cell, args, str(tmp_path))
    err = capfd.readouterr().err
    checked = _said_by(err, "reference check")
    predecessor = _said_by(err, "ready for SIGTERM")
    successor = _said_by(err, "first loss")
    assert len(checked) == len(predecessor) == len(successor) == 1
    assert predecessor != successor
    assert checked == (
        predecessor if where == "predecessor_fresh" else successor
    )
    reference = out["done"]["record"]["reference"]
    assert reference["ok"] is correct
    assert out["done"]["checks"]["reference_agrees"] is correct
    assert line["correct"] is correct, (out["done"]["checks"], out["checks"])
    # What a restart has to hold is held either way.
    for check in ("step_restored", "loader_position_restored",
                  "batch_config_restored", "progress_restored",
                  "loss_continues"):
        assert out["done"]["checks"][check] is True, check
    assert f'"head_token_loss_err": {reference["head_token_loss_err"]}' in err


@pytest.mark.parametrize(
    "where, lose_result, said",
    [
        ("somewhere", False, "reference_check"),
        # The successor branches on the job's parameter, not on whether
        # a result happened to arrive: it never falls back to checking
        # trained weights at limits read on fresh ones.
        ("predecessor_fresh", True, "no reference result"),
    ],
)
def test_kill_resume_refuses_what_its_reference_check_cannot_hold(
    where, lose_result, said, tmp_path, monkeypatch
):
    import argparse

    import rehearse

    from benchmark import launch, run

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cell = manifest.load_cell("gpt2-124m-rescale")
    rehearse.shrink(cell)
    cell.workload["job"]["reference_check"] = where
    if lose_result:
        wait_for = launch.Worker.wait_for

        def lossy(self, event, deadline):
            found = wait_for(self, event, deadline)
            if event == "ready":
                found.pop("reference")
            return found

        monkeypatch.setattr(launch.Worker, "wait_for", lossy)
    args = argparse.Namespace(
        workload=cell.name, seed=3, seconds=2.0, trace=0
    )
    with pytest.raises(launch.WorkerFailure, match=said):
        run.run_cell(cell, args)
