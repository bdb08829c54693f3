"""The readers of the program's set-up spans (PR 24): each on a
hand-made ``spans`` dict, and all four through the harness on the CPU.

No cell names them yet: a cell reports only what the ``metrics`` list
of its ``workloads/<cell>.json`` names, and a PR that is not a
benchmark PR may not edit that file. The rehearsal below runs on a
scratch copy of the manifest with the names appended and the entries
declared — the whole edit a benchmark PR has to make.
"""

import argparse
import json
import math
import os
import shutil

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
READS = {
    "state_init_s": ["trainer.init_state"],
    "trace_lower_s": ["jit.trace", "jit.lower"],
    "calibrate_s": ["step.calibrate"],
    "restart_span_s": ["restart.first_step"],
}


def _reader(name):
    return manifest.load_module(manifest.reader_path(ROOT, name))


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_sums_its_spans(name):
    read = _reader(name).read
    spans = {"ckpt.restore": [9.0], "bench.run_step": [0.5]}
    assert read(None, spans, {}) is None  # the parent has no such span
    for i, span in enumerate(READS[name]):
        spans[span] = [0.25 * (i + 1), 1.0]
    want = sum(sum(spans[span]) for span in READS[name])
    assert read(None, spans, {}) == pytest.approx(want)
    # One of several names present is enough for a value.
    spans.pop(READS[name][0])
    rest = sum(sum(spans[span]) for span in READS[name][1:])
    assert read(None, spans, {}) == (
        pytest.approx(rest) if rest else None
    )


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_declares_a_program_span_of_set_up(name):
    reader = _reader(name)
    assert (reader.UNIT, reader.SOURCE, reader.MOVES) == (
        "s", "program_span", "setup_s"
    )
    assert reader.LAYER in ("trainer set-up", "launcher + job bootstrap")


def _declared(tmp_path, cell_name):
    """A scratch manifest in which ``cell_name`` reports the four."""
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in READS:
        reader = _reader(name)
        bench["per_layer"].append({
            "name": name, "unit": reader.UNIT, "better": "lower",
            "source": reader.SOURCE, "layer": reader.LAYER,
            "moves": reader.MOVES,
        })
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    path = tmp_path / "benchmark" / "workloads" / f"{cell_name}.json"
    workload = json.loads(path.read_text())
    workload["metrics"] += list(READS)
    path.write_text(json.dumps(workload))
    return manifest.load_cell(cell_name, str(tmp_path))


@pytest.mark.parametrize("cell_name", ["gpt2-124m-steady", "gpt2-124m-rescale"])
def test_traced_rehearsal_reports_the_set_up_spans(
    cell_name, tmp_path, monkeypatch
):
    """Both job kinds at a tiny size on the CPU: the traced line
    carries all four as finite numbers (control flow only; platform
    "cpu" is on the line, none of these is a device metric)."""
    import rehearse

    from benchmark import run

    work = tmp_path / "tmp"
    work.mkdir()
    monkeypatch.setenv("TMPDIR", str(work))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
    )
    cell = _declared(tmp_path, cell_name)
    rehearse.shrink(cell)
    args = argparse.Namespace(
        workload=cell_name, seed=3, seconds=2.0, trace=1
    )
    line = run.run_cell(cell, args, root=ROOT)
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    values = {
        name: line["metrics"][name]["value"] for name in READS
    }
    assert all(math.isfinite(v) and v > 0 for v in values.values()), values
    # The umbrella contains the others.
    assert values["restart_span_s"] >= values["calibrate_s"]
    assert values["restart_span_s"] >= values["state_init_s"]
