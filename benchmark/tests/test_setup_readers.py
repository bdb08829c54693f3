"""The readers of the program's set-up spans (PR 24): each on a
hand-made ``spans`` dict, and all four through the harness on the CPU.

Since PR 39 the cells list them (``calibrate_s`` only the fresh-job
cells): the rehearsals below run the real cells, shrunk.
"""

import argparse
import json
import math
import os

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


FRESH_JOB_CELLS = [
    "gpt2-124m-steady", "gpt2-124m-dp4", "lfm2-8b-a1b-steady",
    "keye-vl-2.0-30b-a3b-steady",
]


def test_the_cells_that_list_them():
    """Every cell reports the three spans any worker has; only a FRESH
    job calibrates (since PR 32 a successor under its predecessor's
    layout reuses the restored profile and has no ``step.calibrate``
    span), so the rescale cells do not list ``calibrate_s``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m["workloads"] for m in bench["per_layer"]
              if m["name"] in READS}
    cells = [w["name"] for w in bench["workloads"]]
    assert listed["calibrate_s"] == FRESH_JOB_CELLS
    for name in ("state_init_s", "trace_lower_s", "restart_span_s"):
        assert listed[name] == cells
    for cell_name in cells:
        reported = {
            m["name"] for m in manifest.load_cell(cell_name).per_layer
        }
        want = {n for n in READS if cell_name in listed[n]}
        assert reported & set(READS) == want, cell_name


@pytest.mark.parametrize("cell_name", ["gpt2-124m-steady", "gpt2-124m-rescale"])
def test_traced_rehearsal_reports_the_set_up_spans(
    cell_name, tmp_path, monkeypatch
):
    """Both job kinds at a tiny size on the CPU: the traced line
    carries what the cell lists as finite numbers (control flow only;
    platform "cpu" is on the line, none of these is a device metric).
    A fresh job calibrates; the successor of a rescale does not: its
    journal holds ONE ``step.calibrate_reused`` event and no
    ``step.calibrate`` span, and its line leaves ``calibrate_s`` out."""
    import rehearse

    from adaptdl_tpu import trace
    from benchmark import run

    work = tmp_path / "tmp"
    work.mkdir()
    journal = tmp_path / "journal"
    journal.mkdir()
    monkeypatch.setenv("TMPDIR", str(work))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
    )
    monkeypatch.delenv("ADAPTDL_TRACE", raising=False)
    monkeypatch.setenv("ADAPTDL_TRACE_DIR", str(journal))
    cell = manifest.load_cell(cell_name)
    rehearse.shrink(cell)
    args = argparse.Namespace(
        workload=cell_name, seed=3, seconds=2.0, trace=1
    )
    line = run.run_cell(cell, args, root=ROOT)
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    fresh = cell.workload["job"]["kind"] == "steady"
    listed = [n for n in READS if fresh or n != "calibrate_s"]
    assert {m["name"] for m in cell.per_layer} & set(READS) == set(listed)
    assert set(READS) & set(line["metrics"]) == set(listed)
    values = {name: line["metrics"][name]["value"] for name in listed}
    assert all(math.isfinite(v) and v > 0 for v in values.values()), values
    # The umbrella contains the others.
    assert values["restart_span_s"] >= values["state_init_s"]
    # The window's worker: the only one of a fresh job, the successor
    # (incarnation 1) of a rescale.
    records = [
        rec
        for path in journal.glob("trace-*.jsonl")
        for rec in trace.read_journal(str(path))
        if rec["inc"] == (0 if fresh else 1)
    ]
    calibrated = [
        r for r in records
        if r["name"] == "step.calibrate" and r.get("kind") != "event"
    ]
    reused = [r for r in records if r["name"] == "step.calibrate_reused"]
    if fresh:
        assert values["restart_span_s"] >= values["calibrate_s"]
        assert calibrated and not reused
    else:
        assert len(reused) == 1 and not calibrated
