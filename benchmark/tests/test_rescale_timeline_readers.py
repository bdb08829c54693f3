"""The readers of one rescale's timeline (PR 36): each on hand-made
records, and all of them through the harness on the CPU, on a shrunk
copy of the cell proposed for them, ``lfm2-8b-a1b-rescale``.

``gpt2-124m-rescale`` lists the ten since PR 39. The proposed cell
holds the reference where its limits were read since then
(``job.reference_check`` ``predecessor_fresh``) and was still not
added: its ``rescale_s`` reads in two populations 40 s apart by
whether the successor finds the donating step in the compile cache,
which the PROGRAM decides, and its data is learned before the kill, so
``loss_went_down`` in its window has little room (PERF.md section 7).
Its workload file waits in ``tests/data`` and the rehearsal runs on a
scratch copy of the manifest in which ``timeline_run.declare`` has made
the whole edit that adds it, data files and entries only."""

import argparse
import json
import math
import os

import pytest
import timeline_run

from benchmark import manifest

ROOT = manifest.ROOT
CELL = timeline_run.PROPOSED
NEW = list(timeline_run.NEW)
ATTACHED = list(timeline_run.ATTACHED)
TINY = {  # test_lfm2_cell.py's shrunk copy of the configuration
    "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 8, "experts_held": 2,
    "num_experts_per_tok": 2, "vocab_size": 211, "sequence_length": 32,
    "compute_dtype": "float32",
}
T0 = 1_790_000_000.0


def _reader(name):
    return manifest.load_module(manifest.reader_path(ROOT, name))


def _rec(name, inc, ts, dur, **more):
    return {"name": name, "inc": inc, "ts": T0 + ts, "dur": dur, **more}


def _records():
    """Incarnation 3 looks back on its predecessor, 2; incarnation 1's
    save and an event under a span's name are not this rescale's."""
    return 3, [
        _rec("ckpt.write", 1, -500.0, 99.0),
        _rec("exit.agree", 1, -510.0, 77.0),
        _rec("ckpt.snapshot", 2, -100.0, 55.0),  # a periodic save
        _rec("exit.agree", 2, 0.0, 0.5),
        _rec("ckpt.snapshot", 2, 0.5, 2.0),
        _rec("ckpt.write", 2, 2.5, 6.0),
        _rec("exit.atexit", 2, 8.5, 1.0),
        _rec("boot.process", 3, 12.0, 17.0),
        _rec("boot.import", 3, 23.5, 5.0),
        _rec("boot.import", 3, 23.5, 0.0, kind="event"),
        _rec("restart.first_step", 3, 29.0, 13.0),
    ]


WANT = {
    "rescale_span_s": 42.0,  # 29 + 13 - 0
    "exit_agree_s": 0.5,
    "ckpt_snapshot_s": 2.0,
    "ckpt_write_s": 6.0,
    "exit_teardown_s": 3.5,  # 12.0 less (8.5 - 0)
    "boot_process_s": 17.0,
    "boot_import_s": 5.0,
}
PARENT = {"parent": {"save_exit_s": 12.0}}


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_this_rescale_only(name):
    read = _reader(name).read
    assert read(None, {}, PARENT, records=_records()) == pytest.approx(
        WANT[name]
    )


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_where_the_program_has_no_such_span(name):
    """A parent commit: no hand-over, no boot spans. Nothing is read
    and nothing raises, on an empty buffer, on one that holds only the
    successor's older spans, and on this process's real (empty) one."""
    read = _reader(name).read
    older = (1, [_rec("restart.first_step", 1, 29.0, 13.0)])
    for records in ((0, []), older, None):
        assert read(None, {}, PARENT, records=records) is None
    assert read(None, {}, {}, records=None) is None


def test_a_stale_predecessor_is_not_this_rescale():
    own, recs = _records()
    recs = [r for r in recs if r["inc"] != 2]
    for name in NEW[:5]:
        assert _reader(name).read(None, {}, PARENT, records=(own, recs)) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_declares_a_metric_of_the_rescale(name):
    reader = _reader(name)
    assert (reader.UNIT, reader.MOVES) == ("s", "rescale_s")
    assert reader.LAYER in ("rescale", "launcher + job bootstrap")
    assert reader.SOURCE == (
        "host_clock" if name == "exit_teardown_s" else "program_span"
    )


@pytest.fixture
def declared(tmp_path):
    root = tmp_path / "manifest"
    timeline_run.declare(str(root))
    return str(root)


def test_the_declared_cell_reports_the_timeline(declared):
    cell = manifest.load_cell(CELL, declared)
    assert cell.chips == 1 and cell.config_name == "lfm2-8b-a1b"
    steady = manifest.load_cell("lfm2-8b-a1b-steady")
    assert cell.workload["geometry"] == steady.workload["geometry"]
    assert cell.workload["dataset_samples"] == 1024
    job = cell.workload["job"]
    assert job["kind"] == "kill_resume"
    assert job["reference_check"] == "predecessor_fresh"
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "rescale_s", "setup_s"
    }
    assert set(NEW + ATTACHED) <= {m["name"] for m in cell.per_layer}
    # Adding the cell is data only, and changes nothing that is there:
    # no entry is added or lost, and every cell loads as before.
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        old = json.load(f)
    with open(os.path.join(declared, "BENCHMARK.json")) as f:
        new = json.load(f)
    assert [m["name"] for m in new["per_layer"]] == [
        m["name"] for m in old["per_layer"]
    ]
    assert len(json.dumps(new)) < 64 * 1024
    for entry in old["workloads"]:
        before = manifest.load_cell(entry["name"])
        after = manifest.load_cell(entry["name"], declared)
        assert [m["name"] for m in after.per_layer] == [
            m["name"] for m in before.per_layer
        ]


def test_the_rescale_cell_lists_the_ten():
    cell = manifest.load_cell("gpt2-124m-rescale")
    listed = {m["name"]: m for m in cell.per_layer}
    assert set(NEW + ATTACHED) <= set(listed)
    for name in NEW:
        assert listed[name]["workloads"] == ["gpt2-124m-rescale"]
    # What the timeline divides is reported beside it.
    assert {"save_exit_s", "ckpt_restore_s", "successor_compile_s"} <= set(
        listed
    )
    assert "reference_check" not in cell.workload["job"]


def test_traced_rehearsal_reports_the_timeline(
    declared, tmp_path, monkeypatch
):
    """kill_resume on a shrunk copy of the cell on the CPU: the traced
    line carries the seven and PR 24's three, read from the successor's
    ring buffer alone (no journal), and the program's account of the
    rescale agrees with the parent's clock (control flow only; platform
    "cpu" is on the line, none of these is a device metric)."""
    work = tmp_path / "work"
    work.mkdir()
    monkeypatch.setenv("TMPDIR", str(work))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("ADAPTDL_TRACE", raising=False)
    monkeypatch.delenv("ADAPTDL_TRACE_DIR", raising=False)
    cell = manifest.load_cell(CELL, declared)
    cell.platform = "cpu"
    cell.sizes.update(TINY)
    cell.workload["dataset_samples"] = 64
    cell.workload["job"].update(
        steps_before_kill=4, warm_steps=3, trace_after_steps=2,
        trace_slice_s=0.5,
    )
    args = argparse.Namespace(
        workload=CELL, seed=2**31 + 36, seconds=2.0, trace=1
    )
    out, line = timeline_run.run_job(cell, args, str(work))
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    values = {n: m["value"] for n, m in line["metrics"].items()}
    assert set(NEW + ATTACHED) <= set(values), sorted(values)
    assert all(math.isfinite(values[n]) for n in NEW + ATTACHED)
    assert all(values[n] >= 0 for n in NEW if n != "exit_teardown_s")
    # signal -> agreed -> snapshot -> write -> gone adds up to what the
    # parent's clock saw of the predecessor...
    named = sum(values[n] for n in NEW[1:5])
    assert named == pytest.approx(values["save_exit_s"], abs=0.3)
    assert values["exit_teardown_s"] > 0
    # ...the imports lie inside the process's start-up...
    assert 0 < values["boot_import_s"] <= values["boot_process_s"]
    # ...the program's own rescale_s is the parent's (which a traced
    # line leaves out: the job's own output has it)...
    assert values["rescale_span_s"] == pytest.approx(
        out["end_to_end"]["rescale_s"], abs=1.0
    )
    # ...with little of it still dark.
    assert abs(timeline_run.identities(
        values, out["end_to_end"]["rescale_s"]
    )["still_dark_s"]) < 2.0
    # The reference was held in the predecessor, on its fresh weights.
    assert out["done"]["record"]["reference"]["ok"] is True
    assert out["done"]["checks"]["reference_agrees"] is True
