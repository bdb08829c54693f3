"""BENCHMARK.json, the files it names, and the loader that joins them."""

import json
import os
import re
import shutil

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def copy(tmp_path, bench):
    """A scratch copy of the manifest that a test may break."""
    shutil.copytree(
        os.path.join(ROOT, "benchmark"),
        tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )

    def write(edit):
        edited = json.loads(json.dumps(bench))
        edit(edited)
        with open(tmp_path / "BENCHMARK.json", "w") as f:
            json.dump(edited, f)
        return str(tmp_path)

    return write


def test_every_cell_loads(bench):
    for entry in bench["workloads"]:
        cell = manifest.load_cell(entry["name"])
        assert cell.chips == entry["chips"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, "every cell reports a per-layer metric"
        # A per-layer metric is reported only where the metric it
        # moves is.
        for metric in cell.per_layer:
            assert metric["moves"] in names, (entry["name"], metric)


def test_contract_shape(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    assert len(names) == len(set(names))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in bench["end_to_end"]:
        assert set(metric) <= {
            "name", "unit", "better", "bound", "source", "workloads"
        }
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in bench["per_layer"]:
        assert set(metric) <= {
            "name", "unit", "better", "source", "layer", "moves",
            "workloads",
        }
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(bench)) < 64 * 1024


def test_readers_declare_what_benchmark_json_says(bench):
    for metric in bench["per_layer"]:
        reader = manifest.load_module(
            manifest.bench_path(ROOT, "layer_metrics", metric["name"] + ".py")
        )
        assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
            metric["unit"], metric["layer"], metric["source"],
            metric["moves"],
        ), metric["name"]


def test_every_reader_is_reported_by_some_cell(bench):
    """A reader file no cell lists reaches no ledger (thirteen did
    not until PR 39): every file under ``layer_metrics/`` is declared
    in ``per_layer`` and named by the ``metrics`` list of at least one
    cell it is declared for; a metric with a ``workloads`` key is
    reported by exactly those cells."""
    readers = {
        os.path.splitext(f)[0]
        for f in os.listdir(manifest.bench_path(ROOT, "layer_metrics"))
        if f.endswith(".py")
    }
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert readers == set(declared)
    reported: dict = {}
    for entry in bench["workloads"]:
        for metric in manifest.load_cell(entry["name"]).per_layer:
            reported.setdefault(metric["name"], []).append(entry["name"])
    assert set(reported) == readers
    for name, metric in declared.items():
        if "workloads" in metric:
            assert reported[name] == metric["workloads"], name


def test_unknown_workload_is_rejected():
    with pytest.raises(manifest.ManifestError, match="unknown workload"):
        manifest.load_cell("no-such-cell")


def test_unknown_config_is_rejected(copy):
    def edit(b):
        b["workloads"][0]["config"] = "no-such-config"

    with pytest.raises(manifest.ManifestError, match="unknown config"):
        manifest.load_cell("gpt2-124m-steady", copy(edit))


def _edit_workload(root, name, edit):
    path = os.path.join(root, "benchmark", "workloads", name + ".json")
    with open(path) as f:
        workload = json.load(f)
    edit(workload)
    with open(path, "w") as f:
        json.dump(workload, f)


def test_unknown_metric_is_rejected(copy):
    root = copy(lambda b: None)
    _edit_workload(
        root, "gpt2-124m-steady",
        lambda w: w["metrics"].append("no_such_metric"),
    )
    with pytest.raises(manifest.ManifestError, match="unknown metric"):
        manifest.load_cell("gpt2-124m-steady", root)


def test_metric_of_another_cell_is_rejected(copy):
    root = copy(lambda b: None)
    _edit_workload(
        root, "gpt2-124m-steady",
        lambda w: w["metrics"].append("rescale_s"),
    )
    with pytest.raises(manifest.ManifestError, match="does not list"):
        manifest.load_cell("gpt2-124m-steady", root)


def test_unknown_job_kind_is_rejected(copy):
    root = copy(lambda b: None)
    _edit_workload(
        root, "gpt2-124m-steady",
        lambda w: w["job"].update(kind="no_such_kind"),
    )
    with pytest.raises(manifest.ManifestError, match="unknown job kind"):
        manifest.load_cell("gpt2-124m-steady", root)


def test_metric_without_reader_is_rejected(copy):
    root = copy(lambda b: None)
    os.remove(os.path.join(root, "benchmark", "layer_metrics", "mfu.py"))
    with pytest.raises(manifest.ManifestError, match="no reader"):
        manifest.load_cell("gpt2-124m-steady", root)


def test_peaks_are_keyed_by_exact_device_kind():
    peaks = manifest.load_json(manifest.bench_path(ROOT, "peaks.json"))
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert all("source" in entry for entry in peaks.values())
