"""Finds what a cell needs by the names ``BENCHMARK.json`` gives.

Everything that belongs to one configuration, one cell, one job kind
or one per-layer metric is a file of its own under ``benchmark/``,
found by name — a later PR adds files and entries, and edits none:

    configs/<config>.json   sizes as run, source, reduced, assumed
    configs/<config>.py     builder of the system under test + the
                            plain reference
    workloads/<cell>.json   batch geometry, job kind and its
                            parameters, the metrics the cell reports
    jobs/<kind>.py          the job driver (parent side and worker side)
    layer_metrics/<name>.py one reader: (trace, spans, record) -> number
    peaks.json              chip peaks by exact ``device_kind``

No jax here: the parent process loads the manifest.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ManifestError(Exception):
    """BENCHMARK.json and the files under benchmark/ disagree."""


def load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing file: {path}") from None
    except json.JSONDecodeError as exc:
        raise ManifestError(f"{path}: {exc}") from None


def load_module(path: str):
    """Import one file of the benchmark by path (names carry '-')."""
    if not os.path.isfile(path):
        raise ManifestError(f"missing file: {path}")
    name = "benchmark__" + "".join(
        c if c.isalnum() else "_"
        for c in os.path.relpath(path, ROOT)
    )
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def bench_path(root: str, *parts: str) -> str:
    return os.path.join(root, "benchmark", *parts)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    workload: dict  # workloads/<cell>.json
    sizes: dict  # configs/<config>.json
    config_py: str
    job_py: str
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)
    peaks: dict = field(default_factory=dict)
    # What a worker's devices must be. The CPU rehearsals in
    # ``benchmark/tests`` change it on their own shrunk copy of a cell.
    platform: str = "tpu"


def reader_path(root: str, metric: str) -> str:
    return bench_path(root, "layer_metrics", f"{metric}.py")


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with every file it names checked to exist."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise ManifestError(
            f"unknown workload {name!r}; BENCHMARK.json has "
            f"{sorted(entries)}"
        )
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise ManifestError(
            f"workload {name!r} names unknown config {entry['config']!r}"
        )
    workload = load_json(bench_path(root, "workloads", f"{name}.json"))
    for key in ("config", "chips"):
        if workload.get(key) != entry[key]:
            raise ManifestError(
                f"workloads/{name}.json has {key}={workload.get(key)!r}, "
                f"BENCHMARK.json has {entry[key]!r}"
            )
    config_entry = configs[entry["config"]]
    sizes = load_json(os.path.join(root, config_entry["file"]))
    config_py = os.path.splitext(
        os.path.join(root, config_entry["file"])
    )[0] + ".py"
    kind = workload.get("job", {}).get("kind")
    job_py = bench_path(root, "jobs", f"{kind}.py")
    for path, what in ((config_py, "config module"), (job_py, "job kind")):
        if not os.path.isfile(path):
            raise ManifestError(
                f"workload {name!r}: unknown {what}, no file {path}"
            )
    cell = Cell(
        name=name,
        chips=entry["chips"],
        config_name=entry["config"],
        workload=workload,
        sizes=sizes,
        config_py=config_py,
        job_py=job_py,
        peaks=load_json(bench_path(root, "peaks.json")),
    )
    known = {
        **{m["name"]: ("end_to_end", m) for m in bench["end_to_end"]},
        **{m["name"]: ("per_layer", m) for m in bench["per_layer"]},
    }
    for metric in workload.get("metrics", []):
        if metric not in known:
            raise ManifestError(
                f"workload {name!r} reports unknown metric {metric!r}"
            )
        group, declared = known[metric]
        if not _applies(declared, name):
            raise ManifestError(
                f"metric {metric!r} does not list workload {name!r} "
                "in BENCHMARK.json"
            )
        if group == "per_layer" and not os.path.isfile(
            reader_path(root, metric)
        ):
            raise ManifestError(
                f"per-layer metric {metric!r} has no reader "
                f"{reader_path(root, metric)}"
            )
        getattr(cell, group).append(declared)
    if "setup_s" not in {m["name"] for m in cell.end_to_end}:
        raise ManifestError(f"workload {name!r} must report setup_s")
    return cell
