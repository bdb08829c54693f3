"""Builder's tool: ONE journalled run of a rescale cell with the parent's
``rescale_s`` kept beside the program's own timeline of the rescale (a
traced result line leaves end-to-end metrics out), the three identities
ISSUE 36 asks of the timeline, and the run's waterfall.

    chiprun -- python benchmark/tests/timeline_run.py \
        --cell gpt2-124m-rescale --seed 3600100002 [--trace 0]

A rescale cell reports the ten timeline metrics since PR 39, read by
the cell's own readers from the successor's ring buffer (a traced
run's ``metrics``); ``from_journal`` computes the same values by the
readers' own code from the run's trace journals (``ADAPTDL_TRACE_DIR``
under ``chiprun_out/``), so an untraced run (``--trace 0``) has them
too, and ``identities_reported`` holds the three identities from the
reported values alone. ``--cell lfm2-8b-a1b-rescale`` runs the PROPOSED
cell (``tests/data/lfm2-8b-a1b-rescale.json``; why it is not a cell:
PERF.md section 7) on a scratch copy of the manifest in which
``declare`` has made the whole edit that adds it: data only, its file,
its entry and its name in the lists of the metrics it reports. No jax
here: the job's workers own the chip.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")

PROPOSED = "lfm2-8b-a1b-rescale"
NEW = (
    "rescale_span_s", "exit_agree_s", "ckpt_snapshot_s", "ckpt_write_s",
    "exit_teardown_s", "boot_process_s", "boot_import_s",
)
ATTACHED = ("restart_span_s", "state_init_s", "trace_lower_s")
TIMELINE = (
    "exit.agree", "ckpt.snapshot", "ckpt.write", "exit.atexit",
    "boot.process", "boot.import", "restart.first_step",
)
SUCCESSOR_DETAIL = (
    "bootstrap.init", "trainer.init_state", "ckpt.verify", "ckpt.restore",
    "aot.lookup", "aot.compile", "step.calibrate", "jit.trace",
    "jit.lower", "jit.compile",
)


def declare(scratch: str) -> None:
    """A copy of the manifest under ``scratch`` in which the proposed
    cell exists: its workload file, its entry, and its name in the
    ``workloads`` list of every metric it reports that has one."""
    from benchmark import manifest

    shutil.copytree(
        os.path.join(ROOT, "benchmark"),
        os.path.join(scratch, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    source = manifest.bench_path(ROOT, "tests", "data", f"{PROPOSED}.json")
    shutil.copy(
        source, manifest.bench_path(scratch, "workloads", f"{PROPOSED}.json")
    )
    reported = manifest.load_json(source)["metrics"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": PROPOSED, "config": "lfm2-8b-a1b", "traffic": "rescale",
        "chips": 1,
        "why": "4 x 8192 tokens a step; SIGTERM, save 8.1 GB of DONATED "
        "state, exit 143, a new process restores and re-traces the "
        "donating step; window on the successor; bypasses collectives "
        "and a change of layout",
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in reported and "workloads" in metric:
            metric["workloads"].append(PROPOSED)
    with open(os.path.join(scratch, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


def run_job(cell, args, work_dir: str):
    """``run.run_cell``'s two halves, keeping the job's own output."""
    from benchmark import manifest, run

    ctx = run.JobContext(ROOT, cell, args, work_dir, time.monotonic())
    out = manifest.load_module(cell.job_py).run(ctx)
    return out, run.assemble(cell, args, out)


def timeline_from(records: list[dict], save_exit_s: float) -> dict:
    """The seven and ``restart_span_s``, by the readers' own code, from
    journalled records of incarnations 0 and 1."""
    from benchmark import manifest

    record = {"parent": {"save_exit_s": save_exit_s}}
    values = {}
    for name in NEW:
        read = manifest.load_module(manifest.reader_path(ROOT, name)).read
        value = read(None, {}, record, records=(1, records))
        if value is not None:
            values[name] = value
    first = [
        r for r in records
        if r["name"] == "restart.first_step" and r["inc"] == 1
    ]
    if first:
        values["restart_span_s"] = first[-1]["dur"]
    return values


def identities(values: dict, rescale_s: float) -> dict:
    nan = float("nan")
    named = sum(
        values.get(k, nan)
        for k in ("exit_agree_s", "ckpt_snapshot_s", "ckpt_write_s",
                  "exit_teardown_s")
    )
    span = values.get("rescale_span_s", nan)
    return {
        "save_exit_s_less_its_four_parts": values["save_exit_s"] - named,
        "rescale_s_less_rescale_span_s": rescale_s - span,
        "still_dark_s": span - values["save_exit_s"]
        - values.get("boot_process_s", nan)
        - values.get("restart_span_s", nan),
    }


def main() -> int:
    from adaptdl_tpu import trace
    from benchmark import manifest

    parser = argparse.ArgumentParser()
    parser.add_argument("--cell", required=True)
    parser.add_argument("--seed", type=int, default=3600100001)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=1)
    parser.add_argument(
        "--set", action="append", default=[], metavar="KEY=INT",
        help="override a job parameter of the cell, in memory only",
    )
    ns = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = ns.seconds or json.load(f)["run_seconds"]
    journal_dir = os.path.join(OUT, f"journal-{ns.cell}-{ns.seed}")
    os.makedirs(journal_dir, exist_ok=True)
    os.environ["ADAPTDL_TRACE_DIR"] = journal_dir
    work_dir = tempfile.mkdtemp(prefix="adaptdl-bench-")
    try:
        root = ROOT
        if ns.cell == PROPOSED:
            root = os.path.join(work_dir, "manifest")
            declare(root)
        cell = manifest.load_cell(ns.cell, root)
        for pair in ns.set:
            key, value = pair.split("=")
            cell.workload["job"][key] = int(value)
        args = argparse.Namespace(
            workload=ns.cell, seed=ns.seed, seconds=seconds, trace=ns.trace
        )
        out, line = run_job(cell, args, work_dir)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    # Without ADAPTDL_JOB_ID each process journals to a file of its own.
    records = [
        rec
        for path in glob.glob(os.path.join(journal_dir, "trace-*.jsonl"))
        for rec in trace.read_journal(path)
    ]
    values = {k: v["value"] for k, v in line["metrics"].items()}
    rescale_s = out["end_to_end"]["rescale_s"]
    save_exit_s = out["done"]["record"]["parent"]["save_exit_s"]
    from_journal = timeline_from(records, save_exit_s)
    report = {
        "cell": ns.cell, "seed": ns.seed, "trace": ns.trace,
        "job": cell.workload["job"], "correct": line["correct"],
        "device": line["device"], "rescale_s": rescale_s,
        "setup_s": out["setup_s"], "metrics": values,
        "from_journal": from_journal,
        "identities": identities(
            {"save_exit_s": save_exit_s, **from_journal}, rescale_s
        ),
    }
    if "save_exit_s" in values:  # a traced line: the cell's own readers
        report["identities_reported"] = identities(values, rescale_s)
    with open(os.path.join(OUT, "timelines.jsonl"), "a") as f:
        f.write(json.dumps(report) + "\n")
    print(json.dumps(report, indent=1))
    first = [
        r for r in records
        if r["name"] == "restart.first_step" and r["inc"] == 1
    ]
    horizon = first[-1]["ts"] + first[-1]["dur"] if first else 0.0
    shown = [
        r for r in records
        if r["inc"] == 0 and r["name"] in TIMELINE[:4]
        or r["inc"] == 1 and r["name"] in TIMELINE
        or r["inc"] == 1 and r["name"] in SUCCESSOR_DETAIL
        and (r["dur"] >= 0.4 or r["name"] == "bootstrap.init")
        and r["ts"] <= horizon
    ]
    print(f"\n{journal_dir}: {len(records)} records; the rescale:")
    print(trace.render_waterfall(shown, width=36))
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
