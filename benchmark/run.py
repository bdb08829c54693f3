"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, traced ``breakdown``, and last ``compared`` (every check
and every number compared beside its limit, also the last line of
stderr). It exits non-zero and prints no result when there is no TPU,
fewer chips than the cell asks for, or no program to measure.

This process never imports jax: the workers it starts own the chip.
Everything a cell needs is found by name (``manifest.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import launch, manifest  # noqa: E402

# The driver allows a run 360 s (1200 s for a checkout's first, which
# compiles); a worker that has sent nothing by then is lost.
RUN_DEADLINE_S = 1150.0


class JobContext:
    """What a job kind's parent side gets: the cell, where to work,
    and the spec every worker of this run starts from."""

    def __init__(self, root, cell, args, work_dir, started):
        self.root = root
        self.cell = cell
        self.args = args
        self.work_dir = work_dir
        self.ckpt_dir = os.path.join(work_dir, "ckpt")
        os.makedirs(self.ckpt_dir)
        self.started = started
        self.deadline = started + RUN_DEADLINE_S

    def spec(self, role: str, **extra) -> dict:
        cell, workload = self.cell, self.cell.workload
        spec = {
            "role": role,
            "workload": cell.name,
            "platform": cell.platform,
            "chips": cell.chips,
            "seed": self.args.seed,
            "seconds": self.args.seconds,
            "trace": self.args.trace,
            "work_dir": self.work_dir,
            "config_py": cell.config_py,
            "job_py": cell.job_py,
            "sizes": cell.sizes,
            "geometry": workload["geometry"],
            "dataset_samples": workload["dataset_samples"],
            "job": workload["job"],
            "rate_metric": workload["rate_metric"],
            "peaks": cell.peaks,
            "readers": {
                m["name"]: manifest.reader_path(self.root, m["name"])
                for m in cell.per_layer
            },
        }
        spec.update(extra)
        return spec


def run_cell(cell, args, root: str = ROOT) -> dict:
    """Run one loaded cell; returns the result line as a dict."""
    started = time.monotonic()
    geometry = cell.workload["geometry"]
    if geometry["global_batch"] != cell.chips * geometry["atomic_bsz"] * (
        geometry["accum_steps"] + 1
    ):
        raise manifest.ManifestError(
            f"{cell.name}: global_batch is not chips * atomic_bsz * "
            "(accum_steps + 1)"
        )
    work_dir = tempfile.mkdtemp(prefix="adaptdl-bench-")
    try:
        ctx = JobContext(root, cell, args, work_dir, started)
        out = manifest.load_module(cell.job_py).run(ctx)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    return assemble(cell, args, out)


def assemble(cell, args, out: dict) -> dict:
    done = out["done"]
    checks = {**done["checks"], **out["checks"]}
    if args.trace:
        values = done.get("per_layer", {})
        declared = cell.per_layer
    else:
        values = {
            **done["end_to_end"],
            **out["end_to_end"],
            "setup_s": out["setup_s"],
        }
        declared = cell.end_to_end
        missing = [m["name"] for m in declared if m["name"] not in values]
        if missing:
            raise launch.WorkerFailure(f"no value for {missing}")
    line = {
        "correct": all(checks.values()),
        "attempted": done["attempted"] + out.get("extra_attempted", 0),
        "failed": done["failed"],
        "metrics": {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in declared
            if m["name"] in values
        },
        "device": done["device"],
    }
    if args.trace and "breakdown" in done:
        line["breakdown"] = done["breakdown"]
    # Last on the line and last on stderr: every check, and every
    # number the job compared beside its limit (the configuration's
    # reference check; a restart's losses and positions).
    record = done.get("record", {})
    line["compared"] = {
        "checks": checks,
        **{k: record[k] for k in ("reference", "resume") if k in record},
    }
    if not line["correct"]:
        print(
            f"[bench] NOT correct: {json.dumps(checks)}", file=sys.stderr
        )
    return line


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    # A SIGTERM to this process unwinds through the job kind's
    # ``finally`` blocks, which stop its workers: none is left behind.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        line = run_cell(manifest.load_cell(args.workload), args)
    except (manifest.ManifestError, launch.WorkerFailure) as exc:
        print(f"[bench] FAILED: {exc}", file=sys.stderr)
        return 1
    print(
        f"[bench] compared: {json.dumps(line['compared'])}",
        file=sys.stderr, flush=True,
    )
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
