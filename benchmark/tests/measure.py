"""Builder's tool: several runs of cells in ONE chip call, as the
driver measures them, with the spread the bounds are set from.

    chiprun -- python benchmark/tests/measure.py \
        --cell gpt2-124m-steady --runs 6 --sets 2 [--trace 1] [--seconds N]

Each run is ``python3 benchmark/run.py ...`` with another ``--seed``, a
new process. Result lines go to ``chiprun_out/results.jsonl``, each
run's stderr to ``chiprun_out/logs/``; the summary printed at the end
gives, per cell, set and metric, the median and the spread (distance
between the quartiles over the median). No jax here.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
OUT = os.path.join(ROOT, "chiprun_out")


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4)  # as the driver takes them
    median = statistics.median(values)
    if median == 0:  # compiles_in_window in every run of a traced set
        return 0.0 if q[2] == q[0] else float("inf")
    return (q[2] - q[0]) / abs(median)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cell", action="append", required=True)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--seed0", type=int, default=100)
    parser.add_argument("--cwd", default=ROOT)
    parser.add_argument("--label", default="", help="prefix of the tags")
    args = parser.parse_args()
    with open(os.path.join(args.cwd, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(os.path.join(OUT, "logs"), exist_ok=True)
    seed = args.seed0
    table: dict = {}
    failures = 0
    for cell in args.cell:
        for set_ in range(args.sets):
            for run in range(args.runs):
                seed += 1
                tag = (
                    f"{args.label}{cell}.t{args.trace}.set{set_}.run{run}"
                )
                t0 = time.monotonic()
                with open(
                    os.path.join(OUT, "logs", tag + ".log"), "w"
                ) as log:
                    proc = subprocess.run(
                        bench["command"]
                        + ["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds),
                           "--trace", str(args.trace)],
                        cwd=args.cwd, stdout=subprocess.PIPE,
                        stderr=log, text=True,
                    )
                wall = time.monotonic() - t0
                lines = proc.stdout.strip().splitlines()
                if proc.returncode != 0 or not lines:
                    failures += 1
                    print(f"{tag}: rc={proc.returncode} NO RESULT "
                          f"({wall:.0f}s)", flush=True)
                    continue
                line = json.loads(lines[-1])
                with open(os.path.join(OUT, "results.jsonl"), "a") as f:
                    f.write(json.dumps(
                        {"tag": tag, "seed": seed, "wall_s": wall,
                         "cwd": args.cwd, **line}) + "\n")
                flat = {k: v["value"] for k, v in line["metrics"].items()}
                print(f"{tag}: correct={line['correct']} "
                      f"attempted={line['attempted']} failed="
                      f"{line['failed']} wall={wall:.0f}s "
                      f"peak={line['device'].get('memory_peak_bytes')} "
                      f"{json.dumps(flat)}", flush=True)
                if not line["correct"]:
                    failures += 1
                for k, v in flat.items():
                    table.setdefault((cell, k), {}).setdefault(
                        set_, []).append(v)
    print("\ncell metric set n median spread(IQR/median) "
          "[spread without each set's first run]")
    for (cell, metric), sets in sorted(table.items()):
        for set_, values in sorted(sets.items()):
            print(f"{cell} {metric} set{set_} n={len(values)} "
                  f"median={statistics.median(values):.6g} "
                  f"spread={spread(values):.4%} "
                  f"[{spread(values[1:]):.4%}] "
                  f"values={[round(v, 4) for v in values]}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
