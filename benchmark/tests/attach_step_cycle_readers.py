"""Builder's tool: the whole edit that makes every cell report the five
readers of the program's own step-path names (``host_step_ms``,
``host_exposed_ms``, ``cycle_worst_over_median``,
``shard_dispatch_gap_ms``, ``after_pull_gap_ms``; ``benchmark/
step_cycles.py``), made on a SCRATCH copy of a checkout.

    python benchmark/tests/attach_step_cycle_readers.py <checkout>

In this harness a cell reports a reader only if its
``workloads/<cell>.json`` names it (``manifest.load_cell``), and a PR
that is no ``benchmark`` PR edits no accepted file. So the five reader
files wait in ``benchmark/tests/data/step_cycle_readers/`` and this
tool does what a ``benchmark`` PR would, and nothing else:

- the five files copied into ``<checkout>/benchmark/layer_metrics/``;
- five entries APPENDED to ``BENCHMARK.json``'s ``per_layer`` (layer
  "step, host side", ``moves`` ``tokens_per_s``, no ``workloads`` key:
  every cell reports ``tokens_per_s``);
- their names APPENDED to the ``metrics`` list of every
  ``workloads/<cell>.json``: nothing changed, reordered or removed.

``<checkout>`` may be a parent commit with this PR's ``benchmark/``
laid over it: the readers then find nothing and are left out of the
result line. No jax here.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
STAGED = os.path.join(
    ROOT, "benchmark", "tests", "data", "step_cycle_readers"
)
# In the order a reader of the layer meets them; (name, unit, source).
READERS = (
    ("host_step_ms", "ms", "program_span"),
    ("host_exposed_ms", "ms", "program_span"),
    ("cycle_worst_over_median", "x", "program_span"),
    ("shard_dispatch_gap_ms", "ms", "device_trace"),
    ("after_pull_gap_ms", "ms", "device_trace"),
)


def _rewrite(path: str, edit) -> None:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    edit(data)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1)
        f.write("\n")


def attach(checkout: str) -> list[str]:
    """Make the edit under ``checkout``; returns the cells edited.
    Refuses a checkout that already holds any of it."""
    bench_json = os.path.join(checkout, "BENCHMARK.json")
    with open(bench_json, encoding="utf-8") as f:
        bench = json.load(f)
    names = [name for name, _, _ in READERS]
    if {m["name"] for m in bench["per_layer"]} & set(names):
        raise SystemExit(f"{checkout}: the readers are attached already")
    for name in names:
        shutil.copy(
            os.path.join(STAGED, f"{name}.py"),
            os.path.join(checkout, "benchmark", "layer_metrics", f"{name}.py"),
        )
    _rewrite(
        bench_json,
        lambda b: b["per_layer"].extend(
            {
                "name": name, "unit": unit, "better": "lower",
                "source": source, "layer": "step, host side",
                "moves": "tokens_per_s",
            }
            for name, unit, source in READERS
        ),
    )
    cells = [w["name"] for w in bench["workloads"]]
    for cell in cells:
        _rewrite(
            os.path.join(checkout, "benchmark", "workloads", f"{cell}.json"),
            lambda w: w["metrics"].extend(names),
        )
    return cells


if __name__ == "__main__":
    if len(sys.argv) != 2 or os.path.samefile(sys.argv[1], ROOT):
        raise SystemExit(__doc__)
    print(json.dumps({"attached_in": attach(sys.argv[1])}))
