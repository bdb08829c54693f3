"""Test-only entry: a cell's job driver end to end at a tiny size on
the CPU backend (Pallas in interpret mode, four virtual devices for a
four-chip cell). Proves paths, arguments and control flow before a
chip call; the numbers it prints are NOT device metrics and carry the
platform "cpu" in their ``device``.

    python benchmark/tests/rehearse.py <cell> [--trace 1] [--seconds 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {
    "gpt2-124m": {
        "n_layer": 2, "n_embd": 32, "n_head": 2, "vocab_size": 211,
        "n_positions": 32, "compute_dtype": "float32",
    },
}


def shrink(cell) -> None:
    """Tiny widths, tiny batches, a few hundred samples, on the CPU."""
    cell.platform = "cpu"
    cell.sizes.update(TINY[cell.config_name])
    geometry, job = cell.workload["geometry"], cell.workload["job"]
    geometry["atomic_bsz"] = 2
    geometry["global_batch"] = (
        cell.chips * 2 * (geometry["accum_steps"] + 1)
    )
    cell.workload["dataset_samples"] = 512
    job.update(warm_steps=3, trace_after_steps=2, trace_slice_s=0.5)
    if "steps_before_kill" in job:
        job["steps_before_kill"] = 4


def rehearse(workload: str, seconds: float = 3.0, trace: int = 0,
             seed: int = 0) -> dict:
    from benchmark import manifest, run

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    args = argparse.Namespace(
        workload=workload, seed=seed, seconds=seconds, trace=trace
    )
    cell = manifest.load_cell(workload)
    shrink(cell)
    return run.run_cell(cell, args)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("workload")
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--trace", type=int, default=0)
    ns = parser.parse_args()
    print(json.dumps(rehearse(ns.workload, ns.seconds, ns.trace)))
