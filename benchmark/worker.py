"""A worker: the child process that owns the chip(s).

    python benchmark/worker.py <spec.json> <event pipe fd>

Loads the job kind the spec names and runs its worker side. A failure
is printed with its traceback, reported to the parent and turned into
exit code 1; SystemExit (the graceful 143) passes through.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    from benchmark import harness, manifest

    with open(argv[1], encoding="utf-8") as f:
        spec = json.load(f)
    events = harness.Events(int(argv[2]))
    try:
        manifest.load_module(spec["job_py"]).worker(spec, events)
    except Exception as exc:  # noqa: BLE001 - reported, then fatal
        traceback.print_exc()
        events.send("error", error=f"{type(exc).__name__}: {exc}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
