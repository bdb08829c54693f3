"""Job kind ``steady``: one worker trains without interruption.

Parameters (``workloads/<cell>.json`` -> ``job``): ``warm_steps``
(steps at the pinned configuration before the window),
``trace_after_steps`` and ``trace_slice_s`` (where in the window the
profiled slice lies, and how long it is).
"""

from __future__ import annotations

import time

from benchmark import harness, launch


def run(ctx) -> dict:
    """Parent side: one worker under restarts=0."""
    worker = launch.Worker(
        ctx.root,
        ctx.spec(role="steady"),
        launch.job_env(ctx.root, ctx.ckpt_dir, 0, ctx.cell.chips),
    )
    try:
        worker.wait_for("window_start", ctx.deadline)
        done = worker.wait_for("done", ctx.deadline)
        code = worker.wait_exit(ctx.deadline)
        if code != 0:
            raise launch.WorkerFailure(f"worker exited {code}")
    finally:
        worker.stop()
    return {
        "done": done,
        "setup_s": worker.seen_at["window_start"] - ctx.started,
        "end_to_end": {},
        "checks": {},
    }


def worker(spec: dict, events: harness.Events) -> None:
    """Worker side: build, check against the reference, warm up, run
    the window."""
    run_ = harness.Run(spec, events)
    restored = run_.enter_job()
    harness.check(not restored, "a fresh job found a checkpoint")
    reference = run_.reference_check()
    t0 = time.monotonic()
    run_.settle(spec["job"]["warm_steps"])
    harness.quiesce()
    harness.say(
        f"warm-up {time.monotonic() - t0:.1f}s, {run_.steps} steps; "
        f"compiles so far {run_.compiles.summary()}"
    )
    result = run_.window(spec["seconds"])
    checks = {"reference_agrees": reference["ok"]}
    if spec["chips"] > 1:
        checks.update(harness.spans_chips(run_))
    events.send(
        "done",
        **harness.finish(run_, result, checks, {"reference": reference}),
    )
