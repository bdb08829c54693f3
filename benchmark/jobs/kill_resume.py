"""Job kind ``kill_resume``: train, SIGTERM, save, exit 143, a NEW
process restores and trains on; the window runs on the successor.

Two real processes through the real ``bootstrap`` / ``checkpoint``
path — the mechanism of ``chip_smoke.py``'s incarnation 0 / 1 (PR 21).
``rescale_s`` is the parent's clock from sending SIGTERM until the
successor reports its first completed step.

Parameters (``workloads/<cell>.json`` -> ``job``):
``steps_before_kill`` (steps at the pinned configuration before the
predecessor says it is ready), ``successor_chips`` (layout of the
successor; equal to the cell's chips for now), ``loss_band`` (how far
the first loss after the resume may lie from the last before it),
``warm_steps``, ``trace_after_steps``, ``trace_slice_s`` as in
``steady``, and ``reference_check``: WHERE the run's weights are held
to the configuration's plain reference. ``"successor"`` (the default
when the key is absent) holds the RESTORED weights after the
successor's first step. ``"predecessor_fresh"`` holds the predecessor's
FRESH weights after ``enter_job()`` and before it trains, where
``steady`` holds them and where a configuration's limits are read (a
head's error grows as training sharpens the logits: PERF.md section
7); the result travels with the ``ready`` event and the parent carries
it into the successor's record and ``reference_agrees``. The restored
weights are then held by what a restart has to hold: step, loader
position, batch configuration and progress restored, the first loss
continuing the predecessor's, ``ckpt.verify``'s hashes between the
saved and the restored bytes. Either way the check is off the path
``rescale_s`` times.
"""

from __future__ import annotations

import json
import math
import os
import time

from benchmark import harness, launch

REFERENCE_CHECKS = ("successor", "predecessor_fresh")


def run(ctx) -> dict:
    job = ctx.cell.workload["job"]
    checks = {}
    first = launch.Worker(
        ctx.root,
        ctx.spec(role="predecessor", trace=0),
        launch.job_env(ctx.root, ctx.ckpt_dir, 0, ctx.cell.chips),
    )
    try:
        ready = first.wait_for("ready", ctx.deadline)
        sigterm_at = first.sigterm()
        code = first.wait_exit(ctx.deadline)
        exited_at = time.monotonic()
        if "error" in first.events:
            raise launch.WorkerFailure(first.events["error"]["error"])
    finally:
        first.stop()
    prev = first.events.get("exit")
    checks["exit_143"] = code == harness.GRACEFUL_EXIT_CODE and bool(
        prev and prev["signalled"]
    )
    checks["manifest_complete"] = _manifest_complete(ctx.ckpt_dir)
    if not (checks["exit_143"] and checks["manifest_complete"]):
        raise launch.WorkerFailure(
            f"predecessor exited {code}, events "
            f"{sorted(first.events)}, checks {checks}"
        )
    second = launch.Worker(
        ctx.root,
        ctx.spec(
            role="successor",
            prev=prev,
            parent={"save_exit_s": exited_at - sigterm_at},
            reference=ready.get("reference"),
        ),
        launch.job_env(
            ctx.root, ctx.ckpt_dir, 1, int(job["successor_chips"])
        ),
    )
    try:
        second.wait_for("first_step", ctx.deadline)
        second.wait_for("window_start", ctx.deadline)
        done = second.wait_for("done", ctx.deadline)
        code = second.wait_exit(ctx.deadline)
        if code != 0:
            raise launch.WorkerFailure(f"successor exited {code}")
    finally:
        second.stop()
    done["device"]["memory_peak_bytes"] = max(
        done["device"]["memory_peak_bytes"] or 0,
        prev.get("memory_peak_bytes") or 0,
    ) or None
    return {
        "done": done,
        "setup_s": second.seen_at["window_start"] - ctx.started,
        "end_to_end": {
            "rescale_s": second.seen_at["first_step"] - sigterm_at
        },
        "checks": checks,
        # The rescale counts as one more operation of the run.
        "extra_attempted": 1,
    }


def _manifest_complete(ckpt_dir: str) -> bool:
    manifests = [
        os.path.join(ckpt_dir, d, "manifest.json")
        for d in sorted(os.listdir(ckpt_dir))
        if d.startswith("checkpoint-")
    ]
    if not manifests:
        return False
    with open(manifests[-1], encoding="utf-8") as f:
        states = json.load(f).get("states", {})
    return "elastic_trainer" in states and "adaptdl_dataloader" in states


def worker(spec: dict, events: harness.Events) -> None:
    if spec["role"] == "predecessor":
        _predecessor(spec, events)
    else:
        _successor(spec, events)


def _position(run_) -> dict:
    import jax

    return {
        "step": int(run_.state.step),
        "epoch": int(run_.loader.sampler.epoch),
        "index": int(run_.loader.sampler.index),
        "atomic_bsz": run_.loader.current_atomic_bsz,
        "accum_steps": run_.loader.current_accum_steps,
        "progress": float(jax.device_get(run_.state.progress)),
    }


def _predecessor(spec: dict, events: harness.Events) -> None:
    """restarts=0: train to the pinned configuration and
    ``steps_before_kill`` steps beyond, say so, and train on until the
    loader's exit agreement answers SIGTERM with a checkpoint and
    SystemExit(143)."""
    from adaptdl_tpu import _signal

    where = spec["job"].get("reference_check", "successor")
    harness.check(
        where in REFERENCE_CHECKS,
        f"job.reference_check is {where!r}, not one of {REFERENCE_CHECKS}",
    )
    run_ = harness.Run(spec, events)
    harness.check(not run_.enter_job(), "a fresh job found a checkpoint")
    found = {}
    if where == "predecessor_fresh":
        found["reference"] = run_.reference_check()
    run_.settle(int(spec["job"]["steps_before_kill"]))
    harness.say(
        f"ready for SIGTERM after {run_.steps} steps; compiles so far "
        f"{json.dumps(run_.compiles.summary())}"
    )
    events.send("ready", steps=run_.steps, **found)
    sent = run_.steps
    last = {}

    def after_step(m):
        last["m"] = m
        harness.check(
            run_.steps <= sent + 2000, "SIGTERM never arrived"
        )
        return False

    try:
        run_.drive(after_step)
    except SystemExit as exit_:
        final = {
            "exit_code": exit_.code,
            "signalled": bool(_signal.get_exit_flag()),
            "last_loss": float(last["m"]["loss"]) if last else None,
            "first_loss": float(run_.losses[0]),
            "memory_peak_bytes": run_.memory_peak_bytes(),
            **_position(run_),
        }
        harness.say(f"exiting {exit_.code}: {json.dumps(final)}")
        events.send("exit", **final)
        raise


def _successor(spec: dict, events: harness.Events) -> None:
    """restarts=1, fresh process, same checkpoint directory: restore,
    report the first completed step, then the window."""
    import jax

    prev, job = spec["prev"], spec["job"]
    run_ = harness.Run(spec, events)
    harness.check(run_.enter_job(), "load_state found no checkpoint")
    now = _position(run_)
    harness.say(f"restored {json.dumps(now)}; predecessor left "
                f"{json.dumps(prev)}")
    checks = {
        "step_restored": now["step"] == prev["step"] and now["step"] > 0,
        "loader_position_restored": (now["epoch"], now["index"])
        == (prev["epoch"], prev["index"]),
        "batch_config_restored": (now["atomic_bsz"], now["accum_steps"])
        == (prev["atomic_bsz"], prev["accum_steps"]),
        "progress_restored": math.isclose(
            now["progress"], prev["progress"], rel_tol=1e-6
        ),
    }
    first = {}

    def first_step(m):
        jax.block_until_ready(m)
        events.send("first_step", loss=float(m["loss"]))
        first["compiles"] = run_.compiles.summary()
        first["compile_s"] = first["compiles"]["compile_s"]
        first["loss"] = float(m["loss"])

    run_.settle(int(job["warm_steps"]), on_first_step=first_step)
    gap = abs(first["loss"] - prev["last_loss"])
    # One optimizer step apart on different batches: a restore that
    # lost the weights lands back at the predecessor's FIRST loss.
    checks["loss_continues"] = gap <= job["loss_band"] and gap < abs(
        first["loss"] - prev["first_loss"]
    )
    harness.say(
        f"first loss {first['loss']:.4f} after {prev['last_loss']:.4f} "
        f"(predecessor's first {prev['first_loss']:.4f}); compile/load "
        f"before it {first['compile_s']:.2f}s: "
        f"{json.dumps(first['compiles'])}"
    )
    if job.get("reference_check", "successor") == "successor":
        # After the first step, so that the benchmark's own check is
        # not on the path rescale_s times.
        reference = run_.reference_check()
    else:
        reference = spec["reference"]
        harness.check(
            reference is not None,
            "the predecessor's ready event carried no reference result",
        )
    checks["reference_agrees"] = reference["ok"]
    harness.quiesce()
    result = run_.window(spec["seconds"])
    record = {
        "reference": reference,
        "resume": {
            "first_loss": first["loss"],
            "predecessor_last_loss": prev["last_loss"],
            "predecessor_first_loss": prev["first_loss"],
            "loss_band": job["loss_band"],
            "restored": now,
            "saved": {k: prev[k] for k in now},
        },
        "parent": spec["parent"],
        "successor_compile_s": first["compile_s"],
    }
    events.send("done", **harness.finish(run_, result, checks, record))
