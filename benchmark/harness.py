"""Worker-side machinery shared by the job kinds.

A worker is a child process of ``run.py`` that owns the chip(s) for its
lifetime. It reaches the chip, enters the real job environment
(``initialize_job`` under the ``ADAPTDL_*`` variables its parent
exported), builds the cell's configuration, and drives training the
way a user's script does: ``for batch in AdaptiveDataLoader`` ->
``ElasticTrainer.run_step``. Everything it learns goes to the parent
as JSON lines on a pipe (``Events``).

Mechanism copied from ``chip_smoke.py`` (PR 21): the compile log from
``jax.monitoring``, the SIGTERM -> SystemExit(143) loop, the restore
checks. The benchmark does not import ``chip_smoke.py`` or
``bench.py``; later PRs may change or delete them.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

from benchmark import manifest

EPOCHS = 10**9  # the window, not the epoch count, ends a run
GRACEFUL_EXIT_CODE = 143


class BenchFailure(Exception):
    """The run cannot produce a result (wrong device, broken restore)."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


def say(msg: str) -> None:
    print(f"[bench-worker {os.getpid()}] {msg}", file=sys.stderr, flush=True)


class Events:
    """JSON lines to the parent, stamped with this process's clock."""

    def __init__(self, fd: int):
        self._out = os.fdopen(fd, "w", encoding="utf-8", buffering=1)

    def send(self, event: str, **fields) -> None:
        fields.update(event=event, at=time.time())
        self._out.write(json.dumps(fields) + "\n")
        self._out.flush()


class CompileLog:
    """Backend compiles and persistent-cache traffic of this process,
    from jax's own monitoring events. A backend-compile event fires for
    every program the process asks the compiler for, served from the
    persistent cache or not."""

    def __init__(self):
        import jax.monitoring

        self.compiles: list[tuple[float, float]] = []  # (ended at, s)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.monotonic(), float(duration)))

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def between(self, start: float, end: float) -> list[float]:
        return [s for at, s in self.compiles if start <= at <= end]

    def summary(self) -> dict:
        return {
            "programs": len(self.compiles),
            "compile_s": sum(s for _, s in self.compiles),
            "long_compiles_s": [
                round(s, 2) for _, s in self.compiles if s >= 0.5
            ],
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }


class Spans:
    """The benchmark's own host-clock spans around its calls into the
    program, kept in memory; with ``annotate`` they are also written
    into the profiler's trace so host and device share one clock.
    Traced or not, the program is called from the same line: the
    persistent compile cache's key was seen to differ between the two
    kinds of run for a program first traced under this call (PERF.md,
    Findings PR 22), and Python source locations are the suspect."""

    def __init__(self, annotate: bool):
        import contextlib

        self.durations: dict[str, list[float]] = {}
        self._annotation = lambda name: contextlib.nullcontext()
        if annotate:
            import jax.profiler

            self._annotation = jax.profiler.TraceAnnotation

    def call(self, name: str, fn, *args):
        with self._annotation(name):
            start = time.perf_counter()
            out = fn(*args)
            self.durations.setdefault(name, []).append(
                time.perf_counter() - start
            )
        return out


def reach_chip(spec: dict) -> dict:
    """Import jax, claim the devices, and refuse anything but the
    platform and chip count the cell asks for: a measurement path that
    finds no chip fails, it never falls back."""
    import jax

    devices = jax.devices()
    reached = time.time()
    dev = devices[0]
    report = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }
    say(f"device {json.dumps(report)} jax {jax.__version__}")
    check(
        dev.platform == spec["platform"],
        f"platform is {dev.platform!r}, this benchmark needs "
        f"{spec['platform']!r}",
    )
    check(
        len(devices) >= spec["chips"],
        f"{len(devices)} devices, the cell needs {spec['chips']}",
    )
    if spec["platform"] == "tpu":
        check(
            dev.device_kind in spec["peaks"],
            f"device kind {dev.device_kind!r} is not in "
            "benchmark/peaks.json",
        )
    report["reach_chip_s"] = reached - spec["spawned_at"]
    return report


class Run:
    """One worker's training job: the built configuration, its state
    holder and checkpoint registration, the loader, and the loop."""

    def __init__(self, spec: dict, events: Events):
        self.spec = spec
        self.events = events
        self.compiles = CompileLog()
        self.device = reach_chip(spec)
        self.spans = Spans(annotate=bool(spec["trace"]))
        self.losses: list = []  # device scalars, pulled after the run
        self.steps = 0
        self.state = None

    # -- set-up ---------------------------------------------------------

    def enter_job(self) -> bool:
        """The user's prologue: initialize_job, model + trainer, restore
        if a checkpoint exists, dataset, loader. Returns whether a
        checkpoint was restored."""
        import adaptdl_tpu
        from adaptdl_tpu import checkpoint, metrics
        from adaptdl_tpu.data import AdaptiveDataLoader

        spec = self.spec
        adaptdl_tpu.initialize_job()
        self.config = manifest.load_module(spec["config_py"])
        geometry = spec["geometry"]
        self.built = self.config.build(spec["sizes"], geometry, spec["seed"])
        self.trainer = self.built["trainer"]
        check(
            self.trainer.num_replicas == spec["chips"],
            f"mesh {self.trainer.mesh} for {spec['chips']} chips",
        )
        self.state = self.trainer.init_state()
        save, load = self.built["checkpoint_transforms"] or (None, None)
        ckpt = self.trainer.make_checkpoint_state(
            lambda: self.state,
            lambda s: setattr(self, "state", s),
            transform_save=save,
            transform_load=load,
        )
        restored = bool(checkpoint.load_state(ckpt))
        metrics.ensure_checkpoint_registered()
        self.dataset = self.config.make_dataset(
            spec["sizes"], spec["seed"], spec["dataset_samples"]
        )
        self.loader = AdaptiveDataLoader(
            self.dataset,
            batch_size=geometry["global_batch"],
            seed=spec["seed"],
        )
        # Pinned: one candidate for goodput.optimize, so one step
        # program and one calibration program are all the cell's
        # steady state ever runs, with the policy's host work on the
        # path.
        self.loader.autoscale_batch_size(
            geometry["global_batch"],
            local_bsz_bounds=(geometry["atomic_bsz"],) * 2,
            gradient_accumulation=geometry["accum_steps"] > 0,
        )
        self.units_per_step = geometry[
            "global_batch"
        ] * self.config.units_per_sample(spec["sizes"])
        return restored

    def reference_check(self) -> dict:
        """System loss against the plain reference on the run's own
        weights (one replica's copy, on one device)."""
        import jax

        params = jax.tree.map(
            lambda x: x.addressable_shards[0].data,
            self.trainer.params_tree(self.state),
        )
        result = self.config.reference_check(
            self.built, params, self.dataset, self.spec["sizes"]
        )
        say(f"reference check {json.dumps(result)}")
        return result

    # -- the loop -------------------------------------------------------

    @property
    def at_target(self) -> bool:
        geometry = self.spec["geometry"]
        return (
            self.loader.current_atomic_bsz,
            self.loader.current_accum_steps,
        ) == (geometry["atomic_bsz"], geometry["accum_steps"])

    def drive(self, after_step) -> None:
        """The user's training loop, until ``after_step(metrics)``
        returns True. Each call enters the loader anew, as an epoch
        boundary does; epochs wrap until the caller stops the loop."""
        from adaptdl_tpu import epoch

        for _ in epoch.remaining_epochs_until(EPOCHS):
            batches = iter(self.loader)
            try:
                while True:
                    batch = self.spans.call(
                        "bench.data_next", next, batches, None
                    )
                    if batch is None:
                        break
                    self.state, m = self.spans.call(
                        "bench.run_step",
                        self.trainer.run_step,
                        self.state,
                        batch,
                        self.loader,
                    )
                    self.steps += 1
                    self.losses.append(m["loss"])
                    if after_step(m):
                        return
            finally:
                batches.close()

    def settle(self, min_steps: int, on_first_step=None) -> None:
        """Warm up, counted as set-up: run the real policy path until
        the loader holds the cell's pinned configuration and
        ``min_steps`` steps have completed under it. A fresh job starts
        at (atomic, 0) and adopts the accumulated configuration at its
        first re-optimisation after the goodput model is fitted, as
        any user's job does; a restored job starts where it stopped."""
        import jax

        from adaptdl_tpu import metrics

        for _ in range(4):
            done = {"n": 0}

            def after_step(m):
                done["n"] += 1
                if on_first_step is not None and self.steps == 1:
                    on_first_step(m)
                if self.at_target:
                    if done["n"] >= min_steps:
                        jax.block_until_ready(m["loss"])
                        return True
                    return False
                if done["n"] >= 3:
                    jax.block_until_ready(m["loss"])
                    # The cadence-driven fit runs on a thread; fit now
                    # so the next loop entry's decision is determined.
                    metrics.fit_and_report_now()
                    return metrics.get_goodput_fn() is not None
                return False

            self.drive(after_step)
            if self.at_target and done["n"] >= min_steps:
                return
        raise BenchFailure(
            "the loader never adopted the pinned configuration "
            f"{self.spec['geometry']}: it holds "
            f"({self.loader.current_atomic_bsz}, "
            f"{self.loader.current_accum_steps})"
        )

    def window(self, seconds: float) -> dict:
        """The measured window: steps dispatched for ``seconds``
        seconds of host clock from the first dispatch, ended by
        ``block_until_ready`` on the last step's outputs. With tracing
        on, a short steady slice in the middle is profiled."""
        import jax

        from adaptdl_tpu import metrics

        job = self.spec["job"]
        tracer = (
            SliceTracer(self.spec["work_dir"], job) if self.spec["trace"]
            else None
        )
        first_loss = len(self.losses)
        unhealthy0 = metrics.unhealthy_steps()
        self.spans.durations.clear()
        mark = {"n": 0}
        mono0 = time.monotonic()
        self.events.send("window_start")
        start = time.perf_counter()

        def after_step(m):
            mark["n"] += 1
            if tracer is not None:
                tracer.after_step(mark["n"], m)
            if time.perf_counter() - start >= seconds:
                jax.block_until_ready(m)
                return True
            return False

        self.drive(after_step)
        elapsed = time.perf_counter() - start
        mono1 = time.monotonic()
        if tracer is not None:
            tracer.finish()
            elapsed -= tracer.overhead_s
        losses = [
            float(x) for x in jax.device_get(self.losses[first_loss:])
        ]
        nonfinite = sum(1 for x in losses if not math.isfinite(x))
        flagged = metrics.unhealthy_steps() - unhealthy0
        steps = mark["n"]
        return {
            "steps": steps,
            "window_s": elapsed,
            "units": steps * self.units_per_step,
            "rate": steps * self.units_per_step / elapsed,
            "losses": losses,
            "failed": min(steps, nonfinite + flagged),
            "compiles_in_window": len(
                self.compiles.between(mono0, mono1)
            ),
            "trace_file": tracer.trace_file() if tracer else None,
        }

    # -- after the window -----------------------------------------------

    def memory_peak_bytes(self) -> int | None:
        """Peak on the fullest chip; required on a TPU."""
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in self.trainer.mesh.devices.flat
        ]
        if self.spec["platform"] == "tpu":
            check(
                all(p is not None for p in peaks),
                f"memory_stats() reports no peak_bytes_in_use: {peaks}",
            )
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def program_spans(self) -> dict[str, list[float]]:
        """The program's own spans (adaptdl_tpu.trace) by name."""
        from adaptdl_tpu import trace

        out: dict[str, list[float]] = {}
        for rec in trace.snapshot_spans():
            if "dur" in rec:
                out.setdefault(rec["name"], []).append(float(rec["dur"]))
        return out


class SliceTracer:
    """Profiles a short steady slice of the window: drains the queue,
    starts the profiler, lets ``trace_slice_s`` seconds of steps run
    under a ``bench.slice`` annotation, drains again and stops. The
    slice starts and ends with an idle device, so its busy share is a
    lower bound by at most one step's dispatch latency."""

    def __init__(self, work_dir: str, job: dict):
        self.dir = os.path.join(work_dir, "trace")
        self.after = int(job["trace_after_steps"])
        self.slice_s = float(job["trace_slice_s"])
        self.state = "waiting"
        self.steps_in_slice = 0
        # Seconds the device sat idle while the profiler started and
        # stopped: taken out of a traced run's rate, which would
        # otherwise read the profiler's cost as a slow program.
        self.overhead_s = 0.0

    def after_step(self, n: int, m) -> None:
        import jax

        if self.state == "waiting" and n >= self.after:
            jax.block_until_ready(m)
            t0 = time.perf_counter()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.annotation = jax.profiler.TraceAnnotation("bench.slice")
            self.annotation.__enter__()
            self.started = time.perf_counter()
            self.overhead_s += self.started - t0
            self.state = "tracing"
        elif self.state == "tracing":
            self.steps_in_slice += 1
            if time.perf_counter() - self.started >= self.slice_s:
                jax.block_until_ready(m)
                self._stop()

    def _stop(self) -> None:
        import jax

        t0 = time.perf_counter()
        self.annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.overhead_s += time.perf_counter() - t0
        self.state = "done"

    def finish(self) -> None:
        if self.state == "tracing":
            self._stop()

    def trace_file(self) -> str | None:
        import glob

        found = glob.glob(
            os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")
        )
        return found[0] if found else None


def spans_chips(run: Run) -> dict:
    """Evidence that a data-parallel cell really spans its chips: a
    sharded batch sits on as many distinct devices as the cell has
    chips, and after training on different shards every chip holds
    the same parameters (without the gradient all-reduce each replica
    would have followed its own gradient)."""
    import jax
    import numpy as np

    batch = run.trainer.shard_batch(
        {k: v[: run.spec["geometry"]["global_batch"]]
         for k, v in run.dataset.items()}
    )
    spread = {
        s.device
        for leaf in jax.tree.leaves(batch)
        for s in leaf.addressable_shards
    }
    leaf = jax.tree.leaves(run.trainer.params_tree(run.state))[0]
    copies = [np.asarray(s.data) for s in leaf.addressable_shards]
    return {
        "batch_on_all_chips": len(spread) == run.spec["chips"],
        "replicas_agree": len(copies) == run.spec["chips"]
        and all(np.array_equal(copies[0], c) for c in copies[1:]),
    }


def loss_trend_ok(losses: list[float]) -> bool:
    """On learnable synthetic data the mean loss over the window's last
    ten steps lies below that over its first ten."""
    k = min(10, len(losses) // 2)
    if k == 0:
        return False
    return sum(losses[-k:]) / k < sum(losses[:k]) / k


def quiesce(timeout: float = 120.0) -> None:
    """Before the window: wait for the program's background fit thread
    (named ``adaptdl-fit``), whose first run compiles its objective,
    so that nothing compiles inside the window."""
    import threading

    deadline = time.monotonic() + timeout
    while any(
        t.name == "adaptdl-fit" and t.is_alive()
        for t in threading.enumerate()
    ):
        check(time.monotonic() < deadline, "the fit thread never ended")
        time.sleep(0.05)


def finish(run: Run, result: dict, checks: dict, record: dict) -> dict:
    """The worker's final report: correctness, the rate, and (traced
    run) the per-layer metrics its readers find."""
    from benchmark import xplane

    spec = run.spec
    checks = dict(checks)
    checks["losses_finite"] = result["failed"] == 0 and all(
        math.isfinite(x) for x in result["losses"]
    )
    checks["loss_went_down"] = loss_trend_ok(result["losses"])
    checks["compiles_in_window_0"] = result["compiles_in_window"] == 0
    peak = run.memory_peak_bytes()
    record = dict(
        record,
        cell=spec["workload"],
        chips=spec["chips"],
        device=run.device,
        peak_table=spec["peaks"].get(run.device["kind"]),
        steps=result["steps"],
        window_s=result["window_s"],
        rate=result["rate"],
        units_per_step=run.units_per_step,
        flops_per_unit=run.config.train_flops_per_unit(spec["sizes"]),
        sizes=spec["sizes"],
        geometry=spec["geometry"],
        compiles_in_window=result["compiles_in_window"],
        memory_peak_bytes=peak,
        compile_log=run.compiles.summary(),
    )
    device = {
        "platform": run.device["platform"],
        "kind": run.device["kind"],
        "count": run.device["count"],
        "memory_peak_bytes": peak,
    }
    report = {
        "checks": checks,
        "correct": all(checks.values()),
        "attempted": result["steps"],
        "failed": result["failed"],
        "end_to_end": {spec["rate_metric"]: result["rate"]},
        "device": device,
        "record": record,
    }
    say(
        f"window: {result['steps']} steps in {result['window_s']:.3f}s, "
        f"{spec['rate_metric']}={result['rate']:.1f}, losses "
        f"{result['losses'][:2]}..{result['losses'][-2:]}, checks "
        f"{json.dumps(checks)}, compiles {json.dumps(record['compile_log'])}"
    )
    if not spec["trace"]:
        return report
    trace = None
    if result["trace_file"]:
        trace = xplane.load(result["trace_file"])
        keep = os.environ.get("BENCHMARK_KEEP_TRACE")
        if keep:  # for looking at a trace by hand; unset in every check
            import shutil

            os.makedirs(keep, exist_ok=True)
            shutil.copy(
                result["trace_file"],
                os.path.join(keep, f"{spec['workload']}.xplane.pb"),
            )
            with open(
                os.path.join(keep, f"{spec['workload']}.lines.json"), "w"
            ) as f:
                json.dump(trace.lines_seen, f, indent=1)
    spans = {**run.program_spans(), **run.spans.durations}
    per_layer = {}
    for name, path in spec["readers"].items():
        value = manifest.load_module(path).read(trace, spans, record)
        if value is not None:
            per_layer[name] = float(value)
    report["per_layer"] = per_layer
    busy_s = trace.busy_s() if trace is not None else None
    if busy_s:
        device["busy_s"] = busy_s
        device["window_s"] = trace.window_s()
        report["breakdown"] = {
            "device_ops": trace.top_ops(10),
            "idle_gaps": trace.idle_gaps(10),
        }
        program = trace.step_program()
        say(
            f"trace: busy {device['busy_s']:.3f}s of "
            f"{device['window_s']:.3f}s on {len(trace.devices)} chip(s); "
            f"step program {program}; breakdown "
            f"{json.dumps(report['breakdown'])}"
        )
    else:
        say(f"trace: no device plane found; lines "
            f"{json.dumps(trace.lines_seen if trace else None)}")
    return report
