"""chip_smoke.py — the elastic loop on the chip, at the flagship LM's full width.

Drives the system's main path once through the entry points a user
calls — ``initialize_job`` -> ``ElasticTrainer`` + ``AdaptiveDataLoader``
-> ``run_step`` (profiling, GNS, goodput fit, batch-size
re-optimisation) -> SIGTERM -> ``save_all_states`` -> exit 143 -> a NEW
process -> ``load_state`` -> training continues mid-epoch — with the
model preset of ``examples/transformer_lm.py`` unreduced (12 layers,
d_model 768, 12 heads, d_ff 3072, vocab 32000, seq 512, bf16, remat,
the Pallas flash kernel) and checks what comes out.

    python chip_smoke.py            one chip: incarnation 0, then 1
    python chip_smoke.py --chips 4  four chips, and only this: dp=4
                                    against dp=1 on the same batches,
                                    then save at dp=4, restore at dp=2

Under a launcher the script is the worker instead — the README's way
to run elastically, whose parent must leave the chip to it:

    python -m adaptdl_tpu.sched.local_runner chip_smoke.py \
        --chips 1 --checkpoint-dir DIR

One process per chip: this parent never imports jax. Every phase is a
child (``multiprocessing`` spawn) that has exited before the next
starts, and the device description in the result comes from a child.
The last stdout line is one JSON object, ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": N}}``; every other line
comes before it. Any failed phase, or a device that is not a TPU,
gives ``"ok": false`` and a non-zero exit. The numbers printed on the
way are smoke output (one run, random weights), not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import shutil
import signal
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
GRACEFUL_EXIT_CODE = 143  # adaptdl_tpu._signal, without importing it

# Incarnation 1's first loss against incarnation 0's last: one
# optimizer step apart on different batches of a loss that moves a few
# hundredths of a nat per step at this point of training; a restore
# that lost the weights would land back at incarnation 0's FIRST loss
# (ln(vocab) ~ 10.4), nats away.
LOSS_BAND = 0.5

# dp=4 against dp=1 on the same global batch (scaling gain 1 in both
# arms): the same mathematics in another summation order. Activations
# are bf16 (8 mantissa bits, ~0.4% per rounding) with float32
# reductions, so per-step losses agree to about a percent.
DP_LOSS_RTOL = 0.02
# Parameters after DP_STEPS AdamW steps: an Adam update is
# lr * m / (sqrt(v) + eps), i.e. about +-lr per element whatever the
# gradient's size, so an element whose gradient is below the bf16 noise
# can go either way and two correct runs may differ there by 2*lr per
# step — the elementwise bound. Such elements are few: the update as a
# whole (p_K - p_0) must agree in relative L2.
DP_STEPS = 3
DP_UPDATE_REL_L2 = 0.25


class SmokeFailure(Exception):
    pass


# One clock for the whole log: the parent's start travels to the
# children in their job environment.
_T0_VAR = "CHIP_SMOKE_T0"
_T0 = time.time()


def _say(tag: str, msg: str) -> None:
    since = time.time() - float(os.environ.get(_T0_VAR, _T0))
    print(f"[{tag} +{since:.1f}s] {msg}", flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---- children: each owns the chip(s) for its lifetime ---------------


def _enter_child(env: dict):
    """First thing in every child, before jax is imported: the job
    environment a launcher would export, and the import paths."""
    os.environ.update(env)
    for path in (os.path.join(REPO, "examples"), REPO):
        if path not in sys.path:
            sys.path.insert(0, path)


class _CompileLog:
    """Backend compiles and persistent-cache traffic of this process,
    from jax's own monitoring events; the names of the programs the
    cache missed from the compiler's debug log."""

    def __init__(self):
        import logging

        import jax.monitoring

        self.backend_compile_s: list[float] = []
        self.pc_hits = 0
        self.missed: list[str] = []  # program names, one per miss
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )
        jax.monitoring.register_event_listener(self._on_event)
        handler = logging.Handler(level=logging.DEBUG)
        handler.emit = self._on_log
        compiler_log = logging.getLogger("jax._src.compiler")
        compiler_log.addHandler(handler)
        compiler_log.setLevel(logging.DEBUG)
        compiler_log.propagate = False

    def _on_log(self, record) -> None:
        if "CACHE MISS for" in record.msg:
            self.missed.append(str(record.args[0]))

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s.append(float(duration))

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.pc_hits += 1

    def long_compiles(self, floor: float = 0.5) -> list[float]:
        return [round(s, 2) for s in self.backend_compile_s if s >= floor]


class _CacheWarnings:
    """A compile/executable cache that fails on the chip must fail the
    smoke, not "fall back to the jitted path" unseen: collect every
    WARNING the two caches' modules log."""

    def __init__(self):
        import logging

        self.records: list[str] = []
        handler = logging.Handler(level=logging.WARNING)
        handler.emit = lambda rec: self.records.append(
            f"{rec.name}: {rec.getMessage()}"
        )
        for name in ("adaptdl_tpu.aot_cache", "adaptdl_tpu.trainer"):
            logging.getLogger(name).addHandler(handler)


def _device_report(tag: str, expect_platform: str, count: int) -> dict:
    import jax

    from adaptdl_tpu import flops

    devices = jax.devices()
    dev = devices[0]
    report = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }
    _say(tag, f"device {json.dumps(report)} jax {jax.__version__}")
    _check(
        dev.platform == expect_platform,
        f"platform is {dev.platform!r}, expected {expect_platform!r}",
    )
    _check(
        len(devices) == count,
        f"{len(devices)} devices, this phase needs {count}",
    )
    if expect_platform == "tpu":
        _check(
            flops.device_peak_flops(dev) is not None,
            f"device kind {dev.device_kind!r} is not in the peak table "
            "(adaptdl_tpu/flops.py)",
        )
    return report


def _build(config, tag: str):
    """Model, parameters (from a seed) and loss exactly as
    ``examples/transformer_lm.py --flash`` builds them; ``config`` None
    is the flagship preset, unreduced."""
    import dataclasses

    import transformer_lm as lm

    from adaptdl_tpu.models import init_transformer
    from adaptdl_tpu.models.pipeline_lm import (
        dense_lm_checkpoint_transforms,
    )

    if config is None:
        config = lm.lm_config(on_cpu=False)
    seq_len = config.max_seq_len
    config = dataclasses.replace(
        config, attention_fn=lm.flash_attention_fn(seq_len)
    )
    _say(
        tag,
        f"model layers={config.num_layers} d_model={config.d_model} "
        f"heads={config.num_heads} d_ff={config.d_ff} "
        f"vocab={config.vocab_size} seq={seq_len} "
        f"dtype={config.dtype.__name__} remat={config.remat} "
        f"attention=flash(block={min(128, seq_len)}) "
        f"init_batch={lm.INIT_BATCH_SIZE} max_batch={lm.MAX_BATCH_SIZE} "
        f"local_bounds={lm.LOCAL_BSZ_BOUNDS}",
    )
    model, params = init_transformer(config, seq_len=seq_len)
    return {
        "lm": lm,
        "config": config,
        "seq_len": seq_len,
        "params": params,
        "loss_fn": lm.dense_lm_loss(model),
        "transforms": dense_lm_checkpoint_transforms(config.num_layers),
    }


def _dataset(built, sequences: int):
    from _data import synthetic_tokens

    raw = synthetic_tokens(
        sequences, built["seq_len"], built["config"].vocab_size
    )["tokens"]
    return built["lm"].shifted(raw)


def _trainer_and_checkpoint(built, mesh=None):
    """The example's trainer with its canonical checkpoint transforms
    registered; returns (trainer, holder, ckpt) with holder["state"]
    initialised — ``checkpoint.load_state(ckpt)`` is the caller's."""
    trainer = built["lm"].make_trainer(
        built["loss_fn"], built["params"], mesh
    )
    holder = {"state": trainer.init_state()}
    save, load = built["transforms"]
    ckpt = trainer.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
        transform_save=save,
        transform_load=load,
    )
    return trainer, holder, ckpt


def _finite_gns(m) -> tuple[float, float]:
    """The pulled GNS moments by the estimator's own definitions
    (adaptdl_tpu.gns): the variance estimate is floored above zero, the
    squared-mean estimate is clamped AT zero — a noise-dominated
    reading, e.g. the differenced one-replica estimator after a few
    steps, is 0.0 and valid."""
    import math

    sqr, var = float(m["grad_sqr"]), float(m["grad_var"])
    _check(
        math.isfinite(sqr) and math.isfinite(var) and sqr >= 0 and var > 0,
        f"GNS moments out of range: sqr={sqr} var={var}",
    )
    return sqr, var


def _has_kernel(trainer, atomic: int, accum: int, state, batch) -> bool:
    from adaptdl_tpu.ops.flash_attention import MOSAIC_CALL

    step = trainer.train_step(atomic, accum)
    return MOSAIC_CALL in step._jitted.lower(state, batch, ()).as_text()


def _peak_memory(tag: str, expect_platform: str) -> None:
    """The number is required on the chip: a backend that reports no
    ``memory_stats()`` must not pass unseen as ``None``."""
    import jax

    if expect_platform != "tpu":
        _say(tag, f"peak memory: not reported on {expect_platform}")
        return
    stats = jax.devices()[0].memory_stats()
    _check(
        bool(stats) and "peak_bytes_in_use" in stats,
        f"memory_stats() has no peak_bytes_in_use: {stats!r}",
    )
    peak = stats["peak_bytes_in_use"]
    _say(
        tag,
        f"peak_bytes_in_use={peak} ({peak / 2**30:.2f} GiB of "
        f"{stats.get('bytes_limit', 0) / 2**30:.2f})",
    )


def _guarded(tag: str, conn, body) -> None:
    """Run a child's body; a failure is printed with its traceback,
    reported to the parent and turned into exit code 1. SystemExit (the
    graceful 143) passes through."""
    try:
        body()
    except Exception as exc:  # noqa: BLE001 - reported, then fatal
        traceback.print_exc()
        _say(tag, f"FAILED: {exc}")
        conn.send({"event": "error", "error": f"{tag}: {exc}"})
        sys.exit(1)


def _loop_until_sigterm(
    tag, conn, trainer, loader, holder, on_step, report
) -> None:
    """The user's training loop. ``on_step(n, m)`` runs after every
    step and returns True once the phase's checks are done; the child
    then tells the parent it is ready for SIGTERM and keeps training —
    the loader's exit agreement takes the checkpoint and raises
    SystemExit(143), at which point the final position is reported."""
    from adaptdl_tpu import _signal, epoch

    n = 0
    ready_at = None
    try:
        for _ in epoch.remaining_epochs_until(1):
            for batch in loader:
                holder["state"], m = trainer.run_step(
                    holder["state"], batch, loader
                )
                n += 1
                if ready_at is None:
                    if on_step(n, m):
                        ready_at = n
                        conn.send({"event": "ready", **report})
                        _say(tag, f"ready for SIGTERM after step {n}")
                elif n > ready_at + 500:
                    raise SmokeFailure("SIGTERM never arrived")
        raise SmokeFailure("the epoch ended before SIGTERM arrived")
    except SystemExit as exit_:
        import jax

        final = {
            "exit_code": exit_.code,
            "signalled": _signal.get_exit_flag(),
            "step": int(holder["state"].step),
            "epoch": int(loader.sampler.epoch),
            "index": int(loader.sampler.index),
            "atomic_bsz": loader.current_atomic_bsz,
            "accum_steps": loader.current_accum_steps,
            "last_loss": float(m["loss"]),
            "progress": float(jax.device_get(holder["state"].progress)),
        }
        _say(tag, f"exiting {exit_.code}: {json.dumps(final)}")
        conn.send({"event": "exit", **final})
        raise


def incarnation0(
    conn,
    env: dict,
    config=None,
    expect_platform: str = "tpu",
    sequences: int = 65536,
    ready_after: int = 60,
) -> None:
    """``ADAPTDL_NUM_RESTARTS=0``: train from scratch through at least
    one metrics pull, one goodput fit and one batch-size
    re-optimisation, execute an accumulated step program, then train
    on until SIGTERM ends the incarnation with a checkpoint and 143."""
    tag = "inc0"
    _enter_child(env)

    def body():
        import math

        import jax

        import adaptdl_tpu
        from adaptdl_tpu import checkpoint, metrics, trace

        compiles = _CompileLog()
        cache_warnings = _CacheWarnings()
        report = {"device": _device_report(tag, expect_platform, 1)}
        adaptdl_tpu.initialize_job()
        _say(
            tag,
            "compile cache dir: "
            f"{jax.config.jax_compilation_cache_dir}",
        )
        built = _build(config, tag)
        trainer, holder, ckpt = _trainer_and_checkpoint(built)
        _check(
            not checkpoint.load_state(ckpt),
            "incarnation 0 found a checkpoint to restore",
        )
        metrics.ensure_checkpoint_registered()
        dataset = _dataset(built, sequences)
        loader = built["lm"].make_loader(dataset)
        kernel_expected = expect_platform == "tpu"
        seen_configs: dict[tuple, int] = {}
        pulled: list[float] = []
        timed: list[float] = []
        marks: dict = {}
        window = max(ready_after - 39, 2)  # steps 21..30 of 60

        def on_step(n, m):
            cfg = (loader.current_atomic_bsz, loader.current_accum_steps)
            if cfg not in seen_configs:
                jax.block_until_ready(m["loss"])
                seen_configs[cfg] = n
                _say(
                    tag,
                    f"step {n}: first step at (atomic, accum)={cfg} "
                    f"loss={float(m['loss']):.4f}; backend compiles "
                    f"so far (s >= 0.5): {compiles.long_compiles()}",
                )
            if n == 1:
                marks["first_loss"] = float(m["loss"])
            if n % trainer.metrics_every == 1 or n == 1:
                # The step on which run_step pulled loss and GNS
                # moments to the host: already synchronised.
                loss = float(m["loss"])
                _check(math.isfinite(loss), f"step {n}: loss {loss}")
                pulled.append(loss)
            if 0 <= n - window < 10 and len(seen_configs) == 1:
                # Steady step time: host clock around a step that
                # ends in block_until_ready (the window's first
                # reading drains the queue and is dropped).
                jax.block_until_ready(m["loss"])
                now = time.monotonic()
                if n > window:
                    timed.append(now - marks["t_prev"])
                marks["t_prev"] = now
            if n == ready_after - 15:
                # One fit now, synchronously, on the profiles measured
                # so far (the cadence-driven fits run on a thread).
                t0 = time.monotonic()
                metrics.fit_and_report_now()
                perf = metrics.current_state().perf_params
                _check(perf is not None, "goodput fit produced nothing")
                _check(
                    all(math.isfinite(v) for v in perf),
                    f"goodput fit not finite: {perf}",
                )
                _say(
                    tag,
                    f"step {n}: goodput fit in "
                    f"{time.monotonic() - t0:.2f}s on "
                    f"{len(metrics.current_state().profile)} profiled "
                    f"configuration(s): {dict(perf._asdict())}",
                )
                marks["fit"] = True
            if n < ready_after:
                return False
            # ---- the loader's cadence has re-optimised by now ------
            _check(marks.get("fit", False), "no goodput fit ran")
            _check(
                metrics.get_goodput_fn() is not None,
                "no goodput function at the re-optimisation",
            )
            _check(len(pulled) >= 2, f"metrics pulls: {pulled}")
            _check(bool(timed), "no steady step was timed")
            sqr, var = _finite_gns(m)
            accumulated = [c for c in seen_configs if c[1] >= 1]
            if len(seen_configs) > 1:
                _say(
                    tag,
                    "batch-size policy changed the configuration "
                    f"inside the loop: {list(seen_configs)}",
                )
            else:
                _say(
                    tag,
                    "batch-size policy re-optimised and KEPT "
                    f"{cfg} (goodput model fitted, no candidate "
                    "beat it by the 5% threshold)",
                )
            state = holder["state"]
            batch = trainer.shard_batch(
                {k: v[: cfg[0] * (cfg[1] + 1)] for k, v in dataset.items()}
            )
            if kernel_expected:
                _check(
                    _has_kernel(trainer, *cfg, state, batch),
                    "the lowered step has no Mosaic custom call: the "
                    "flash kernel was interpreted or replaced",
                )
                _say(tag, "Mosaic custom call present in the lowered step")
            report.update(
                steps=n,
                first_loss=marks["first_loss"],
                loss=float(m["loss"]),
                step_time_s=sorted(timed)[len(timed) // 2],
                grad_sqr=sqr,
                grad_var=var,
            )
            _say(
                tag,
                f"steps={n} first_loss={marks['first_loss']:.4f} "
                f"last_loss={float(m['loss']):.4f} pulled={pulled} "
                f"steady_step_s(median of {len(timed)})="
                f"{report['step_time_s']:.4f} readings={timed} "
                f"grad_sqr={sqr:.4g} grad_var={var:.4g} "
                f"progress={float(m['progress']):.2f}",
            )
            if not accumulated:
                # Run one accumulated configuration explicitly so the
                # lax.scan accumulation path executes either way. The
                # parent's SIGTERM is on its way while this compiles:
                # the signal must wait for the compile and still end
                # in a complete checkpoint.
                conn.send({"event": "ready", **report})
                _say(tag, "ready for SIGTERM (accumulated compile next)")
                acc = (cfg[0], 1)
                rows = 2 * acc[0]
                batch2 = trainer.shard_batch(
                    {k: v[-rows:] for k, v in dataset.items()}
                )
                t0 = time.monotonic()
                step2 = trainer.train_step(*acc)
                holder["state"], m2 = step2(holder["state"], batch2)
                jax.block_until_ready(m2["loss"])
                took = time.monotonic() - t0
                loss2 = float(m2["loss"])
                _check(math.isfinite(loss2), f"accumulated loss {loss2}")
                seen_configs[acc] = n
                from adaptdl_tpu import _signal

                _say(
                    tag,
                    f"explicit accumulated step (atomic, accum)={acc} "
                    f"loss={loss2:.4f} in {took:.2f}s incl. compile; "
                    "SIGTERM landed during it: "
                    f"{_signal.get_exit_flag()}",
                )
            _check(
                len(seen_configs) >= 2
                and any(c[1] >= 1 for c in seen_configs),
                f"step programs executed: {list(seen_configs)}",
            )
            _say(
                tag,
                f"step programs executed: {list(seen_configs)}; "
                f"backend compiles (s >= 0.5): {compiles.long_compiles()}; "
                f"persistent cache hits={compiles.pc_hits} "
                f"misses={len(compiles.missed)}; aot spans: "
                + json.dumps(_aot_spans(trace)),
            )
            _peak_memory(tag, expect_platform)
            _check(
                not cache_warnings.records,
                f"cache warnings: {cache_warnings.records}",
            )
            return True

        # on_step may have told the parent itself (before the
        # accumulated compile); the loop's own "ready" is then a
        # harmless repeat the parent ignores.
        _loop_until_sigterm(
            tag, conn, trainer, loader, holder, on_step, report
        )

    _guarded(tag, conn, body)


def _aot_spans(trace) -> list[dict]:
    return [
        {
            "name": rec["name"],
            "s": round(rec.get("dur", 0.0), 3),
            **{
                k: v
                for k, v in (rec.get("attrs") or {}).items()
                if k in ("hit", "persistent_cache_hit")
            },
        }
        for rec in trace.snapshot_spans()
        if rec.get("name") in ("aot.lookup", "aot.compile")
    ]


def incarnation1(
    conn,
    env: dict,
    prev: dict,
    spawned_at: float,
    config=None,
    expect_platform: str = "tpu",
    sequences: int = 65536,
    steps: int = 12,
    replicas: int = 1,
    devices: int = 1,
    same_topology: bool = True,
) -> None:
    """``ADAPTDL_NUM_RESTARTS=1`` in a fresh process on the same
    checkpoint directory: everything must continue from ``prev`` (the
    predecessor's report at its exit) — through warm caches when the
    topology is the predecessor's (another mesh is another program)."""
    tag = f"inc1.dp{replicas}"
    _enter_child(env)

    def body():
        import math

        import jax

        import adaptdl_tpu
        from adaptdl_tpu import checkpoint, epoch, metrics, trace

        compiles = _CompileLog()
        cache_warnings = _CacheWarnings()
        report = {"device": _device_report(tag, expect_platform, devices)}
        adaptdl_tpu.initialize_job()
        built = _build(config, tag)
        trainer, holder, ckpt = _trainer_and_checkpoint(built)
        _check(trainer.num_replicas == replicas, f"mesh {trainer.mesh}")
        t0 = time.monotonic()
        _check(checkpoint.load_state(ckpt), "load_state found nothing")
        restore_s = time.monotonic() - t0
        metrics.ensure_checkpoint_registered()
        dataset = _dataset(built, sequences)
        loader = built["lm"].make_loader(dataset)
        step0 = int(holder["state"].step)
        position = (int(loader.sampler.epoch), int(loader.sampler.index))
        restored_cfg = (
            loader.current_atomic_bsz, loader.current_accum_steps
        )
        _say(
            tag,
            f"restored in {restore_s:.2f}s: step={step0} "
            f"position={position} (atomic, accum)={restored_cfg} "
            f"progress={float(holder['state'].progress):.2f}; "
            f"predecessor left step={prev['step']} "
            f"position={(prev['epoch'], prev['index'])} "
            f"(atomic, accum)={(prev['atomic_bsz'], prev['accum_steps'])}",
        )
        _check(step0 == prev["step"] and step0 > 0, "state.step")
        _check(
            position == (prev["epoch"], prev["index"]) and position[1] > 0,
            "loader position",
        )
        _check(
            restored_cfg == (prev["atomic_bsz"], prev["accum_steps"]),
            "batch-size decision",
        )
        _check(
            math.isclose(
                float(holder["state"].progress), prev["progress"],
                rel_tol=1e-6,
            ),
            "scale-invariant progress",
        )
        on_all = {
            shard.device
            for leaf in jax.tree.leaves(holder["state"].params)
            for shard in leaf.addressable_shards
        }
        _check(
            on_all == set(trainer.mesh.devices.flat),
            f"restored parameters live on {on_all}",
        )
        def first_step(m):
            """Where the restart's first step program came from, and
            how far its loss is from where the predecessor stopped."""
            loss = float(m["loss"])  # synchronises: the step is done
            to_first_step = time.time() - spawned_at
            spans = _aot_spans(trace)
            aot_hit = any(
                s["name"] == "aot.lookup" and s.get("hit") for s in spans
            )
            pc_served = any(
                s["name"] == "aot.compile"
                and s.get("persistent_cache_hit")
                for s in spans
            )
            source = (
                "the AOT executable cache (no trace, no compile)"
                if aot_hit
                else "the persistent compile cache"
                if pc_served
                else "a FRESH compile"
            )
            _say(
                tag,
                f"process start -> first completed step: "
                f"{to_first_step:.1f}s; first step program came from "
                f"{source}; persistent cache hits={compiles.pc_hits} "
                f"misses={len(compiles.missed)} {compiles.missed}; "
                f"backend compiles (s >= 0.5): "
                f"{compiles.long_compiles()}; aot spans: "
                f"{json.dumps(spans)}",
            )
            if same_topology:
                _check(
                    aot_hit or pc_served,
                    "the first step was compiled afresh",
                )
                _check(
                    compiles.pc_hits > 0,
                    "the persistent compile cache was never hit",
                )
            gap = abs(loss - prev["last_loss"])
            _say(
                tag,
                f"first loss {loss:.4f} vs predecessor's last "
                f"{prev['last_loss']:.4f} (|gap| {gap:.4f}, band "
                f"{LOSS_BAND}); predecessor's first "
                f"{prev['first_loss']:.4f}",
            )
            _check(
                math.isfinite(loss) and gap <= LOSS_BAND,
                f"loss gap {gap:.4f} > {LOSS_BAND}",
            )
            _check(
                gap < abs(loss - prev["first_loss"]),
                "the resumed loss is nearer the predecessor's FIRST "
                "loss than its last: the weights did not come back",
            )

        n = 0
        for _ in epoch.remaining_epochs_until(1):
            for batch in loader:
                holder["state"], m = trainer.run_step(
                    holder["state"], batch, loader
                )
                n += 1
                if n == 1:
                    first_step(m)
                if n >= steps:
                    break
            break
        loss_n = float(m["loss"])
        _say(tag, f"after {n} steps: loss {loss_n:.4f}")
        _check(math.isfinite(loss_n), f"loss {loss_n}")
        _check(
            int(holder["state"].step) == step0 + n,
            f"state.step {int(holder['state'].step)} != {step0} + {n}",
        )
        _finite_gns(m)
        _peak_memory(tag, expect_platform)
        _check(
            not cache_warnings.records,
            f"cache warnings: {cache_warnings.records}",
        )
        conn.send({"event": "done", **report})

    _guarded(tag, conn, body)


def dp4_incarnation(
    conn,
    env: dict,
    config=None,
    expect_platform: str = "tpu",
    sequences: int = 65536,
    devices: int = 4,
    ready_after: int = 12,
) -> None:
    """One process, four chips: the default ``{"data": 4}`` mesh
    against a one-device mesh on the same seeded batches, proof that
    batch and gradient mean really span the chips, then the elastic
    loop at dp=4 until SIGTERM."""
    tag = "dp4"
    _enter_child(env)

    def body():
        import jax
        import numpy as np

        import adaptdl_tpu
        from adaptdl_tpu import checkpoint, metrics
        from adaptdl_tpu.parallel import create_mesh

        report = {"device": _device_report(tag, expect_platform, devices)}
        adaptdl_tpu.initialize_job()
        built = _build(config, tag)
        lm = built["lm"]
        dataset = _dataset(built, sequences)
        trainer4, holder, ckpt = _trainer_and_checkpoint(built)
        _check(
            dict(trainer4.mesh.shape) == {"data": devices},
            f"default mesh is {dict(trainer4.mesh.shape)}",
        )
        trainer1 = lm.make_trainer(
            built["loss_fn"], built["params"],
            create_mesh(devices=jax.devices()[:1]),
        )
        state1 = trainer1.init_state()
        p0 = jax.tree.map(np.asarray, trainer4.params_tree(holder["state"]))
        bsz = lm.INIT_BATCH_SIZE  # global batch: scaling gain 1 both arms
        step4 = trainer4.train_step(bsz // devices, 0)
        step1 = trainer1.train_step(bsz, 0)
        rng = np.random.default_rng(0)
        for k in range(DP_STEPS):
            idx = rng.integers(0, sequences, size=bsz)
            host = {name: v[idx] for name, v in dataset.items()}
            batch4 = trainer4.shard_batch(host)
            if k == 0:
                spread = {
                    s.device for s in batch4["inputs"].addressable_shards
                }
                _check(
                    len(spread) == devices,
                    f"the sharded batch sits on {spread}",
                )
                if expect_platform == "tpu":
                    _check(
                        _has_kernel(
                            trainer4, bsz // devices, 0,
                            holder["state"], batch4,
                        ),
                        "no Mosaic custom call in the dp step",
                    )
            holder["state"], m4 = step4(holder["state"], batch4)
            state1, m1 = step1(state1, trainer1.shard_batch(host))
            l4, l1 = float(m4["loss"]), float(m1["loss"])
            _say(
                tag,
                f"step {k}: loss dp{devices}={l4:.5f} dp1={l1:.5f} "
                f"rel diff {abs(l4 - l1) / abs(l1):.2e}",
            )
            _check(
                abs(l4 - l1) <= DP_LOSS_RTOL * abs(l1),
                f"per-step loss differs beyond {DP_LOSS_RTOL}",
            )
        # GNS: finite and in range in both arms, NOT equal — dp=1 has
        # one gradient sample a step and uses the differenced estimator.
        _say(
            tag,
            f"GNS (sqr, var): dp{devices}={_finite_gns(m4)} "
            f"dp1={_finite_gns(m1)}",
        )
        p4 = jax.tree.map(np.asarray, trainer4.params_tree(holder["state"]))
        p1 = jax.tree.map(np.asarray, trainer1.params_tree(state1))
        flat = lambda t: np.concatenate(  # noqa: E731
            [np.ravel(x).astype(np.float64) for x in jax.tree.leaves(t)]
        )
        f0, f1, f4 = flat(p0), flat(p1), flat(p4)
        max_abs = float(np.max(np.abs(f4 - f1)))
        rel_l2 = float(
            np.linalg.norm(f4 - f1) / np.linalg.norm(f1 - f0)
        )
        bound = 2 * DP_STEPS * lm.LEARNING_RATE * 1.05
        _say(
            tag,
            f"parameters after {DP_STEPS} steps: max|dp{devices} - dp1|="
            f"{max_abs:.3e} (bound {bound:.3e}); update rel-L2 "
            f"distance {rel_l2:.4f} (bound {DP_UPDATE_REL_L2})",
        )
        _check(max_abs <= bound, "parameters differ elementwise")
        _check(rel_l2 <= DP_UPDATE_REL_L2, "parameter updates differ")
        # The gradient mean spans the chips: an all-reduce in the
        # COMPILED step (served by the persistent cache — the program
        # has just run), and live bytes on every chip.
        host = {name: v[:bsz] for name, v in dataset.items()}
        compiled = step4._jitted.lower(
            holder["state"], trainer4.shard_batch(host), ()
        ).compile()
        _check(
            "all-reduce" in compiled.as_text(),
            "no all-reduce in the compiled dp step",
        )
        if expect_platform == "tpu":
            in_use = {
                str(d): (d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in jax.devices()
            }
            _say(tag, f"bytes_in_use per chip: {in_use}")
            _check(all(v > 0 for v in in_use.values()), "an idle chip")
        del state1, trainer1, p0, p1, p4, f0, f1, f4
        # ---- the elastic loop at dp=4, then SIGTERM ------------------
        _check(not checkpoint.load_state(ckpt), "found a checkpoint")
        metrics.ensure_checkpoint_registered()
        loader = lm.make_loader(dataset)
        marks = {}

        def on_step(n, m):
            if n == 1:
                marks["first_loss"] = float(m["loss"])
            if n < ready_after:
                return False
            report.update(
                steps=n, first_loss=marks["first_loss"],
                loss=float(m["loss"]),
            )
            return True

        _loop_until_sigterm(
            tag, conn, trainer4, loader, holder, on_step, report
        )

    _guarded(tag, conn, body)


def launched_worker(
    config=None, expect_platform: str = "tpu", steps: int = 30
) -> int:
    """The script as a launcher's worker (the launcher exported the
    job environment and holds no chip): the same preset through the
    same loop for a bounded number of steps, hints posted to the
    launcher's supervisor, then a clean exit the launcher reports as
    success. A SIGTERM from the launcher checkpoints and exits 143 as
    in any job; the relaunch resumes here."""
    tag = "worker"
    _enter_child({})
    try:
        import jax

        import adaptdl_tpu
        from adaptdl_tpu import checkpoint, env, epoch, metrics

        _device_report(tag, expect_platform, 1)
        adaptdl_tpu.initialize_job()
        built = _build(config, tag)
        trainer, holder, ckpt = _trainer_and_checkpoint(built)
        restored = checkpoint.load_state(ckpt)
        metrics.ensure_checkpoint_registered()
        loader = built["lm"].make_loader(_dataset(built, 65536))
        _say(
            tag,
            f"restarts={env.num_restarts()} restored={restored} "
            f"step={int(holder['state'].step)} supervisor="
            f"{env.supervisor_url()}",
        )
        n = 0
        for _ in epoch.remaining_epochs_until(1):
            for batch in loader:
                holder["state"], m = trainer.run_step(
                    holder["state"], batch, loader
                )
                n += 1
                if n >= steps:
                    break
            break
        jax.block_until_ready(m["loss"])
        metrics.fit_and_report_now()  # one hint post, synchronously
        _finite_gns(m)
        _say(
            tag,
            f"TRAINED steps={n} step={int(holder['state'].step)} "
            f"loss={float(m['loss']):.4f}",
        )
        return 0
    except SmokeFailure as exc:
        _say(tag, f"FAILED: {exc}")
        return 1


# ---- parent: never imports jax --------------------------------------


def _job_env(ckpt_dir: str, restarts: int, replicas: int | None) -> dict:
    env = {
        _T0_VAR: repr(_T0),
        "ADAPTDL_CHECKPOINT_PATH": ckpt_dir,
        "ADAPTDL_NUM_RESTARTS": str(restarts),
        # Refit every couple of seconds instead of every 30: the whole
        # incarnation lasts about a minute.
        "ADAPTDL_FIT_INTERVAL": "2",
    }
    if replicas is not None:
        env["ADAPTDL_NUM_REPLICAS"] = str(replicas)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # The checkpoint directory is thrown away, and the cache's path
        # is part of its key: name the checkout's fixed directory
        # through the existing knob (bootstrap appends
        # ".jax_compile_cache").
        env["ADAPTDL_COMPILE_CACHE"] = os.environ.get(
            "ADAPTDL_COMPILE_CACHE", REPO
        )
    return env


def _run_child(target, kwargs, *, sigterm_on_ready: bool, timeout: float):
    """Start one child, relay its reports, deliver SIGTERM when it says
    it is ready, and never leave it running. Returns (exit code,
    {event: message})."""
    ctx = multiprocessing.get_context("spawn")
    reader, writer = ctx.Pipe(duplex=False)
    proc = ctx.Process(target=target, args=(writer,), kwargs=kwargs)
    proc.start()
    writer.close()
    events: dict[str, dict] = {}
    deadline = time.monotonic() + timeout
    try:
        while True:
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"{target.__name__} exceeded {timeout:.0f}s"
                )
            if not reader.poll(1.0):
                continue
            try:
                msg = reader.recv()
            except EOFError:
                break  # every write end closed: the child is gone
            first_ready = (
                msg["event"] == "ready" and "ready" not in events
            )
            events.setdefault(msg["event"], msg)
            if first_ready and sigterm_on_ready:
                os.kill(proc.pid, signal.SIGTERM)
        proc.join(timeout=60)
    finally:
        if proc.is_alive():
            proc.kill()
            proc.join()
        reader.close()
    return proc.exitcode, events


def _expect_graceful_exit(name: str, code, events, ckpt_dir: str) -> dict:
    if "error" in events:
        raise SmokeFailure(events["error"]["error"])
    _check(
        code == GRACEFUL_EXIT_CODE,
        f"{name} exited {code}, expected {GRACEFUL_EXIT_CODE}",
    )
    _check("ready" in events and "exit" in events, f"{name}: {events}")
    _check(events["exit"]["signalled"], f"{name} exited unsignalled")
    manifests = [
        os.path.join(ckpt_dir, d, "manifest.json")
        for d in sorted(os.listdir(ckpt_dir))
        if d.startswith("checkpoint-")
    ]
    _check(bool(manifests), f"no checkpoint-* under {ckpt_dir}")
    with open(manifests[-1], encoding="utf-8") as f:
        manifest = json.load(f)
    _check(
        "elastic_trainer" in manifest.get("states", {}),
        f"incomplete manifest {manifests[-1]}: {manifest}",
    )
    _say(
        "smoke",
        f"{name} exited {code}; {manifests[-1]} lists "
        f"{sorted(manifest['states'])}",
    )
    return {**events["ready"], **events["exit"]}


def _expect_done(name: str, code, events) -> dict:
    if "error" in events:
        raise SmokeFailure(events["error"]["error"])
    _check(code == 0 and "done" in events, f"{name} exited {code}")
    return events["done"]


def run_elastic_loop(
    config=None,
    expect_platform: str = "tpu",
    timeout: float = 1000.0,
    sequences: int = 65536,
    ready_after: int = 60,
    steps: int = 12,
) -> dict:
    """Phases 1 and 2 on one chip. ``config`` (a TransformerConfig) and
    the step counts shrink the run for the CPU test; the command line
    offers neither."""
    ckpt_dir = tempfile.mkdtemp(prefix="chip-smoke-ckpt-")
    try:
        code, events = _run_child(
            incarnation0,
            dict(
                env=_job_env(ckpt_dir, 0, None),
                config=config,
                expect_platform=expect_platform,
                sequences=sequences,
                ready_after=ready_after,
            ),
            sigterm_on_ready=True,
            timeout=timeout,
        )
        prev = _expect_graceful_exit("incarnation 0", code, events, ckpt_dir)
        code, events = _run_child(
            incarnation1,
            dict(
                env=_job_env(ckpt_dir, 1, None),
                prev=prev,
                spawned_at=time.time(),
                config=config,
                expect_platform=expect_platform,
                sequences=sequences,
                steps=steps,
            ),
            sigterm_on_ready=False,
            timeout=timeout,
        )
        done = _expect_done("incarnation 1", code, events)
        _check(
            done["device"] == prev["device"],
            "the incarnations saw different devices",
        )
        return prev["device"]
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def run_four_chips(
    config=None,
    expect_platform: str = "tpu",
    devices: int = 4,
    timeout: float = 1000.0,
    sequences: int = 65536,
) -> dict:
    """Phase 3: dp=4 against dp=1, then save at dp=4 and restore at
    dp=2 in a fresh process — "resume at any replica count"."""
    ckpt_dir = tempfile.mkdtemp(prefix="chip-smoke-ckpt-")
    try:
        # The serialized-executable cache is phase 2's subject; with
        # it off the step that runs is the jitted one itself, whose
        # compiled text this phase reads back through the persistent
        # cache instead of compiling a twin.
        code, events = _run_child(
            dp4_incarnation,
            dict(
                env={**_job_env(ckpt_dir, 0, devices),
                     "ADAPTDL_AOT_CACHE": "off"},
                config=config,
                expect_platform=expect_platform,
                devices=devices,
                sequences=sequences,
            ),
            sigterm_on_ready=True,
            timeout=timeout,
        )
        prev = _expect_graceful_exit(
            f"dp={devices} incarnation", code, events, ckpt_dir
        )
        # The restored batch-size decision is per replica; at half the
        # replicas it no longer reaches the initial global batch, so
        # the loader re-decides on its first iteration. What must
        # carry over exactly is step, position and progress.
        code, events = _run_child(
            incarnation1,
            dict(
                env=_job_env(ckpt_dir, 1, devices // 2),
                prev=prev,
                spawned_at=time.time(),
                config=config,
                expect_platform=expect_platform,
                replicas=devices // 2,
                devices=devices,
                same_topology=False,
                sequences=sequences,
            ),
            sigterm_on_ready=False,
            timeout=timeout,
        )
        _expect_done(f"dp={devices // 2} restore", code, events)
        return prev["device"]
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the dp=4 phase, its dp=1 comparison and the "
        "4 -> 2 restore (needs four chips)",
    )
    args = parser.parse_args(argv)
    if os.environ.get("ADAPTDL_SUPERVISOR_URL"):
        return launched_worker()
    started = time.monotonic()
    device = None
    error = None
    try:
        if args.chips == 4:
            device = run_four_chips()
        else:
            device = run_elastic_loop()
        _check(device["platform"] == "tpu", f"not a TPU: {device}")
    except SmokeFailure as exc:
        error = str(exc)
    except Exception as exc:  # noqa: BLE001 - reported as not ok
        traceback.print_exc()
        error = f"{type(exc).__name__}: {exc}"
    _say("smoke", f"wall time {time.monotonic() - started:.1f}s")
    if error is not None:
        _say("smoke", f"FAILED: {error}")
        print(json.dumps({"ok": False, "error": error, "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
