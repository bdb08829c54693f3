"""Benchmark: elastic goodput retention on the CIFAR ResNet-18 config.

Measures the BASELINE.md north-star metric on real hardware: goodput
(statistical efficiency x samples/s) of the *adaptive* batch-size path
relative to the fixed-allocation baseline on the same chip(s). The
fixed run (batch 128, the reference CIFAR config:
examples/pytorch-cifar/main.py + tests/short-workload/
resnet18-cifar10.sh) is the denominator; the adaptive run lets the
goodput model pick (atomic_bsz, accum_steps) up to 4096 with local
bounds (64, 1024).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
vs_baseline is the ratio against the fixed-allocation goodput (the
self-generated baseline; the reference publishes no numbers —
BASELINE.md). >= 0.90 meets the north-star; > 1.0 means the adaptive
policy beats fixed allocation outright. Extra keys on the same line:
``platform`` / ``device_kind`` / ``device_count`` exactly as jax
reports them, ``transformer_tokens_per_s`` (steady-state causal-LM
throughput), and ``rescale_p50_s`` (median checkpoint-save -> restore
-> first-step latency, the elastic rescale cost).

One process, one device owner: the bench never starts a child that
touches the device (a chip serves one process at a time). It measures
on a TPU only — without ``--quick`` any other platform is refused
with a non-zero exit, never measured under a device metric's name.
``--quick`` is the explicit tiny preset the tests run on the CPU; a
missing chip never selects it. A phase that raises is named in
``failed_phases`` on the JSON line and makes the exit code non-zero.
All phases run against an internal deadline (``BENCH_BUDGET_SECONDS``),
shedding the optional metrics first and degrading step counts second.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

_START = time.monotonic()
_BUDGET = float(os.environ.get("BENCH_BUDGET_SECONDS", "480"))
# Primary metric, buffered as soon as it exists: if the watchdog fires
# during an optional bench, the handler prints this (and exits
# non-zero) instead of losing the already-measured number.
_PRIMARY_RESULT: dict | None = None
# Phases that raised (or overran the watchdog): named on the JSON line,
# and any entry makes the exit code non-zero.
_FAILED_PHASES: list[str] = []


def _remaining() -> float:
    return _BUDGET - (time.monotonic() - _START)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _run_phase(name: str, min_remaining: float, fn, *args, **kwargs):
    """One optional phase. Budget pressure skips it (returns None); a
    phase that raises is logged with its traceback and recorded in
    ``_FAILED_PHASES`` — reported and fatal to the exit code, never
    swallowed."""
    if _remaining() <= min_remaining:
        return None
    try:
        return fn(*args, **kwargs)
    except Exception:  # noqa: BLE001 - reported via failed_phases
        _log(f"{name} bench failed:\n{traceback.format_exc()}")
        _FAILED_PHASES.append(name)
        return None


def _use_fixed_compile_cache() -> None:
    """One persistent compile cache for the run: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else the checkout's fixed
    directory named through the existing knob — never beside the
    throw-away checkpoint directories the rescale phases create (the
    path is part of the cache key, so a directory that moves never
    hits)."""
    from adaptdl_tpu import env
    from adaptdl_tpu.bootstrap import _enable_compilation_cache

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ.setdefault(
            "ADAPTDL_COMPILE_CACHE", env.checkout_root()
        )
    _enable_compilation_cache()


def _make_dataset(n: int, image_size: int, num_classes: int = 10):
    rng = np.random.default_rng(0)
    templates = rng.normal(
        size=(num_classes, image_size, image_size, 3)
    ).astype(np.float32)
    labels = rng.integers(0, num_classes, size=n)
    images = 0.5 * templates[labels] + rng.normal(
        size=(n, image_size, image_size, 3)
    ).astype(np.float32)
    return {"image": images, "label": labels.astype(np.int32)}


def _steady_state_time(state, step_fn, batch, steps: int):
    """Amortized per-step wall-clock: dispatch the whole window and
    block once. Per-step host syncs would measure the host round-trip,
    not the device; real training keeps the dispatch queue full
    exactly like this."""
    state, times, m = _steady_state_windows(
        state, step_fn, batch, steps, windows=1
    )
    return state, times[0], m


def _steady_state_windows(
    state, step_fn, batch, steps: int, windows: int = 3
):
    """Per-step time measured over ``windows`` independent dispatch
    windows — the retention ratio is built from medians and reported
    with the window spread, so a one-off scheduler hiccup on the
    shared host can't swing the headline metric by itself (the r3->r4
    1.07 -> 0.94 swing was measurement noise, not a regression)."""
    import jax

    state, m = step_fn(state, batch)  # compile + warmup
    jax.block_until_ready(m["loss"])
    times = []
    for _ in range(windows):
        start = time.monotonic()
        for _ in range(steps):
            state, m = step_fn(state, batch)
        jax.block_until_ready(m["loss"])
        times.append((time.monotonic() - start) / steps)
    return state, times, m


def _bench_convergence(on_tpu: bool, full: bool) -> dict | None:
    """REALIZED statistical efficiency: epochs to a fixed train
    accuracy under the elastic autoscale schedule vs the fixed batch
    size — measured by actually training both arms, not by the
    goodput model's efficiency prediction (the reference's autobsz
    claim, docs/README.rst:68-80, is exactly this comparison).

    Same model init, same data, same seed everywhere; the only
    difference is the batch-size schedule (fixed init_bsz vs the
    goodput-driven autoscale with AdaScale LR compensation)."""
    import jax
    import jax.numpy as jnp
    import optax

    from adaptdl_tpu import epoch as epoch_mod
    from adaptdl_tpu import metrics
    from adaptdl_tpu.data import AdaptiveDataLoader
    from adaptdl_tpu.models import cnn_loss_fn, init_cnn
    from adaptdl_tpu.scaling_rules import AdaScale
    from adaptdl_tpu.trainer import ElasticTrainer

    image_size = 16 if full else 8
    n = 2048 if full else 512
    init_bsz = 32
    max_bsz = 512 if full else 128
    target_acc = 0.85
    max_epochs = 30 if full else 25
    dataset = _make_dataset(n, image_size, num_classes=10)
    model, params = init_cnn(
        image_size=image_size,
        channels=3,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )

    @jax.jit
    def accuracy(p):
        logits = model.apply(
            {"params": p}, dataset["image"], train=False
        )
        return (logits.argmax(-1) == dataset["label"]).mean()

    def run_arm(adaptive: bool) -> int | None:
        """Epochs until train accuracy >= target (None: never)."""
        metrics._reset_state()
        epoch_mod._reset_state()  # arms are independent logical jobs
        trainer = ElasticTrainer(
            loss_fn=cnn_loss_fn(model),
            params=params,
            optimizer=optax.sgd(0.1, momentum=0.9),
            init_batch_size=init_bsz,
            scaling_rule=AdaScale(),
        )
        state = trainer.init_state()
        loader = AdaptiveDataLoader(
            dataset, batch_size=init_bsz,
            name=f"bench-conv-{'a' if adaptive else 'f'}",
        )
        if adaptive:
            loader.autoscale_batch_size(
                max_bsz,
                local_bsz_bounds=(16, 256),
                gradient_accumulation=True,
            )
            loader._reoptimize_every = 5
        epochs_done = 0
        for e in epoch_mod.remaining_epochs_until(max_epochs):
            for host_batch in loader:
                state, _ = trainer.run_step(state, host_batch, loader)
            epochs_done = e + 1
            if float(accuracy(trainer.params_tree(state))) >= target_acc:
                return epochs_done
            if _remaining() < 60:
                _log("convergence: budget pressure — stopping arm")
                return None
        return None

    fixed_epochs = run_arm(adaptive=False)
    adaptive_epochs = (
        run_arm(adaptive=True) if _remaining() > 90 else None
    )
    _log(
        f"convergence: target={target_acc} "
        f"fixed_epochs={fixed_epochs} adaptive_epochs={adaptive_epochs}"
    )
    out: dict = {"convergence_target_acc": target_acc}
    if fixed_epochs is not None:
        out["epochs_to_target_fixed"] = fixed_epochs
    if adaptive_epochs is not None:
        out["epochs_to_target_adaptive"] = adaptive_epochs
    if fixed_epochs is not None and adaptive_epochs is not None:
        # >= 1.0: the elastic schedule converged in no more epochs
        # than fixed batch — realized statistical efficiency held.
        out["convergence_ratio_fixed_over_adaptive"] = round(
            fixed_epochs / adaptive_epochs, 3
        )
    return out or None


def _bench_transformer_tokens(on_tpu: bool, full: bool) -> dict | None:
    """Steady-state causal-LM training throughput: tokens/s and MFU.

    Full mode runs a GPT-2-medium-class shape (d=1024, 8 layers,
    seq 1024) — big enough that the MXU, not dispatch overhead, sets
    the step time, so the MFU figure means something.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from adaptdl_tpu.flops import mfu as mfu_fn
    from adaptdl_tpu.flops import transformer_train_flops
    from adaptdl_tpu.models import TransformerConfig, init_transformer
    from adaptdl_tpu.trainer import ElasticTrainer

    seq_len = 1024 if full else 32
    cfg = TransformerConfig(
        vocab_size=32000 if full else 256,
        num_layers=8 if full else 2,
        num_heads=16 if full else 2,
        d_model=1024 if full else 32,
        d_ff=4096 if full else 64,
        max_seq_len=seq_len,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        # Remat trades FLOPs for HBM — the right trade on TPU, a pure
        # slowdown for the --quick CPU preset where memory isn't
        # scarce. The knob is reported in the JSON so lines stay
        # comparable.
        remat=on_tpu,
    )
    model, params = init_transformer(cfg, seq_len=seq_len)

    def loss_fn(p, batch, rng):
        logits = model.apply(
            {"params": p}, batch["inputs"], train=True, rng=rng
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["targets"]
        ).mean()

    def peak_hbm_gb() -> float | None:
        stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
        if stats and "peak_bytes_in_use" in stats:
            return round(stats["peak_bytes_in_use"] / 2**30, 3)
        return None

    def run_arm(arm_loss, bsz):
        trainer = ElasticTrainer(
            loss_fn=arm_loss,
            params=params,
            optimizer=optax.adamw(3e-4),
            init_batch_size=bsz,
        )
        state = trainer.init_state()
        rng = np.random.default_rng(3)
        tokens = rng.integers(
            0, cfg.vocab_size, size=(bsz, seq_len + 1)
        )
        batch = trainer.shard_batch(
            {
                "inputs": tokens[:, :-1].astype(np.int32),
                "targets": tokens[:, 1:].astype(np.int32),
            }
        )
        step_fn = trainer.train_step(bsz // trainer.num_replicas, 0)
        steps = 20 if full else 3
        _, t_step, _ = _steady_state_time(state, step_fn, batch, steps)
        return bsz * seq_len / t_step, t_step

    bsz = 8
    out = {}
    # Chunked-head arm FIRST (TPU full mode only): the row-streaming
    # loss (ops/chunked_xent.py) removes the [tokens, vocab] logits
    # buffer. peak_bytes_in_use is a cumulative process-wide
    # high-water mark, so the smaller arm must run before the dense
    # arm for its peak reading to mean anything (earlier resnet phases
    # peak well below either arm).
    peak_chunked = None
    if full and _remaining() > 150:
        from adaptdl_tpu.ops.chunked_xent import chunked_softmax_xent

        def chunked_loss(p, batch, rng):
            hidden = model.apply(
                {"params": p}, batch["inputs"], train=True, rng=rng,
                return_hidden=True,
            )
            flat = hidden.reshape(-1, hidden.shape[-1])
            return chunked_softmax_xent(
                flat,
                p["embed"]["embedding"],
                batch["targets"].reshape(-1),
                1024,  # rows a chunk: the live logits are 1/8 of dense
            ).mean()

        try:
            chunked_tps, t_chunked = run_arm(chunked_loss, bsz)
            peak_chunked = peak_hbm_gb()
            _log(
                f"transformer chunked-xent: step={t_chunked*1e3:.1f}ms "
                f"tokens/s={chunked_tps:.0f} peak_hbm_gb={peak_chunked}"
            )
            out["transformer_chunked_xent_tokens_per_s"] = round(
                chunked_tps, 1
            )
            if peak_chunked is not None:
                out["transformer_chunked_xent_peak_hbm_gb"] = (
                    peak_chunked
                )
        except Exception:  # noqa: BLE001 - reported via failed_phases
            _log(f"chunked-xent arm failed:\n{traceback.format_exc()}")
            _FAILED_PHASES.append("transformer_chunked_xent")

    # zero3_blocks arm (TPU full mode): the per-layer-FSDP flagship's
    # steady-state tokens/s on the same shape — prices the per-block
    # gather/reduce-scatter schedule against the dense replicated arm.
    if full and on_tpu and _remaining() > 180:
        try:
            from adaptdl_tpu.models import init_zero3_lm

            z_loss, z_params = init_zero3_lm(cfg, seq_len=seq_len)
            z_trainer = ElasticTrainer(
                loss_fn=z_loss,
                params=z_params,
                optimizer=optax.adamw(3e-4),
                init_batch_size=bsz,
                zero3_blocks="blocks",
            )
            z_state = z_trainer.init_state()
            rngz = np.random.default_rng(13)
            z_tokens = rngz.integers(
                0, cfg.vocab_size, size=(bsz, seq_len + 1)
            ).astype(np.int32)
            z_batch = z_trainer.shard_batch({"tokens": z_tokens})
            z_step = z_trainer.train_step(
                bsz // z_trainer.num_replicas, 0
            )
            _, t_z, _ = _steady_state_time(z_state, z_step, z_batch, 10)
            out["transformer_z3b_tokens_per_s"] = round(
                bsz * seq_len / t_z, 1
            )
            _log(
                f"transformer z3b: step={t_z*1e3:.1f}ms "
                f"tokens/s={bsz*seq_len/t_z:.0f}"
            )
        except Exception:  # noqa: BLE001 - reported via failed_phases
            _log(f"z3b transformer arm failed:\n{traceback.format_exc()}")
            _FAILED_PHASES.append("transformer_z3b")

    tokens_per_s, t_step = run_arm(loss_fn, bsz)
    flops = transformer_train_flops(cfg, bsz, seq_len)
    mfu_val = mfu_fn(
        flops.total, t_step, num_devices=len(jax.devices())
    )
    # Valid as the dense arm's peak only if it exceeds the chunked
    # arm's (expected: the dense head's logits dominate); otherwise
    # the high-water mark belongs to the chunked arm — don't claim it.
    peak_dense = peak_hbm_gb()
    if (
        peak_dense is not None
        and peak_chunked is not None
        and peak_dense <= peak_chunked
    ):
        peak_dense = None
    _log(
        f"transformer: seq={seq_len} bsz={bsz} step={t_step*1e3:.1f}ms "
        f"tokens/s={tokens_per_s:.0f} "
        f"model_tflops/step={flops.total/1e12:.2f} "
        f"mfu={mfu_val if mfu_val is None else round(mfu_val, 4)} "
        f"peak_hbm_gb={peak_dense}"
    )
    out["transformer_tokens_per_s"] = round(tokens_per_s, 1)
    out["transformer_remat"] = bool(cfg.remat)
    if mfu_val is not None:
        out["transformer_mfu"] = round(mfu_val, 4)
    if peak_dense is not None:
        out["transformer_peak_hbm_gb"] = peak_dense
    return out


def _bench_z3b_memory(on_tpu: bool, full: bool) -> dict | None:
    """Compiled per-device memory accounting for the three parameter
    storage modes (dense / zero3-lite / zero3_blocks) on a block-stack
    LM shape: XLA's memory analysis is deterministic and hardware-
    independent, so this arm reports under --quick on the CPU too — the
    HBM story behind zero3_blocks (per-step peak = params/dp + ONE
    gathered block) as numbers, not prose."""
    import jax
    import jax.numpy as jnp
    import optax

    from adaptdl_tpu.models import TransformerConfig, init_zero3_lm
    from adaptdl_tpu.models.transformer import init_transformer, lm_loss_fn
    from adaptdl_tpu.parallel.mesh import create_mesh
    from adaptdl_tpu.trainer import ElasticTrainer

    dp = min(len(jax.devices()), 8)
    if dp < 2:
        return None
    cfg = TransformerConfig(
        vocab_size=2048 if full else 256,
        num_layers=8 if full else 4,
        num_heads=8 if full else 2,
        d_model=512 if full else 64,
        d_ff=2048 if full else 128,
        max_seq_len=128 if full else 32,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        remat=False,
    )
    seq = 32 if full else 16
    bsz = dp * 2
    mesh = create_mesh({"data": dp}, devices=jax.devices()[:dp])
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, cfg.vocab_size, size=(bsz, seq + 1)).astype(
        np.int32
    )
    out = {}
    for mode in ("dense", "lite", "z3b"):
        if mode == "z3b":
            loss_fn, params = init_zero3_lm(cfg, seq_len=seq)
            kw = {"zero3_blocks": "blocks"}
        else:
            model, params = init_transformer(cfg, seq_len=seq)
            loss_fn = lm_loss_fn(model)
            kw = {"zero3": True} if mode == "lite" else {}
        trainer = ElasticTrainer(
            loss_fn, params, optax.adamw(1e-3), bsz, mesh=mesh, **kw
        )
        state = trainer.init_state()
        step = trainer.train_step(bsz // dp, 0)
        batch = trainer.shard_batch({"tokens": tokens})
        ma = (
            step._jitted.lower(state, batch, ())
            .compile()
            .memory_analysis()
        )
        if ma is None or not hasattr(ma, "temp_size_in_bytes"):
            return None
        out[f"mem_{mode}_temp_mb"] = round(
            ma.temp_size_in_bytes / 2**20, 2
        )
        out[f"mem_{mode}_args_mb"] = round(
            ma.argument_size_in_bytes / 2**20, 2
        )
    _log(
        "z3b memory (per device, compiled): "
        + " ".join(f"{k}={v}" for k, v in out.items())
    )
    out["mem_z3b_temp_vs_lite"] = round(
        out["mem_z3b_temp_mb"] / max(out["mem_lite_temp_mb"], 1e-9), 3
    )
    return out


def _bench_flash_attention(on_tpu: bool, full: bool) -> dict | None:
    """Compiled flash-attention vs XLA dense attention, fwd+bwd step
    time at the shape where the kernel matters (long seq, bf16).

    Off-TPU the Pallas kernel runs in interpret mode (Python speed) —
    timing it would be meaningless, so this phase is TPU-only, and it
    refuses to time anything but the Mosaic-compiled kernel.
    """
    if not on_tpu:
        return None
    import jax
    import jax.numpy as jnp

    from adaptdl_tpu.models.transformer import causal_attention
    from adaptdl_tpu.ops.flash_attention import flash_attention

    B, H, S, D = (4, 8, 2048, 64) if full else (1, 2, 256, 64)
    rng = np.random.default_rng(5)
    qkv = [
        jnp.asarray(
            rng.normal(size=(B, H, S, D)), dtype=jnp.bfloat16
        )
        for _ in range(3)
    ]

    def loss_flash(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    def loss_dense(q, k, v):
        return causal_attention(q, k, v).astype(jnp.float32).sum()

    from adaptdl_tpu.ops.flash_attention import MOSAIC_CALL

    def timed(loss, kernel=True):
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        if kernel and MOSAIC_CALL not in g.lower(*qkv).as_text():
            raise RuntimeError(
                "flash attention lowered without the Mosaic kernel "
                "(interpret mode?) — refusing to time it as one"
            )
        jax.block_until_ready(g(*qkv))  # compile + warmup
        n = 10 if full else 3
        start = time.monotonic()
        for _ in range(n):
            out = g(*qkv)
        jax.block_until_ready(out)
        return (time.monotonic() - start) / n

    t_flash = timed(loss_flash)
    if _remaining() < 45:
        _log("flash bench: budget pressure — skipping dense arm")
        return {"flash_attn_ms": round(t_flash * 1e3, 3)}
    t_dense = timed(loss_dense, kernel=False)
    speedup = t_dense / t_flash
    _log(
        f"flash attn: seq={S} flash={t_flash*1e3:.2f}ms "
        f"dense={t_dense*1e3:.2f}ms speedup={speedup:.3f}x"
    )
    out = {
        "flash_attn_ms": round(t_flash * 1e3, 3),
        "flash_attn_speedup_vs_xla": round(speedup, 3),
    }
    # Block-size sweep (full mode): the Mosaic-compiled kernel's best
    # (block_q, block_k) at this shape.
    if full and _remaining() > 120:
        import functools

        best = (None, t_flash)
        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                if (bq, bk) == (128, 128):
                    continue  # the default, timed above
                try:
                    fa = functools.partial(
                        flash_attention, block_q=bq, block_k=bk
                    )
                    t = timed(
                        lambda q, k, v: fa(q, k, v)
                        .astype(jnp.float32)
                        .sum()
                    )
                except Exception:  # noqa: BLE001 - via failed_phases
                    _log(
                        f"flash sweep ({bq},{bk}) failed:\n"
                        f"{traceback.format_exc()}"
                    )
                    _FAILED_PHASES.append(f"flash_sweep_{bq}x{bk}")
                    continue
                _log(f"flash sweep ({bq},{bk}): {t*1e3:.2f}ms")
                if t < best[1]:
                    best = ((bq, bk), t)
                if _remaining() < 90:
                    break
            if _remaining() < 90:
                break
        if best[0] is not None:
            out["flash_attn_best_block"] = list(best[0])
            out["flash_attn_best_ms"] = round(best[1] * 1e3, 3)
    return out


def _bench_rescale_latency(trainer_factory, dataset, init_bsz, trials=3):
    """Median PLANNED-rescale latency: the cost of one elastic
    rescale when the successor pulls state peer-to-peer from the
    doomed incarnation's handoff shard server instead of
    round-tripping through checkpoint storage. Each trial measures
    the full planned path — snapshot (critical path), differential
    durable write (overlapped fallback, ``ADAPTDL_CKPT_FULL_EVERY=2``
    so it is a *delta* against the steady-state full snapshot),
    shard-server setup + chunk fetch + re-materialization, first step
    through the AOT-executable cache — and then the storage restore
    of the SAME delta-chain checkpoint as the fallback reference.

    Returns ``(p50, breakdown, trace_summary)``: ``p50`` is the
    planned-path median; the breakdown holds per-phase medians
    (snapshot_s / write_s / handoff_s / first_step_s), the
    storage-path reference (restore_s, storage_p50_s — what the same
    rescale would have cost through storage), and ``delta_ratio``
    (delta bytes / full bytes of the overlapped durable write).
    ``trace_summary`` is the graftscope per-phase view of the same
    trials — median span durations keyed by span name (ckpt.snapshot
    / ckpt.write / handoff.fetch / ckpt.restore / aot.lookup /
    aot.compile) plus the span count — emitted on the BENCH JSON line
    as ``rescale_trace`` so the two instruments cross-check each
    other. All timing is ``time.monotonic()``."""
    _use_fixed_compile_cache()
    return _rescale_trials(
        trainer_factory, dataset, init_bsz, trials=trials
    )


def _rescale_trials(trainer_factory, dataset, init_bsz, trials=3):
    import tempfile

    from adaptdl_tpu import aot_cache
    from adaptdl_tpu import checkpoint as ckpt_mod
    from adaptdl_tpu import handoff as handoff_mod
    from adaptdl_tpu import metrics as metrics_mod
    from adaptdl_tpu import trace

    # Bracket the trials in the trace buffer so the summary covers
    # exactly these spans (earlier phases recorded their own).
    trace_start_seq = trace.buffer_seq()
    planned_times: list[float] = []
    storage_times: list[float] = []
    parts: dict[str, list] = {
        "snapshot_s": [], "write_s": [], "handoff_s": [],
        "restore_s": [], "first_step_s": [], "delta_ratio": [],
    }
    rng = np.random.default_rng(4)
    for trial in range(trials):
        with tempfile.TemporaryDirectory() as tmp:
            os.environ["ADAPTDL_CHECKPOINT_PATH"] = tmp
            # Delta cadence 2: the steady-state save below is the
            # full snapshot, the rescale's overlapped durable write
            # is a delta against it — the production planned-rescale
            # shape.
            os.environ["ADAPTDL_CKPT_FULL_EVERY"] = "2"
            trainer = trainer_factory()
            holder = {"state": trainer.init_state()}
            ck = trainer.make_checkpoint_state(
                lambda: holder["state"],
                lambda s: holder.__setitem__("state", s),
                name=f"bench-rescale-{trial}",
            )
            # Warm state: one compiled step (this also persists the
            # step executable into the job's AOT cache, as steady-
            # state training does long before any rescale).
            atomic = init_bsz // trainer.num_replicas
            step_fn = trainer.train_step(atomic, 0)
            idx = rng.integers(0, len(dataset["label"]), size=init_bsz)
            batch = trainer.shard_batch(
                {k: v[idx] for k, v in dataset.items()}
            )
            holder["state"], m = step_fn(holder["state"], batch)
            import jax

            jax.block_until_ready(m["loss"])
            aot_cache.wait_for_writes()
            # Steady-state history: the periodic FULL snapshot every
            # job has long before a rescale, plus one more step so
            # the rescale-time state genuinely differs from it.
            ckpt_mod.save_all_states()
            holder["state"], m = step_fn(holder["state"], batch)
            jax.block_until_ready(m["loss"])

            start = time.monotonic()
            # Pipelined save: the snapshot phase blocks; the (delta)
            # write runs behind the restarted incarnation's
            # construction, exactly as behind a relaunch in
            # production — it is the durable FALLBACK; the restore
            # itself goes peer-to-peer below.
            handle = ckpt_mod.save_all_states(wait=False)
            snapshot_s = time.monotonic() - start
            # The doomed incarnation's shard server, serving its
            # in-memory snapshot chunks (in production this is the
            # detached child spawn_server leaves behind).
            server = handoff_mod.serve_states()
            # "Restart": a fresh trainer (new step cache) pulling the
            # saved state from the peer, then one step to readiness.
            trainer2 = trainer_factory()
            holder2 = {"state": trainer2.init_state()}
            ck.unregister()
            ck2 = trainer2.make_checkpoint_state(
                lambda: holder2["state"],
                lambda s: holder2.__setitem__("state", s),
                name=f"bench-rescale-{trial}",
            )
            handoff_mod.set_source(server.url)
            t0 = time.monotonic()
            if not ckpt_mod.load_state(ck2):
                raise RuntimeError(
                    "rescale trial: restore found neither the peer "
                    "nor a complete checkpoint"
                )
            handoff_s = time.monotonic() - t0
            t0 = time.monotonic()
            step_fn2 = trainer2.train_step(atomic, 0)
            s2, m2 = step_fn2(holder2["state"], batch)
            jax.block_until_ready(m2["loss"])
            first_step_s = time.monotonic() - t0
            planned_times.append(time.monotonic() - start)
            server.stop()
            handoff_mod._reset_client_state()
            # Storage-path reference: the SAME rescale through the
            # durable delta-chain checkpoint (what every unplanned
            # restart pays, and what the planned path just skipped).
            handle.wait()
            trainer3 = trainer_factory()
            holder3 = {"state": trainer3.init_state()}
            ck2.unregister()
            ck3 = trainer3.make_checkpoint_state(
                lambda: holder3["state"],
                lambda s: holder3.__setitem__("state", s),
                name=f"bench-rescale-{trial}",
            )
            t0 = time.monotonic()
            if not ckpt_mod.load_state(ck3):
                raise RuntimeError(
                    "rescale trial: storage restore found no "
                    "complete checkpoint (background write failed?)"
                )
            restore_s = time.monotonic() - t0
            t0 = time.monotonic()
            step_fn3 = trainer3.train_step(atomic, 0)
            s3, m3 = step_fn3(holder3["state"], batch)
            jax.block_until_ready(m3["loss"])
            storage_first_step_s = time.monotonic() - t0
            storage_times.append(
                snapshot_s + restore_s + storage_first_step_s
            )
            stats = metrics_mod.restart_stats() or {}
            parts["snapshot_s"].append(snapshot_s)
            parts["write_s"].append(handle.write_s)
            parts["handoff_s"].append(handoff_s)
            parts["restore_s"].append(restore_s)
            parts["first_step_s"].append(first_step_s)
            if stats.get("deltaRatio") is not None:
                parts["delta_ratio"].append(stats["deltaRatio"])
            ck3.unregister()
            os.environ.pop("ADAPTDL_CHECKPOINT_PATH", None)
            os.environ.pop("ADAPTDL_CKPT_FULL_EVERY", None)
    p50 = float(np.median(planned_times))
    breakdown = {
        key: round(float(np.median(vals)), 4)
        for key, vals in parts.items()
        if vals
    }
    breakdown["storage_p50_s"] = round(
        float(np.median(storage_times)), 4
    )
    trial_spans = [
        rec
        for rec in trace.snapshot_spans()
        if rec.get("seq", 0) > trace_start_seq
    ]
    trace_summary = {
        "phases": {
            name: round(seconds, 4)
            for name, seconds in sorted(
                trace.phase_summary(trial_spans).items()
            )
        },
        "span_count": len(trial_spans),
    }
    _log(
        f"rescale: planned={['%.2f' % t for t in planned_times]} "
        f"storage={['%.2f' % t for t in storage_times]} "
        f"p50={p50:.2f}s breakdown={breakdown} "
        f"trace={trace_summary['phases']}"
    )
    return p50, breakdown, trace_summary


def _bench_warm_rescale(
    trainer_factory, dataset, init_bsz, trials=2
) -> dict | None:
    """Speculative warm-up vs the cold planned rescale, in-process.

    The warm arm stages everything the runner's warm successor does
    while the incumbent is still training — successor construction,
    step compile, differential chunk prefetch from the incumbent's
    shard server — OUTSIDE the measured window, then measures only
    the cutover: differential pull of the chunks that changed since
    the prefetch, re-materialization, first step. The cold arm
    measures the same rescale with everything inside the window (the
    existing planned path). Both windows are also bracketed as
    ``restart.first_step`` pending spans, so the trace view and the
    wall-clock agree. Reports per-arm ``cutover_s`` and ``steps_lost``
    (cutover over the measured steady step time) plus the
    differential pull's wire bytes vs the full pull volume — the
    changed-shard case by construction (the incumbent takes a step
    between prefetch and drain, so params move but e.g. the treedef
    chunk does not)."""
    import tempfile

    from adaptdl_tpu import checkpoint as ckpt_mod
    from adaptdl_tpu import handoff as handoff_mod
    from adaptdl_tpu import trace

    import jax

    warm_cutover: list[float] = []
    cold_cutover: list[float] = []
    warm_lost: list[int] = []
    cold_lost: list[int] = []
    diff_bytes: list[int] = []
    full_bytes: list[int] = []
    step_times: list[float] = []
    rng = np.random.default_rng(7)
    for trial in range(trials):
        with tempfile.TemporaryDirectory() as tmp:
            os.environ["ADAPTDL_CHECKPOINT_PATH"] = tmp
            trainer = trainer_factory()
            holder = {"state": trainer.init_state()}
            ck = trainer.make_checkpoint_state(
                lambda: holder["state"],
                lambda s: holder.__setitem__("state", s),
                name=f"bench-warm-{trial}",
            )
            atomic = init_bsz // trainer.num_replicas
            step_fn = trainer.train_step(atomic, 0)
            idx = rng.integers(0, len(dataset["label"]), size=init_bsz)
            batch = trainer.shard_batch(
                {k: v[idx] for k, v in dataset.items()}
            )
            holder["state"], step_s, m = _steady_state_time(
                holder["state"], step_fn, batch, steps=4
            )
            step_times.append(step_s)
            # Incumbent's latest save + shard server: the state the
            # warm successor prefetches against.
            ckpt_mod.save_all_states()
            server_a = handoff_mod.serve_states()
            # ---- warm-up (overlapped with the incumbent in
            # production, so deliberately unmeasured).
            trainer2 = trainer_factory()
            holder2 = {"state": trainer2.init_state()}
            step_fn2 = trainer2.train_step(atomic, 0)
            _s, m2 = step_fn2(holder2["state"], batch)  # compile only
            jax.block_until_ready(m2["loss"])
            handoff_mod.warm_prefetch(url=server_a.url)
            # ---- incumbent trains past the prefetched snapshot: the
            # cutover pull is differential against a CHANGED state.
            holder["state"], m = step_fn(holder["state"], batch)
            jax.block_until_ready(m["loss"])
            ckpt_mod.save_all_states()  # final drain snapshot
            server_a.stop()
            server_b = handoff_mod.serve_states()
            ck.unregister()
            ck2 = trainer2.make_checkpoint_state(
                lambda: holder2["state"],
                lambda s: holder2.__setitem__("state", s),
                name=f"bench-warm-{trial}",
            )
            before = dict(handoff_mod._fetch_stats)
            handoff_mod.set_source(server_b.url)
            trace.begin_pending("restart.first_step", arm="warm")
            t0 = time.monotonic()
            if not ckpt_mod.load_state(ck2):
                raise RuntimeError(
                    "warm rescale trial: cutover restore failed"
                )
            holder2["state"], m2 = step_fn2(holder2["state"], batch)
            jax.block_until_ready(m2["loss"])
            cut = time.monotonic() - t0
            trace.end_pending("restart.first_step", arm="warm")
            warm_cutover.append(cut)
            warm_lost.append(int(cut // max(step_s, 1e-9)))
            stats = handoff_mod._fetch_stats
            wire = int(stats["bytes"] - before["bytes"])
            reused = int(stats["reused"] - before["reused"])
            diff_bytes.append(wire)
            full_bytes.append(wire + reused)
            ck2.unregister()
            handoff_mod._reset_client_state()
            # ---- cold arm: the same rescale with successor build,
            # compile, full pull, and first step all on the clock.
            trace.begin_pending("restart.first_step", arm="cold")
            t0 = time.monotonic()
            trainer3 = trainer_factory()
            holder3 = {"state": trainer3.init_state()}
            ck3 = trainer3.make_checkpoint_state(
                lambda: holder3["state"],
                lambda s: holder3.__setitem__("state", s),
                name=f"bench-warm-{trial}",
            )
            handoff_mod.set_source(server_b.url)
            if not ckpt_mod.load_state(ck3):
                raise RuntimeError(
                    "warm rescale trial: cold restore failed"
                )
            step_fn3 = trainer3.train_step(atomic, 0)
            holder3["state"], m3 = step_fn3(holder3["state"], batch)
            jax.block_until_ready(m3["loss"])
            cold = time.monotonic() - t0
            trace.end_pending("restart.first_step", arm="cold")
            cold_cutover.append(cold)
            cold_lost.append(int(cold // max(step_s, 1e-9)))
            server_b.stop()
            ck3.unregister()
            handoff_mod._reset_client_state()
            os.environ.pop("ADAPTDL_CHECKPOINT_PATH", None)
    out = {
        "warm_rescale": {
            "step_s": round(float(np.median(step_times)), 4),
            "warm_cutover_s": round(float(np.median(warm_cutover)), 4),
            "cold_cutover_s": round(float(np.median(cold_cutover)), 4),
            "warm_steps_lost": int(np.median(warm_lost)),
            "cold_steps_lost": int(np.median(cold_lost)),
            "diff_pull_bytes": int(np.median(diff_bytes)),
            "full_pull_bytes": int(np.median(full_bytes)),
        }
    }
    _log(f"warm rescale: {out['warm_rescale']}")
    return out


def _bench_mesh_rescale(trials: int = 3) -> dict | None:
    """Mesh-shape elasticity's rescale cost: a PLANNED dp -> (dp, tp)
    reshape where the successor re-materializes the predecessor's
    peer-served state onto a tensor-parallel mesh, plus the
    range-pull bytes story — what a shard-map-keyed successor
    (``handoff.fraction_plan``) pulls versus the full-leaf handoff.

    Reports ``mesh_rescale_p50_s`` (median collect+serve+reshard-
    restore wall, the reshape's critical path; the durable write
    overlaps it exactly as in ``_bench_rescale_latency``) and the
    fraction-pull bytes ratio. All timing ``time.monotonic()``."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from adaptdl_tpu import checkpoint as ckpt_mod
    from adaptdl_tpu import handoff as handoff_mod
    from adaptdl_tpu.parallel import create_mesh
    from adaptdl_tpu.trainer import ElasticTrainer
    from jax.sharding import PartitionSpec as P

    ndev = len(jax.devices())
    tp = 2 if ndev >= 2 else 1
    if tp == 1:
        return None
    ndev = (ndev // tp) * tp
    dim = 256
    rng = np.random.default_rng(11)
    data = {
        "x": rng.normal(size=(64, dim)).astype(np.float32),
        "label": rng.normal(size=(64,)).astype(np.float32),
    }
    params = {
        "w1": jnp.asarray(
            rng.normal(size=(dim, dim)).astype(np.float32)
        ),
        "w2": jnp.asarray(rng.normal(size=(dim,)).astype(np.float32)),
    }

    def loss_fn(p, batch, _rng):
        h = jnp.tanh(batch["x"] @ p["w1"])
        return jnp.mean((h @ p["w2"] - batch["label"]) ** 2)

    def sharding_fn(path, leaf):
        # w1's rows shard over the model axis; the rest replicate.
        if getattr(path[-1], "key", None) == "w1":
            return P("model")
        return P()

    def make_dp():
        return ElasticTrainer(
            loss_fn, params, optax.sgd(0.1, momentum=0.9), 8,
            mesh=create_mesh(devices=jax.devices()[:ndev]),
        )

    def make_tp():
        return ElasticTrainer(
            loss_fn, params, optax.sgd(0.1, momentum=0.9), 8,
            mesh=create_mesh(
                {"data": ndev // tp, "model": tp},
                devices=jax.devices()[:ndev],
            ),
            param_sharding_fn=sharding_fn,
        )

    reshape_times: list[float] = []
    frac_bytes: list[int] = []
    full_bytes: list[int] = []
    for trial in range(trials):
      server = None
      try:
        with tempfile.TemporaryDirectory() as tmp:
            os.environ["ADAPTDL_CHECKPOINT_PATH"] = tmp
            trainer = make_dp()
            holder = {"state": trainer.init_state()}
            ck = trainer.make_checkpoint_state(
                lambda: holder["state"],
                lambda s: holder.__setitem__("state", s),
                name=f"bench-mesh-{trial}",
            )
            atomic = max(8 // trainer.num_replicas, 1)
            step = trainer.train_step(atomic, 0)
            batch = {
                k: v[: atomic * trainer.num_replicas]
                for k, v in data.items()
            }
            holder["state"], m = step(
                holder["state"], trainer.shard_batch(batch)
            )
            jax.block_until_ready(m["loss"])

            # Planned reshape: collect+serve the predecessor's state,
            # re-materialize onto the (dp, tp) mesh peer-to-peer.
            start = time.monotonic()
            server = handoff_mod.serve_states()
            trainer2 = make_tp()
            holder2 = {"state": trainer2.init_state()}
            ck.unregister()
            ck2 = trainer2.make_checkpoint_state(
                lambda: holder2["state"],
                lambda s: holder2.__setitem__("state", s),
                name=f"bench-mesh-{trial}",
            )
            handoff_mod.set_source(server.url)
            if not ckpt_mod.load_state(ck2):
                raise RuntimeError(
                    "mesh reshape trial: peer restore failed"
                )
            reshape_times.append(time.monotonic() - start)
            full_bytes.append(handoff_mod._fetch_stats["bytes"])

            # Range-pull arm: a shard-map-keyed successor (one tp
            # shard's fraction of every leaf) against the same peer.
            ck2.unregister()
            handoff_mod._reset_client_state()
            trainer3 = make_tp()
            holder3 = {"state": trainer3.init_state()}
            ck3 = trainer3.make_checkpoint_state(
                lambda: holder3["state"],
                lambda s: holder3.__setitem__("state", s),
                name=f"bench-mesh-{trial}",
                shard_plan_fn=lambda rows: handoff_mod.fraction_plan(
                    rows, 0, tp
                ),
            )
            handoff_mod.set_source(server.url)
            if not ckpt_mod.load_state(ck3):
                raise RuntimeError(
                    "mesh reshape trial: range-pull restore failed"
                )
            frac_bytes.append(handoff_mod._fetch_stats["bytes"])
            ck3.unregister()
      finally:
        # A failed trial must not leak into later bench phases: the
        # env var would point at a deleted tempdir, the in-process
        # shard server would pin the payload, and the handoff
        # client's sticky manifest would pollute later measurements.
        if server is not None:
            server.stop()
        handoff_mod._reset_client_state()
        os.environ.pop("ADAPTDL_CHECKPOINT_PATH", None)
    out = {
        "mesh_rescale_p50_s": round(
            float(np.median(reshape_times)), 4
        ),
        "mesh_handoff_full_bytes": int(np.median(full_bytes)),
        "mesh_handoff_frac_bytes": int(np.median(frac_bytes)),
        "mesh_handoff_bytes_fraction": round(
            float(
                np.median(frac_bytes) / max(np.median(full_bytes), 1)
            ),
            4,
        ),
        "mesh_tp": tp,
    }
    _log(f"mesh rescale: {out}")
    return out


def main(quick: bool = False) -> int:
    import jax
    import jax.numpy as jnp
    import optax

    from adaptdl_tpu import metrics
    from adaptdl_tpu.data import AdaptiveDataLoader
    from adaptdl_tpu.goodput import GradParams
    from adaptdl_tpu.models import init_resnet18, resnet_loss_fn
    from adaptdl_tpu.scaling_rules import AdaScale
    from adaptdl_tpu.trainer import ElasticTrainer

    device = jax.devices()[0]
    platform = device.platform
    on_tpu = platform == "tpu"
    if not on_tpu and not quick:
        _log(
            f"bench: platform={platform!r} is not a TPU — refusing to "
            "measure (device metrics come from a chip run; --quick is "
            "the explicit tiny CPU preset the tests use)"
        )
        return 2
    # Single-process SPMD: one replica per addressable device.
    os.environ.setdefault(
        "ADAPTDL_NUM_REPLICAS", str(len(jax.devices()))
    )
    full = not quick
    image_size = 32 if full else 8
    width = 64 if full else 8
    dataset_n = 8192 if full else 512
    measure_steps = 30 if full else 3
    # Quick mode still needs enough steps for at least two batch-size
    # re-optimizations, or the "adaptive" run never adapts and the
    # ratio measures noise.
    adapt_steps = 120 if full else 25
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    init_bsz = 128 if full else 32
    max_bsz = 4096 if full else 128
    bounds = (64, 1024) if full else (8, 64)

    model, params = init_resnet18(
        image_size=image_size, width=width, dtype=dtype
    )
    dataset = _make_dataset(dataset_n, image_size)
    _log(
        f"bench: platform={platform} width={width} "
        f"budget_left={_remaining():.0f}s"
    )

    def make_trainer():
        return ElasticTrainer(
            loss_fn=resnet_loss_fn(model),
            params=params,
            optimizer=optax.sgd(0.1, momentum=0.9),
            init_batch_size=init_bsz,
            scaling_rule=AdaScale(),
        )

    # ---- fixed-allocation baseline: batch 128 -----------------------
    metrics._reset_state()
    trainer = make_trainer()
    state = trainer.init_state()
    atomic_fixed = init_bsz // trainer.num_replicas
    step_fn = trainer.train_step(atomic_fixed, 0)
    rng = np.random.default_rng(1)
    idx = rng.integers(0, dataset_n, size=init_bsz)
    batch = trainer.shard_batch(
        {k: v[idx] for k, v in dataset.items()}
    )
    state, fixed_times, _ = _steady_state_windows(
        state, step_fn, batch, measure_steps, windows=3
    )
    t_fixed = float(np.median(fixed_times))
    goodput_fixed = init_bsz / t_fixed  # efficiency(128) == 1
    _log(
        f"fixed: batch={init_bsz} step={t_fixed*1e3:.1f}ms "
        f"(windows {['%.1f' % (t*1e3) for t in fixed_times]}) "
        f"goodput={goodput_fixed:.1f} budget_left={_remaining():.0f}s"
    )

    # ---- adaptive run: goodput model drives the batch size ----------
    if _remaining() < 120:
        # Deep in the budget already: shed adaptation depth, keep the
        # measurement phases.
        adapt_steps = min(adapt_steps, 20)
        _log(f"budget pressure: adapt_steps={adapt_steps}")
    metrics._reset_state()
    trainer = make_trainer()
    state = trainer.init_state()
    loader = AdaptiveDataLoader(
        dataset, batch_size=init_bsz, name="bench-loader"
    )
    loader.autoscale_batch_size(
        max_bsz, local_bsz_bounds=bounds, gradient_accumulation=True
    )
    loader._reoptimize_every = 10 if full else 5
    steps = 0
    from adaptdl_tpu import epoch as epoch_mod

    for e in epoch_mod.remaining_epochs_until(1_000_000):
        for host_batch in loader:
            state, m = trainer.run_step(state, host_batch, loader)
            steps += 1
            if steps % 10 == 0:
                metrics.fit_and_report_now()
            if steps >= adapt_steps or _remaining() < 90:
                break
        if steps >= adapt_steps or _remaining() < 90:
            break
    # Re-decide at measurement time with the FINAL fitted perf/grad
    # state: the in-loop decisions run on whatever statistics existed
    # mid-adaptation, and measuring a config the policy would no
    # longer pick makes the ratio swing run-to-run (the r3-r5 noise
    # band) — the retention question is "the config the policy holds
    # NOW vs fixed", so align the decision with the evaluation state.
    metrics.fit_and_report_now()
    loader._optimize_batch_size()
    final_atomic = loader.current_atomic_bsz
    final_accum = loader.current_accum_steps
    final_bsz = loader.current_batch_size
    # Quiesce the background perf-fit thread before timing: on a
    # small host it contends with the measurement (XLA compiles +
    # L-BFGS on the same cores) and skews the ratio.
    if metrics._fit_thread is not None and metrics._fit_thread.is_alive():
        metrics._fit_thread.join(timeout=60)
    # Steady-state throughput at the adapted configuration.
    step_fn = trainer.train_step(final_atomic, final_accum)
    idx = rng.integers(0, dataset_n, size=final_bsz)
    batch = trainer.shard_batch(
        {k: v[idx] for k, v in dataset.items()}
    )
    state, adapt_times, m = _steady_state_windows(
        state, step_fn, batch, measure_steps, windows=3
    )
    t_adapt = float(np.median(adapt_times))
    grad_params = metrics.current_state().grad_params or GradParams(
        float(m["grad_sqr"]), float(m["grad_var"])
    )
    from adaptdl_tpu.goodput import GoodputFunction, PerfParams

    efficiency = GoodputFunction(
        metrics.current_state().perf_params
        or PerfParams(0.1, 0.01, 0.02, 0.006, 0.01, 0.003, 1.1),
        grad_params,
        init_bsz,
    ).efficiency(final_bsz)
    goodput_adapt = (final_bsz / t_adapt) * float(efficiency)
    _log(
        f"adaptive: batch={final_bsz} (atomic={final_atomic}, "
        f"accum={final_accum}) step={t_adapt*1e3:.1f}ms "
        f"eff={float(efficiency):.3f} goodput={goodput_adapt:.1f} "
        f"budget_left={_remaining():.0f}s"
    )
    ratio = goodput_adapt / goodput_fixed
    # Window spread of the ratio: all (fixed, adapt) window pairings.
    # A wide band says the number is noise-dominated (the r3->r4
    # 1.07 -> 0.94 swing) and should be read against the band, not as
    # a point regression.
    pair_ratios = [
        (final_bsz / ta * float(efficiency)) / (init_bsz / tf)
        for tf in fixed_times
        for ta in adapt_times
    ]
    global _PRIMARY_RESULT
    _PRIMARY_RESULT = {
        "metric": "elastic_goodput_retention_resnet18_cifar",
        "value": round(ratio, 4),
        "unit": "x_fixed_allocation_goodput",
        "vs_baseline": round(ratio, 4),
        "value_ci": [
            round(min(pair_ratios), 4),
            round(max(pair_ratios), 4),
        ],
        "platform": platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }

    # ---- optional depth: realized convergence, transformer tokens/s
    # + MFU, flash kernel, rescale p50. Ordered by verdict priority.
    convergence_stats = _run_phase(
        "convergence", 150, _bench_convergence, on_tpu, full
    )
    z3b_stats = _run_phase(
        "z3b_memory", 140, _bench_z3b_memory, on_tpu, full
    )
    transformer_stats = _run_phase(
        "transformer", 120, _bench_transformer_tokens, on_tpu, full
    )
    flash_stats = _run_phase(
        "flash", 90, _bench_flash_attention, on_tpu, full
    )

    def rescale_phase():
        metrics._reset_state()
        return _bench_rescale_latency(make_trainer, dataset, init_bsz)

    rescale_p50, rescale_breakdown, rescale_trace = _run_phase(
        "rescale", 60, rescale_phase
    ) or (None, None, None)

    # Speculative warm-up: cutover-only cost (and steps lost) of a
    # planned rescale when the successor was pre-warmed, vs the same
    # rescale cold, plus the differential pull's byte savings.
    def warm_phase():
        metrics._reset_state()
        return _bench_warm_rescale(
            make_trainer, dataset, init_bsz,
            trials=2 if _remaining() > 100 else 1,
        )

    warm_stats = _run_phase("warm_rescale", 50, warm_phase)

    # Mesh-shape reshape: the planned dp -> (dp, tp) rescale path +
    # the shard-map range-pull bytes vs the full-leaf handoff.
    def mesh_phase():
        metrics._reset_state()
        return _bench_mesh_rescale(
            trials=3 if _remaining() > 90 else 1
        )

    mesh_stats = _run_phase("mesh_rescale", 45, mesh_phase)

    # Thousand-job control plane (bench_sched.py): allocator decide
    # p50/p99 at 1k jobs / 10k slots (cold full cycle vs the
    # incremental path) + supervisor per-endpoint p99s under
    # simulated-worker load. Pure CPU control-plane work — runs the
    # same on every platform.
    def sched_phase():
        import bench_sched

        stats = bench_sched.collect(quick=_remaining() < 150)
        _log(f"sched bench: {stats}")
        return stats

    sched_stats = _run_phase("sched", 75, sched_phase)

    result = dict(_PRIMARY_RESULT)
    if convergence_stats:
        result.update(convergence_stats)
    if z3b_stats:
        result.update(z3b_stats)
    if transformer_stats:
        result.update(transformer_stats)
    if flash_stats:
        result.update(flash_stats)
    if rescale_p50 is not None:
        result["rescale_p50_s"] = round(rescale_p50, 3)
    if rescale_breakdown is not None:
        result["rescale_breakdown"] = rescale_breakdown
    if rescale_trace is not None:
        result["rescale_trace"] = rescale_trace
    if warm_stats:
        result.update(warm_stats)
    if mesh_stats:
        result.update(mesh_stats)
    if sched_stats:
        result.update(sched_stats)
    if _FAILED_PHASES:
        result["failed_phases"] = list(_FAILED_PHASES)
    print(json.dumps(result))
    return 1 if _FAILED_PHASES else 0


def _install_watchdog(seconds: int = 530) -> None:
    """A hung backend call must fail loudly and non-zero rather than
    leave a silent process for the caller's timeout to reap. If the
    headline number already exists it is still printed — with the
    overrun named in ``failed_phases``."""
    import signal

    def on_alarm(signum, frame):  # noqa: ARG001
        _log(f"bench watchdog: no full result after {seconds}s")
        if _PRIMARY_RESULT is not None:
            partial = dict(_PRIMARY_RESULT)
            partial["failed_phases"] = _FAILED_PHASES + ["watchdog"]
            print(json.dumps(partial), flush=True)
        sys.exit(2)

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)


if __name__ == "__main__":
    _install_watchdog()
    sys.exit(main(quick="--quick" in sys.argv))
