"""Per-step profiling, perf-param fitting, and hint reporting.

The reference profiles three times per step via backward hooks and CUDA
events (reference: adaptdl/adaptdl/torch/_metrics.py:29-66,
parallel.py:107-146). Under XLA the whole step is one fused program, so
hook timing is impossible — and unnecessary. The TPU profiling model:

- ``profile_step``: wall-clock of the full jitted step (host-timed with
  ``block_until_ready``), keyed by (num_nodes, num_replicas,
  atomic_bsz) exactly like the reference's profile table.
- The compute/communication split the perf model needs comes from a
  one-off *compute-only calibration* per atomic_bsz: the same
  microbatch gradient computation compiled without the collective
  (``ElasticTrainer`` provides it). ``accum`` observations are the
  calibration times; ``optim`` observations are
  ``measured_step_time - accum_steps * accum_time`` — the residual
  containing the gradient sync, with XLA's compute/comm overlap
  absorbed into the model's gamma p-norm.

Every ``fit_interval`` seconds, rank 0 refits PerfParams and posts
sched hints (reference cadence: _metrics.py:60-66). All of it lives in
a checkpointable ``MetricsState``.
"""

from __future__ import annotations

import logging
import pickle
import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from adaptdl_tpu import checkpoint, env, sched_hints, trace
from adaptdl_tpu.goodput import (
    GoodputFunction,
    GradParams,
    PerfParams,
    fit_perf_params,
)

LOG = logging.getLogger(__name__)

def _default_fit_interval() -> float:
    """Seconds between perf refits/hint posts (reference cadence 30s,
    _metrics.py:60-66); ADAPTDL_FIT_INTERVAL overrides (tests, demos)."""
    return env.fit_interval()


@dataclass
class _ProfileEntry:
    optim_time_sum: float = 0.0
    optim_count: int = 0
    accum_time_sum: float = 0.0
    accum_count: int = 0


@dataclass
class MetricsState:
    """Everything the adaptation engine knows about this job so far.

    Profile keys are ``(num_nodes, num_replicas, seq_shards,
    model_shards, stage_shards, expert_shards, pipeline_micro,
    atomic_bsz)`` — the reference's (nodes, replicas, bsz) keying
    (reference: _metrics.py:29-66) extended with the sharding axes and
    the GPipe microbatch count so the fit can identify the
    ring/TP/expert collective and pipeline-hop terms from timings that
    actually ran them.
    """

    # Fields mutated after worker threads exist (the trainer step
    # loop, the background fit thread, and the checkpoint writer
    # thread all touch them) are guarded-by annotations enforced at
    # lint time by graftcheck's lock-discipline pass (GC101).
    profile: dict[
        tuple[int, int, int, int, int, int, int, int], _ProfileEntry
    ] = field(  # guarded-by: _profile_lock
        default_factory=lambda: defaultdict(_ProfileEntry)
    )
    perf_params: PerfParams | None = None  # guarded-by: _profile_lock
    grad_params: GradParams | None = None  # guarded-by: _profile_lock
    init_batch_size: int | None = None
    max_batch_size: int | None = None
    local_bsz_bounds: tuple[int, int] | None = None
    gradient_accumulation: bool = False
    max_profiled_replicas: int = 0
    max_seq_shards: int = 1
    max_model_shards: int = 1
    max_stage_shards: int = 1
    max_expert_shards: int = 1
    # Default/current GPipe M (overridden per-run by the scheduler's
    # ADAPTDL_PIPELINE_MICRO via the trainer's active topology) and the
    # largest M the job's data layer supports (the search's cap).
    pipeline_microbatches: int = 4
    max_pipeline_micro: int = 8
    # Interleaved-schedule chunk count the model can split into
    # (0 = plain GPipe only); see parallel/pipeline.py.
    pipeline_chunks: int = 0
    # Explicit candidate mesh shapes ((sp, tp, ss, ep) tuples) posted
    # as the meshShapeGrid hint; None advertises only the max_* limits
    # (the scheduler then enumerates powers of two).
    mesh_shape_grid: tuple | None = None
    progress: float = 0.0
    # Measured checkpoint pipeline timings (checkpoint.save_all_states
    # records them): the last save's snapshot/write phase durations,
    # per-state breakdowns, and per-state restore durations from this
    # incarnation's startup. Together they price a rescale from
    # measurements instead of the policy's assumed restart penalty.
    # Written from the BACKGROUND WRITER thread, read from the fit
    # thread — hence the guard.
    ckpt_snapshot_s: float | None = None  # guarded-by: _profile_lock
    ckpt_write_s: float | None = None  # guarded-by: _profile_lock
    ckpt_per_state: dict = field(  # guarded-by: _profile_lock
        default_factory=dict
    )
    # Differential-checkpoint accounting: the last save's kind and
    # total serialized bytes, plus the last FULL save's bytes — the
    # denominator that makes a delta's size meaningful (deltaRatio =
    # delta bytes / full bytes).
    ckpt_save_kind: str | None = None  # guarded-by: _profile_lock
    ckpt_save_bytes: int | None = None  # guarded-by: _profile_lock
    ckpt_full_bytes: int | None = None  # guarded-by: _profile_lock
    # Peer-to-peer handoff: measured transfer of the last completed
    # fetch (successor side) — seconds and bytes over the wire.
    handoff_s: float | None = None  # guarded-by: _profile_lock
    handoff_bytes: int | None = None  # guarded-by: _profile_lock
    restore_per_state: dict = field(  # guarded-by: _profile_lock
        default_factory=dict
    )
    # In-process (atomic_bsz, accum) re-tunes adopted without a
    # checkpoint-restart (the live re-tune fast path).
    num_retunes: int = 0  # guarded-by: _profile_lock
    # graftwatch inputs: a smoothed step time (piggybacked on
    # heartbeats for per-slot straggler detection) and the measured
    # throughput behind the measuredGoodput hint — examples/s EWMA at
    # the batch geometry of the last profiled step.
    step_time_ewma: float | None = None  # guarded-by: _profile_lock
    examples_ewma: float | None = None  # guarded-by: _profile_lock
    last_global_bsz: int | None = None  # guarded-by: _profile_lock
    # Numeric-health guard (goodput hygiene): the raw EWMAs record
    # EVERY step including unhealthy/rolled-back ones, while the
    # guarded EWMAs above skip the samples the guard condemned — the
    # guarded-vs-raw gap is what a flapping job actually costs.
    # suppress_profile_steps counts condemned samples the dataloader
    # has not yet recorded.
    raw_step_time_ewma: float | None = None  # guarded-by: _profile_lock
    raw_examples_ewma: float | None = None  # guarded-by: _profile_lock
    unhealthy_steps: int = 0  # guarded-by: _profile_lock
    suppress_profile_steps: int = 0  # guarded-by: _profile_lock


_state = MetricsState()
_last_fit_time: float | None = None
_profile_lock = threading.Lock()  # lock-order: 30
_fit_thread: threading.Thread | None = None
_active_topology: tuple[int, int, int, int, int] | None = None


def _reset_state() -> None:
    """Test isolation."""
    global _state, _last_fit_time, _fit_thread, _active_topology
    if _fit_thread is not None and _fit_thread.is_alive():
        _fit_thread.join(timeout=60)
    _state = MetricsState()
    _last_fit_time = None
    _fit_thread = None
    _active_topology = None


def set_active_topology(
    seq_shards: int,
    model_shards: int,
    stage_shards: int = 1,
    expert_shards: int = 1,
    pipeline_micro: int | None = None,
) -> None:
    """Registered by the trainer with the (sp, tp, ss, ep, M) its mesh
    actually has. Profiles and batch decisions key on THIS, never on
    the scheduler's requested ADAPTDL_SEQ_SHARDS — a job is free to
    build a different mesh (e.g. CLI flags), and mis-keyed timings
    would teach the fit ring/TP/expert terms from measurements that
    never ran those collectives."""
    global _active_topology
    stage_shards = max(int(stage_shards), 1)
    if pipeline_micro is None:
        pipeline_micro = (
            _state.pipeline_microbatches if stage_shards > 1 else 1
        )
    _active_topology = (
        max(int(seq_shards), 1),
        max(int(model_shards), 1),
        stage_shards,
        max(int(expert_shards), 1),
        max(int(pipeline_micro), 1),
    )


def active_topology() -> tuple[int, int, int, int, int]:
    """The training process's live (seq_shards, model_shards,
    stage_shards, expert_shards, pipeline_micro): whatever the trainer
    registered, else the scheduler's request."""
    if _active_topology is not None:
        return _active_topology
    ss = env.stage_shards()
    return (
        env.seq_shards(),
        env.model_shards(),
        ss,
        env.expert_shards(),
        env.pipeline_micro() if ss > 1 else 1,
    )


def current_state() -> MetricsState:
    return _state


def set_batch_size_config(
    init_batch_size: int,
    max_batch_size: int | None = None,
    local_bsz_bounds: tuple[int, int] | None = None,
    gradient_accumulation: bool = False,
) -> None:
    _state.init_batch_size = init_batch_size
    _state.max_batch_size = max_batch_size
    _state.local_bsz_bounds = local_bsz_bounds
    _state.gradient_accumulation = gradient_accumulation


def set_topology_config(
    max_seq_shards: int = 1,
    max_model_shards: int = 1,
    max_stage_shards: int = 1,
    pipeline_microbatches: int = 4,
    max_expert_shards: int = 1,
    max_pipeline_micro: int | None = None,
    pipeline_chunks: int = 0,
    mesh_shape_grid=None,
) -> None:
    """Advertise how far this job can shard each sample/model
    (sequence shards need ring attention; model shards need a
    param_sharding_fn; stage shards need a gpipe_loss built with
    ``env.pipeline_micro()``; expert shards need an expert-sharded
    MoE). The scheduler's topology search stays within these limits;
    ``max_pipeline_micro`` caps the GPipe M it may pick (defaults to
    the larger of 8 and the job's default M); ``pipeline_chunks``
    declares the interleaved schedule's uniform chunk count (jobs
    built on ``interleaved_loss``; 0 = plain GPipe only).
    ``mesh_shape_grid`` posts an EXPLICIT candidate shape set
    ((sp, tp, ss, ep) tuples — ``goodput.mesh_shape_grid`` builds
    one) instead of the limits-derived power-of-two enumeration, for
    jobs whose model code supports non-pow2 factorizations or only a
    sparse subset of the cross product."""
    _state.max_seq_shards = max(int(max_seq_shards), 1)
    _state.max_model_shards = max(int(max_model_shards), 1)
    _state.max_stage_shards = max(int(max_stage_shards), 1)
    _state.max_expert_shards = max(int(max_expert_shards), 1)
    _state.pipeline_microbatches = max(int(pipeline_microbatches), 1)
    if max_pipeline_micro is None:
        max_pipeline_micro = max(8, _state.pipeline_microbatches)
    _state.max_pipeline_micro = max(int(max_pipeline_micro), 1)
    _state.pipeline_chunks = max(int(pipeline_chunks), 0)
    _state.mesh_shape_grid = (
        tuple(
            (int(sp), int(tp), int(ss), int(ep))
            for sp, tp, ss, ep in mesh_shape_grid
        )
        if mesh_shape_grid
        else None
    )


def _topology_suffix() -> tuple[int, int, int, int, int]:
    sp, tp, ss, ep, micro = active_topology()
    return (sp, tp, ss, ep, micro if ss > 1 else 1)


def _profile_key(
    atomic_bsz: int,
) -> tuple[int, int, int, int, int, int, int, int]:
    sp, tp, ss, ep, micro = _topology_suffix()
    return (
        env.num_nodes(), env.num_replicas(), sp, tp, ss, ep, micro,
        atomic_bsz,
    )


def profile_accum_time(atomic_bsz: int, accum_time: float) -> None:
    """Record a compute-only (no-sync) calibration measurement."""
    key = _profile_key(atomic_bsz)
    with _profile_lock:
        entry = _state.profile[key]
        entry.accum_time_sum += accum_time
        entry.accum_count += 1


def accum_time_on_record(atomic_bsz: int) -> tuple[float, int] | None:
    """What calibration has already measured for ``atomic_bsz`` under
    the RUNNING layout: (mean seconds, observations) of the entry
    ``profile_accum_time`` would add to now, else None. A restored
    profile answers for a predecessor that ran this very layout; a
    new batch size or another replica count is another key."""
    key = _profile_key(atomic_bsz)
    with _profile_lock:
        entry = _state.profile.get(key)
        if entry is None or entry.accum_count <= 0:
            return None
        return entry.accum_time_sum / entry.accum_count, entry.accum_count


def profile_step(
    atomic_bsz: int, accum_steps: int, step_time: float
) -> None:
    """Record one full fused-step wall-clock measurement.

    The optim-time observation is the step time minus the modelled
    accumulation micro-steps, clamped to stay positive.
    """
    # First profiled step of this incarnation closes the
    # restart->first-step span bootstrap opened (a no-op ever after):
    # the tail of the rescale timeline, measured where the step
    # actually ran rather than where the restart was requested.
    trace.end_pending(
        "restart.first_step", atomic_bsz=int(atomic_bsz)
    )
    key = _profile_key(atomic_bsz)
    with _profile_lock:
        # Goodput hygiene (guard.py): a sample the guard condemned
        # feeds only the RAW EWMAs below, never the profile table or
        # the guarded EWMAs behind measuredGoodput/the perf fit — a
        # flapping job must report degraded goodput, not a lie.
        suppressed = _state.suppress_profile_steps > 0
        if suppressed:
            _state.suppress_profile_steps -= 1
        alpha = 0.2
        if step_time > 0:
            dp = env.data_parallel_replicas()
            global_bsz = int(atomic_bsz) * (int(accum_steps) + 1) * dp
            examples_s = global_bsz / step_time
            prev = _state.raw_step_time_ewma
            _state.raw_step_time_ewma = (
                step_time if prev is None
                else (1 - alpha) * prev + alpha * step_time
            )
            prev = _state.raw_examples_ewma
            _state.raw_examples_ewma = (
                examples_s if prev is None
                else (1 - alpha) * prev + alpha * examples_s
            )
            _state.last_global_bsz = global_bsz
        if not suppressed:
            entry = _state.profile[key]
            if accum_steps > 0 and entry.accum_count > 0:
                accum_time = entry.accum_time_sum / entry.accum_count
                optim_time = max(
                    step_time - accum_steps * accum_time,
                    0.1 * step_time,
                )
            else:
                optim_time = step_time
            entry.optim_time_sum += optim_time
            entry.optim_count += 1
            # graftwatch's measured half: smooth the step time
            # (straggler heartbeats) and the realized examples/s at
            # the step's batch geometry (the measuredGoodput hint).
            # EWMA alpha 0.2 — a few fit intervals of memory, jitter
            # smoothed out.
            if step_time > 0:
                prev = _state.step_time_ewma
                _state.step_time_ewma = (
                    step_time if prev is None
                    else (1 - alpha) * prev + alpha * step_time
                )
                prev = _state.examples_ewma
                _state.examples_ewma = (
                    examples_s if prev is None
                    else (1 - alpha) * prev + alpha * examples_s
                )
            # The allocator's 2x scale-up gate works in CHIPS (the
            # policy's replica axis is chips once topology search is
            # in play), so profiled coverage must count chips too: a
            # dp=1 x sp=8 run has profiled 8 chips, not 1 replica —
            # otherwise sp-factorized jobs would be permanently
            # capped at 2 chips.
            sp, tp, ss, ep, _micro = active_topology()
            _state.max_profiled_replicas = max(
                _state.max_profiled_replicas,
                env.num_replicas() * sp * tp * ss * ep,
            )
    if not suppressed:
        _maybe_fit_and_report()


def record_checkpoint_save(
    snapshot_s: float,
    write_s: float,
    per_state: dict,
    kind: str = "full",
    total_bytes: int | None = None,
) -> None:
    """Measured phase durations AND sizes of the last completed save.
    Called from the BACKGROUND WRITER thread under the async pipeline
    (checkpoint._record_save_metrics) while the fit thread may be
    reading ``restart_stats`` — the lock keeps the fields one
    consistent observation (a torn read would pair a new snapshot
    time with the previous save's write time). ``kind`` is "full" or
    "delta"; a full save's bytes also become the delta-ratio
    denominator."""
    with _profile_lock:
        _state.ckpt_snapshot_s = float(snapshot_s)
        _state.ckpt_write_s = float(write_s)
        _state.ckpt_per_state = dict(per_state)
        _state.ckpt_save_kind = kind
        if total_bytes is not None:
            _state.ckpt_save_bytes = int(total_bytes)
            if kind == "full":
                _state.ckpt_full_bytes = int(total_bytes)


def record_handoff(seconds: float, transferred_bytes: int) -> None:
    """Measured peer-to-peer handoff transfer (successor side): the
    whole manifest+chunk fetch in seconds and bytes. Feeds
    ``restartStats`` so Pollux prices a *planned* rescale at the
    handoff's cost, not the storage round-trip's."""
    with _profile_lock:
        _state.handoff_s = float(seconds)
        _state.handoff_bytes = int(transferred_bytes)


def record_checkpoint_restore(name: str, seconds: float) -> None:
    """Measured restore duration of one state at incarnation start."""
    with _profile_lock:
        _state.restore_per_state[name] = float(seconds)


def record_retune() -> None:
    """An in-process (atomic_bsz, accum) re-tune was adopted — a
    rescale that cost zero restarts."""
    with _profile_lock:
        _state.num_retunes += 1


def restart_stats() -> dict | None:  # wire: produces=restart_stats
    """Measured rescale-cost components for the sched-hints payload:
    ``snapshotS``/``writeS`` from the last save, ``restoreS`` summed
    over this incarnation's state restores, ``overlapFrac`` = the
    fraction of the save pipeline that runs off the training critical
    path (write / (snapshot + write)). None until something has been
    measured. Runs on the fit thread; the lock pins one consistent
    snapshot of the writer-thread-updated fields (summing
    ``restore_per_state`` while a restore inserts would raise
    "dict changed size during iteration")."""
    with _profile_lock:
        if (
            _state.ckpt_snapshot_s is None
            and not _state.restore_per_state
            and _state.handoff_s is None
        ):
            return None
        stats: dict = {"numRetunes": _state.num_retunes}
        if _state.ckpt_snapshot_s is not None:
            snap = _state.ckpt_snapshot_s
            write = _state.ckpt_write_s or 0.0
            stats["snapshotS"] = round(snap, 4)
            stats["writeS"] = round(write, 4)
            if snap + write > 0:
                stats["overlapFrac"] = round(
                    write / (snap + write), 4
                )
        # Sizes: delta-vs-full timings are meaningless without the
        # bytes behind them, and the policy's restart pricing wants
        # the transfer volume, not just the wall clock.
        if _state.ckpt_save_bytes is not None:
            stats["saveBytes"] = _state.ckpt_save_bytes
            stats["saveKind"] = _state.ckpt_save_kind or "full"
            if (
                _state.ckpt_save_kind == "delta"
                and _state.ckpt_full_bytes
            ):
                stats["deltaRatio"] = round(
                    _state.ckpt_save_bytes
                    / _state.ckpt_full_bytes,
                    4,
                )
        if _state.handoff_s is not None:
            stats["handoffS"] = round(_state.handoff_s, 4)
            stats["handoffBytes"] = _state.handoff_bytes or 0
        if _state.restore_per_state:
            stats["restoreS"] = round(
                sum(_state.restore_per_state.values()), 4
            )
        return stats


def step_time_ewma() -> float | None:
    """This process's smoothed step time (seconds), or None before the
    first profiled step — what the heartbeat thread piggybacks for
    graftwatch's straggler detection."""
    with _profile_lock:
        return _state.step_time_ewma


def measured_goodput() -> float | None:
    """Realized goodput (useful examples/s): the measured throughput
    EWMA times the statistical efficiency at the running batch size,
    under the CURRENT fitted grad params. None until a step has been
    profiled and grad params exist. This is the measured half of
    graftwatch's predicted-vs-realized drift monitor — computed from
    observations, with only the efficiency weighting shared with the
    model, so a mis-fitted perf model shows up as drift instead of
    cancelling out."""
    with _profile_lock:
        examples = _state.examples_ewma
        global_bsz = _state.last_global_bsz
        grad = _state.grad_params
        init = _state.init_batch_size
    return _goodput_from(examples, global_bsz, grad, init)


def _goodput_from(examples, global_bsz, grad, init) -> float | None:
    if examples is None or not global_bsz or grad is None or not init:
        return None
    scale = global_bsz / init
    denom = grad.var / scale + grad.sqr
    gain = (grad.var + grad.sqr) / denom if denom > 0 else 1.0
    return examples * gain / scale


def raw_goodput() -> float | None:
    """Unfiltered realized goodput: the same statistical-efficiency
    weighting as :func:`measured_goodput` but over the raw throughput
    EWMA that includes unhealthy and rolled-back steps. The
    guarded-vs-raw gap is the throughput a flapping job wastes —
    exported via the ``guardStats`` hint for the per-job Grafana
    panel."""
    with _profile_lock:
        examples = _state.raw_examples_ewma
        global_bsz = _state.last_global_bsz
        grad = _state.grad_params
        init = _state.init_batch_size
    return _goodput_from(examples, global_bsz, grad, init)


def note_unhealthy_step(n: int = 1) -> None:
    """The guard condemned the current step: count it and suppress
    the next ``n`` profile samples from the guarded EWMA and perf fit
    (the dataloader records a step's sample only after the trainer's
    guard has graded it). Raw EWMAs still record everything."""
    with _profile_lock:
        _state.unhealthy_steps += 1
        _state.suppress_profile_steps += max(int(n), 0)


def unhealthy_steps() -> int:
    """Guard-condemned steps observed this incarnation."""
    with _profile_lock:
        return _state.unhealthy_steps


def update_grad_params(sqr: float, var: float) -> None:
    """Latest GNS estimates from the train step's fused statistics."""
    with _profile_lock:
        _state.grad_params = GradParams(sqr=float(sqr), var=float(var))


def update_progress(progress: float) -> None:
    _state.progress = float(progress)


def _fit() -> PerfParams | None:
    nodes, replicas, bszs = [], [], []
    sps, tps, sss, eps, micros = [], [], [], [], []
    accum_times, optim_times = [], []
    with _profile_lock:
        snapshot = [
            (key, _ProfileEntry(**vars(entry)))
            for key, entry in _state.profile.items()
        ]
    chunks = _state.pipeline_chunks
    interleaves = []
    for (n, r, sp, tp, ss, ep, micro, bsz), entry in snapshot:
        if entry.optim_count == 0:
            continue
        # A missing calibration falls back to the optim time, which
        # keeps the fit feasible on fresh jobs.
        if entry.accum_count > 0:
            accum = entry.accum_time_sum / entry.accum_count
        else:
            accum = entry.optim_time_sum / entry.optim_count
        nodes.append(n)
        replicas.append(r)
        sps.append(sp)
        tps.append(tp)
        sss.append(ss)
        eps.append(ep)
        micros.append(micro)
        bszs.append(bsz)
        accum_times.append(accum)
        optim_times.append(entry.optim_time_sum / entry.optim_count)
        # A chunk-declared job runs the interleaved schedule whenever
        # the observed (ss, M) admits it — the fit must model those
        # rows with the v-shrunken bubble or it mis-attributes the
        # savings to the compute terms (and the topology search would
        # then discount the bubble twice).
        runnable = (
            chunks > 0 and ss > 1
            and chunks % ss == 0 and micro >= ss
        )
        interleaves.append(chunks // ss if runnable else 1)
    if not nodes:
        return None
    with trace.span("goodput.fit", points=len(nodes)):
        return fit_perf_params(
            nodes,
            replicas,
            bszs,
            accum_times,
            optim_times,
            seq_shards=sps,
            model_shards=tps,
            stage_shards=sss,
            pipeline_micro=micros,
            expert_shards=eps,
            pipeline_interleave=interleaves,
        )


def _maybe_fit_and_report(
    now: float | None = None, interval: float | None = None
) -> None:
    global _last_fit_time
    interval = _default_fit_interval() if interval is None else interval
    now = time.monotonic() if now is None else now
    if _last_fit_time is not None and now - _last_fit_time < interval:
        return
    _last_fit_time = now
    if env.replica_rank() != 0:
        return
    # Fit in the background: the refit compiles/solves on the host and
    # must never stall the training step loop.
    global _fit_thread
    if _fit_thread is None or not _fit_thread.is_alive():
        _fit_thread = threading.Thread(
            target=fit_and_report_now,
            name="adaptdl-fit",
            daemon=True,
        )
        _fit_thread.start()
        _ensure_atexit_join()


_atexit_registered = False


def _ensure_atexit_join() -> None:
    """Join any in-flight fit at interpreter exit: a daemon thread
    killed mid-XLA-call aborts the process with a C++ exception."""
    global _atexit_registered
    if _atexit_registered:
        return
    _atexit_registered = True
    import atexit

    def _join():
        if _fit_thread is not None and _fit_thread.is_alive():
            _fit_thread.join(timeout=60)

    atexit.register(_join)


def fit_and_report_now() -> None:  # wire: produces=sched_hints
    """Refit perf params and (best-effort) post sched hints."""
    perf = _fit()
    with _profile_lock:
        if perf is not None:
            _state.perf_params = perf
        # Snapshot the cross-thread fields once, under the lock; the
        # hint assembly below works on the local copies.
        perf_params = _state.perf_params
        grad_params = _state.grad_params
    if _state.init_batch_size is None:
        return
    hints = sched_hints.empty_hints()
    hints["initBatchSize"] = _state.init_batch_size
    if _state.local_bsz_bounds is not None:
        hints["localBszBounds"] = list(_state.local_bsz_bounds)
    hints["maxBatchSize"] = _state.max_batch_size
    hints["maxProfiledReplicas"] = _state.max_profiled_replicas
    hints["gradientAccumulation"] = _state.gradient_accumulation
    hints["maxSeqShards"] = _state.max_seq_shards
    hints["maxModelShards"] = _state.max_model_shards
    hints["maxStageShards"] = _state.max_stage_shards
    hints["maxExpertShards"] = _state.max_expert_shards
    hints["maxPipelineMicro"] = _state.max_pipeline_micro
    hints["pipelineMicrobatches"] = _topology_suffix()[4]
    hints["pipelineChunks"] = _state.pipeline_chunks
    if _state.mesh_shape_grid is not None:
        hints["meshShapeGrid"] = [
            list(shape) for shape in _state.mesh_shape_grid
        ]
    measured = measured_goodput()
    if measured is not None:
        # graftwatch's drift monitor pairs this with the model's
        # prediction at the published allocation each allocator cycle.
        hints["measuredGoodput"] = round(measured, 6)
    stats = restart_stats()
    if stats is not None:
        # Measured rescale cost: the supervisor prices checkpoint-
        # restart decisions against these instead of an assumed
        # penalty (sched/allocator.job_info_from_hints).
        hints["restartStats"] = stats
    try:
        from adaptdl_tpu import guard as guard_mod

        gstats = guard_mod.guard_stats()
    except Exception:  # noqa: BLE001 - guard is observability here
        gstats = None
    if gstats is not None:
        # Numeric-health summary (incidents, rollbacks, last-good
        # age, raw-vs-guarded goodput) for graftwatch's per-job
        # series and the Grafana guard panels.
        hints["guardStats"] = gstats
    if grad_params is not None:
        hints["gradParams"] = dict(grad_params._asdict())
    if perf_params is not None:
        hints["perfParams"] = {
            k: float(v) for k, v in perf_params._asdict().items()
        }
    sched_hints.post_sched_hints(hints)
    # Piggyback the trace flush on the hint cadence: the worker's
    # buffered spans reach the supervisor's per-job trace store (and
    # its /metrics histograms) without a dedicated reporting thread.
    trace.flush_to_supervisor()


def get_goodput_fn() -> GoodputFunction | None:
    """Assembled from the latest fitted perf + grad params, or None
    until both exist (reference: _metrics.py:96-101)."""
    with _profile_lock:
        perf_params = _state.perf_params
        grad_params = _state.grad_params
    if (
        perf_params is None
        or grad_params is None
        or _state.init_batch_size is None
    ):
        return None
    return GoodputFunction(
        perf_params, grad_params, _state.init_batch_size
    )


class _MetricsCheckpoint(checkpoint.State):
    """Profiles and fitted params survive restarts, so a rescaled job
    does not re-learn its performance model from scratch."""

    def __init__(self):
        super().__init__("adaptdl_metrics")

    def sync(self) -> None:
        # Rank 0's view is authoritative; no cross-replica merge needed
        # because every replica profiles identical fused steps.
        pass

    def save(self, fileobj):
        # Snapshot phase runs on the trainer thread while the fit /
        # writer threads may be live — take one consistent view.
        with _profile_lock:
            payload = self._payload_locked()
        pickle.dump(payload, fileobj)

    def _payload_locked(self):  # holds-lock: _profile_lock
        return {
            "profile": dict(_state.profile),
            "perf_params": _state.perf_params,
            "grad_params": _state.grad_params,
            "init_batch_size": _state.init_batch_size,
            "max_batch_size": _state.max_batch_size,
            "local_bsz_bounds": _state.local_bsz_bounds,
            "gradient_accumulation": _state.gradient_accumulation,
            "max_profiled_replicas": _state.max_profiled_replicas,
            "max_seq_shards": _state.max_seq_shards,
            "max_model_shards": _state.max_model_shards,
            "max_stage_shards": _state.max_stage_shards,
            "max_expert_shards": _state.max_expert_shards,
            "pipeline_microbatches": _state.pipeline_microbatches,
            "max_pipeline_micro": _state.max_pipeline_micro,
            "mesh_shape_grid": _state.mesh_shape_grid,
            "progress": _state.progress,
            # The save that persists this payload is still in flight
            # when these are read back, so they describe the PREVIOUS
            # save — exactly what a restarted incarnation can report
            # before its own first save completes.
            "ckpt_snapshot_s": _state.ckpt_snapshot_s,
            "ckpt_write_s": _state.ckpt_write_s,
            "ckpt_per_state": dict(_state.ckpt_per_state),
            "ckpt_save_kind": _state.ckpt_save_kind,
            "ckpt_save_bytes": _state.ckpt_save_bytes,
            "ckpt_full_bytes": _state.ckpt_full_bytes,
            "handoff_s": _state.handoff_s,
            "handoff_bytes": _state.handoff_bytes,
            "num_retunes": _state.num_retunes,
            "raw_step_time_ewma": _state.raw_step_time_ewma,
            "raw_examples_ewma": _state.raw_examples_ewma,
            "unhealthy_steps": _state.unhealthy_steps,
        }

    def load(self, fileobj):
        payload = pickle.load(fileobj)
        old_micro = max(int(payload.get("pipeline_microbatches", 4)), 1)
        profile = defaultdict(_ProfileEntry)
        for key, entry in payload["profile"].items():
            if len(key) == 3:  # pre-sp/tp checkpoint: (n, r, bsz)
                n, r, bsz = key
                key = (n, r, 1, 1, 1, 1, 1, bsz)
            elif len(key) == 5:  # pre-stage: (n, r, sp, tp, bsz)
                n, r, sp, tp, bsz = key
                key = (n, r, sp, tp, 1, 1, 1, bsz)
            elif len(key) == 6:  # pre-expert/micro: (n,r,sp,tp,ss,bsz)
                n, r, sp, tp, ss, bsz = key
                # Old checkpoints ran stage schedules at the state's
                # default M.
                key = (
                    n, r, sp, tp, ss, 1, old_micro if ss > 1 else 1, bsz
                )
            profile[key] = entry
        # Restore runs at incarnation start, but a fit thread kicked
        # by an early profile_step may already be reading.
        with _profile_lock:
            _state.profile = profile
            _state.perf_params = payload["perf_params"]
            _state.grad_params = payload["grad_params"]
            _state.ckpt_snapshot_s = payload.get("ckpt_snapshot_s")
            _state.ckpt_write_s = payload.get("ckpt_write_s")
            _state.ckpt_per_state = dict(
                payload.get("ckpt_per_state", {})
            )
            _state.ckpt_save_kind = payload.get("ckpt_save_kind")
            _state.ckpt_save_bytes = payload.get("ckpt_save_bytes")
            _state.ckpt_full_bytes = payload.get("ckpt_full_bytes")
            _state.handoff_s = payload.get("handoff_s")
            _state.handoff_bytes = payload.get("handoff_bytes")
            _state.num_retunes = int(payload.get("num_retunes", 0))
            # Pre-guard checkpoints carry no raw-EWMA fields.
            _state.raw_step_time_ewma = payload.get("raw_step_time_ewma")
            _state.raw_examples_ewma = payload.get("raw_examples_ewma")
            _state.unhealthy_steps = int(
                payload.get("unhealthy_steps", 0)
            )
        _state.init_batch_size = payload["init_batch_size"]
        _state.max_batch_size = payload["max_batch_size"]
        _state.local_bsz_bounds = payload["local_bsz_bounds"]
        _state.gradient_accumulation = payload["gradient_accumulation"]
        _state.max_profiled_replicas = payload["max_profiled_replicas"]
        _state.max_seq_shards = payload.get("max_seq_shards", 1)
        _state.max_model_shards = payload.get("max_model_shards", 1)
        _state.max_stage_shards = payload.get("max_stage_shards", 1)
        _state.max_expert_shards = payload.get("max_expert_shards", 1)
        _state.pipeline_microbatches = old_micro
        _state.max_pipeline_micro = payload.get(
            "max_pipeline_micro", max(8, old_micro)
        )
        grid = payload.get("mesh_shape_grid")
        _state.mesh_shape_grid = (
            tuple(tuple(shape) for shape in grid) if grid else None
        )
        _state.progress = payload["progress"]


def ensure_checkpoint_registered() -> None:
    """Register the metrics state so that it is saved, and restore it
    from the job's newest checkpoint — once: a second call finds it
    registered and leaves it alone. Every process calls this (all load
    the same payload); without a checkpoint it only registers."""
    try:
        state = _MetricsCheckpoint()
    except ValueError:
        return  # already registered, and restored then
    checkpoint.load_state(state)
