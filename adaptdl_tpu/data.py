"""Elastic, adaptive-batch-size data pipeline.

``AdaptiveDataLoader`` is the user's inner loop and the place where all
the elasticity machinery meets (reference:
adaptdl/adaptdl/torch/data.py):

- **ElasticSampler**: deterministic epoch shuffling; partitions the
  *remaining* samples of an epoch evenly across replicas, so a job
  restarted mid-epoch at a different replica count divides the rest of
  the epoch among its new replicas (reference: data.py:63-111).
- **adaptive batch size**: each loop entry (and periodically during
  it) re-optimizes (atomic_bsz, accum_steps) with the fitted goodput
  function, adopting a new configuration only for >5% predicted
  speedup; the result is broadcast from rank 0 so every replica uses
  identical shapes (reference: data.py:270-305). TPU delta: candidate
  sizes are *bucketed* (multiples of 8 below 128, multiples of 64
  above) because every new shape is an XLA recompile — hysteresis plus
  bucketing keeps recompiles rare.
- **graceful preemption**: once per step the loader polls the SIGTERM
  flag through an *async* control-plane allreduce (overlapped with the
  device step), and when all replicas agree, checkpoints and exits
  with code 143 (reference: data.py:311-334).
- **replay**: finished loops are skipped after a restart; the
  interrupted loop resumes at its saved position (reference:
  data.py:361-379).

Batch contract (replica-major, matching
``ElasticTrainer.shard_batch``'s data-axis layout): on a single-process
job the loader yields the *global* host batch, shaped
``[num_replicas * (accum_steps+1) * atomic_bsz, ...]``; on a
multi-host job (``ADAPTDL_NUM_PROCESSES > 1``) it yields only this
process's contiguous block of those rows (``1/num_processes`` of
them), which ``shard_batch`` reassembles into the global array. Either
way one process feeds all its addressable devices (the SPMD model),
instead of the reference's one-loader-per-GPU-process model.
"""

from __future__ import annotations

import logging
import sys
import time
from typing import Any, Iterator

import numpy as np

from adaptdl_tpu import (
    _signal,
    checkpoint,
    collective,
    env,
    metrics,
    sched_hints,
    trace,
)

LOG = logging.getLogger(__name__)

SPEEDUP_THRESHOLD = 1.05
_current_dataloader: "AdaptiveDataLoader | None" = None


def current_dataloader() -> "AdaptiveDataLoader | None":
    return _current_dataloader


def bucket_atomic_bsz(atomic_bsz: int) -> int:
    """Round a candidate atomic batch size DOWN onto the recompile
    grid. Rounding down keeps every batch-size cap the goodput
    optimizer already enforced (max_batch_size, local bounds) intact;
    rounding up could silently exceed them."""
    if atomic_bsz <= 8:
        return max(int(atomic_bsz), 1)
    if atomic_bsz <= 128:
        return int(atomic_bsz // 8 * 8)
    return int(atomic_bsz // 64 * 64)


class ElasticSampler:
    """Deterministic shuffle + remaining-sample partition.

    ``set_position(epoch, index)`` establishes where the epoch stands;
    ``replica_indices(rank)`` yields the indices replica ``rank`` will
    consume for the rest of the epoch. All replicas derive the same
    permutation from the epoch number alone.
    """

    def __init__(self, dataset_size: int, shuffle: bool = True, seed: int = 0):
        self.dataset_size = dataset_size
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self.index = 0  # samples of this epoch already consumed
        self._perm_cache: tuple[int, np.ndarray] | None = None

    def set_position(self, epoch: int, index: int) -> None:
        self.epoch = epoch
        self.index = index

    def _permutation(self) -> np.ndarray:
        if not self.shuffle:
            return np.arange(self.dataset_size)
        if self._perm_cache is None or self._perm_cache[0] != self.epoch:
            rng = np.random.default_rng((self.seed, self.epoch))
            self._perm_cache = (self.epoch, rng.permutation(self.dataset_size))
        return self._perm_cache[1]

    def remaining(self) -> int:
        return max(self.dataset_size - self.index, 0)

    def next_indices(self, count: int) -> np.ndarray:
        """The next ``count`` sample indices of this epoch, in
        replica-major order: caller lays them out contiguously per
        replica, matching the data-axis sharding split."""
        return self._permutation()[self.index : self.index + count]


class AdaptiveDataLoader:
    """Iterates global batches with adaptive sizing and elasticity.

    Args:
      dataset: indexable providing ``dataset[i] -> pytree of arrays``
        OR a dict of equal-length numpy arrays (fast path).
      batch_size: the initial (and LR-reference) global batch size.
      shuffle: deterministic per-epoch shuffling.
      drop_last: drop the trailing partial batch (required under XLA's
        static shapes; the epoch accounting treats the tail as done).
      name: checkpoint registry key, must be unique per loader.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        shuffle: bool = True,
        drop_last: bool = True,
        seed: int = 0,
        name: str = "adaptdl_dataloader",
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.drop_last = drop_last
        self._size = _dataset_size(dataset)
        self.sampler = ElasticSampler(self._size, shuffle, seed)
        self._max_batch_size: int | None = None
        self._local_bsz_bounds: tuple[int, int] | None = None
        self._gradient_accumulation = False
        # Current configuration (all replicas agree).
        self._atomic_bsz = max(batch_size // env.num_replicas(), 1)
        self._accum_steps = 0
        # Replay bookkeeping, keyed per epoch: after a restart only the
        # interrupted epoch re-runs, so finished-loop counts from other
        # epochs must not suppress its loops (reference keys loop
        # positions per epoch for the same reason, data.py:336-379).
        self._loops_finished: dict[int, int] = {}
        self._loops_started: dict[int, int] = {}
        self._exit_future = None
        self._reoptimize_every = 50  # optimizer steps between re-opts
        # Periodic fault-tolerance saves (ADAPTDL_CKPT_EVERY_STEPS):
        # deterministic in the step counter so every replica calls the
        # collective sync() in lockstep; pipelined (wait=False) so
        # only the snapshot phase blocks the loop.
        self._ckpt_every_steps = env.checkpoint_every_steps()
        self._last_profiled_config: tuple[int, int] | None = None
        # Numeric-health guard (guard.py): poisoned sample ranges the
        # deterministic sampler must never re-feed, as (epoch, start,
        # end) half-open index spans into the epoch permutation, plus
        # the span of the batch most recently yielded (the guard's
        # blame identity for the step it is grading). Persisted with
        # the loader position so a rollback's resume still skips them.
        self._skip_ranges: list[tuple[int, int, int]] = []
        self._last_span: tuple[int, int, int] | None = None
        # Bumped by every checkpoint restore. The iterator compares it
        # across a yield: a guard rollback restores the sampler
        # position DURING the step, and the restored cursor is then
        # authoritative — advancing it past the in-flight batch would
        # silently drop the batches it rewound to.
        self._restore_gen = 0
        # True once a (bsz, accum) decision has been taken this
        # incarnation: only *changes* after that count as live
        # re-tunes (the first decision is initialization, not a
        # rescale avoided).
        self._decided_once = False
        metrics.set_batch_size_config(batch_size)
        self._checkpoint = _DataLoaderCheckpoint(name, self)
        checkpoint.load_state(self._checkpoint)

    # -- configuration -------------------------------------------------

    def autoscale_batch_size(
        self,
        max_batch_size: int,
        local_bsz_bounds: tuple[int, int] | None = None,
        gradient_accumulation: bool = False,
    ) -> None:
        """Let the goodput model choose the global batch size up to
        ``max_batch_size`` (reference API: data.py:242-268)."""
        if max_batch_size < self.batch_size:
            raise ValueError("max_batch_size below initial batch size")
        self._max_batch_size = max_batch_size
        self._local_bsz_bounds = local_bsz_bounds
        self._gradient_accumulation = gradient_accumulation
        metrics.set_batch_size_config(
            self.batch_size,
            max_batch_size,
            local_bsz_bounds,
            gradient_accumulation,
        )

    @property
    def current_atomic_bsz(self) -> int:
        return self._atomic_bsz

    @property
    def current_accum_steps(self) -> int:
        return self._accum_steps

    @property
    def current_batch_size(self) -> int:
        """Global batch size currently in effect."""
        return (
            env.num_replicas()
            * self._atomic_bsz
            * (self._accum_steps + 1)
        )

    @property
    def current_local_bsz(self) -> int:
        return self._atomic_bsz * (self._accum_steps + 1)

    # -- adaptive sizing ----------------------------------------------

    def _optimize_batch_size(self) -> None:
        """Re-optimize (atomic_bsz, accum_steps); adopt on >5% speedup."""
        with trace.span("policy.optimize") as attrs:
            if env.replica_rank() == 0:
                decision = self._rank0_decision()
            else:
                decision = None
            decision = collective.broadcast(decision)
            self.apply_retune(*decision)
            attrs["atomic_bsz"] = self._atomic_bsz
            attrs["accum_steps"] = self._accum_steps

    def apply_retune(self, atomic_bsz: int, accum_steps: int) -> None:
        """Adopt a new (atomic_bsz, accum_steps) IN-PROCESS — the live
        re-tune fast path. The sampler position, epoch bookkeeping,
        and the trainer's jit cache (keyed by these shapes) all carry
        over; nothing restarts and ``ADAPTDL_NUM_RESTARTS`` does not
        move. Must be called with the same values on every replica
        (the internal path broadcasts from rank 0)."""
        decision = (max(int(atomic_bsz), 1), max(int(accum_steps), 0))
        changed = decision != (self._atomic_bsz, self._accum_steps)
        self._atomic_bsz, self._accum_steps = decision
        if changed and self._decided_once:
            LOG.info(
                "live re-tune: atomic_bsz=%d accum_steps=%d "
                "(no restart)", *decision,
            )
            metrics.record_retune()
        self._decided_once = True

    def _rank0_decision(self) -> tuple[int, int]:
        num_replicas = env.num_replicas()
        if self._max_batch_size is None:
            return max(self.batch_size // num_replicas, 1), 0
        remote = self._supervisor_decision(num_replicas)
        if remote is not None:
            return remote
        goodput_fn = metrics.get_goodput_fn()
        if goodput_fn is None:
            # No fitted model yet: split the initial batch size.
            atomic = max(self.batch_size // num_replicas, 1)
            if self._local_bsz_bounds is not None:
                atomic = int(
                    np.clip(atomic, *self._local_bsz_bounds)
                )
            return atomic, 0
        num_nodes = env.num_nodes()
        # Score configurations at the topology that is actually
        # running: the ring/TP collective terms belong in both sides
        # of the comparison, and the atomic-bsz memory ceiling scales
        # with the shard group (each chip holds 1/(sp*tp) of a
        # microbatch's activations).
        sp, tp, ss, ep, pipeline_micro = metrics.active_topology()
        # Memory-ceiling group: sp/tp shard each microbatch's
        # activations; pipeline stages and expert shards do NOT
        # (in-flight microbatches / replicated group batches keep
        # per-chip activation memory ~constant).
        group = sp * tp
        pipeline_micro = pipeline_micro if ss > 1 else 1
        # The restored config may be infeasible at the new replica
        # count (e.g. global batch beyond max_batch_size after growing
        # the job); then the optimizer's choice is adopted outright.
        current_feasible = (
            self.current_batch_size <= self._max_batch_size
            and (
                self._local_bsz_bounds is None
                or self._local_bsz_bounds[0]
                <= self._atomic_bsz
                <= self._local_bsz_bounds[1] * group
            )
            and self.current_batch_size >= self.batch_size
        )
        current_goodput = (
            goodput_fn(
                num_nodes,
                num_replicas,
                self._atomic_bsz,
                self._accum_steps,
                seq_shards=sp,
                model_shards=tp,
                stage_shards=ss,
                pipeline_micro=pipeline_micro,
                expert_shards=ep,
            )
            if current_feasible
            else 0.0
        )
        _, atomic_bsz, accum_steps = goodput_fn.optimize(
            num_nodes,
            num_replicas,
            max_batch_size=self._max_batch_size,
            atomic_bsz_range=self._local_bsz_bounds,
            accumulation=self._gradient_accumulation,
            seq_shards=sp,
            model_shards=tp,
            stage_shards=ss,
            pipeline_micro=pipeline_micro,
            expert_shards=ep,
        )
        atomic_bsz = bucket_atomic_bsz(int(atomic_bsz))
        if self._local_bsz_bounds is not None:
            atomic_bsz = int(
                np.clip(
                    atomic_bsz,
                    self._local_bsz_bounds[0],
                    self._local_bsz_bounds[1] * group,
                )
            )
        candidate_goodput = goodput_fn(
            num_nodes,
            num_replicas,
            atomic_bsz,
            int(accum_steps),
            seq_shards=sp,
            model_shards=tp,
            stage_shards=ss,
            pipeline_micro=pipeline_micro,
            expert_shards=ep,
        )
        if candidate_goodput > SPEEDUP_THRESHOLD * current_goodput:
            return atomic_bsz, int(accum_steps)
        return self._atomic_bsz, self._accum_steps

    def _supervisor_decision(  # wire: consumes=config,batch_config
        self, num_replicas: int
    ) -> tuple[int, int] | None:
        """The allocator's published (atomicBsz, accumSteps) for this
        job, if any — computed from the same fitted goodput model the
        local path uses, already hysteresis-filtered, and counted by
        the supervisor as a live re-tune rather than a restart. The
        fetch is best-effort (rank 0 only, re-optimization cadence):
        None falls back to the local decision."""
        remote = sched_hints.fetch_job_config()
        if not remote or not remote.get("batchConfig"):
            return None
        # The published config belongs to the published ALLOCATION. If
        # the allocator just decided a different device set, this
        # incarnation is about to be restarted — adopting a config
        # sized for the future world would skew the remaining steps'
        # profile for nothing.
        allocation = remote.get("allocation") or []
        if allocation and len(allocation) != num_replicas:
            return None
        cfg = remote["batchConfig"]
        try:
            atomic = bucket_atomic_bsz(int(cfg.get("atomicBsz", 0)))
            accum = max(int(cfg.get("accumSteps", 0)), 0)
        except (TypeError, ValueError):
            return None
        if atomic < 1:
            return None
        # Same bucketing/bounds discipline as a local decision: the
        # allocator optimizes off the recompile grid and without the
        # sp/tp activation-sharding allowance.
        sp, tp, _, _, _ = metrics.active_topology()
        if self._local_bsz_bounds is not None:
            atomic = int(
                np.clip(
                    atomic,
                    self._local_bsz_bounds[0],
                    self._local_bsz_bounds[1] * sp * tp,
                )
            )
        total = num_replicas * atomic * (accum + 1)
        if total > self._max_batch_size:
            return None
        return atomic, accum

    # -- elasticity ----------------------------------------------------

    def _check_exit(self) -> None:
        """Overlapped exit-flag agreement; checkpoint+exit(143) once
        every replica has seen the signal. A preemption notice routes
        the final save through the urgent drain — deadline-budgeted,
        joins any in-flight async write, reports to the supervisor —
        instead of the plain blocking save."""
        if self._exit_future is not None:
            should_exit = self._exit_future.result()
            if should_exit:
                from adaptdl_tpu.sched import preemption

                notice = preemption.notice_active()
                # signal -> every replica agreed: the head of the
                # rescale's trace. The handler's one clock is the wall
                # clock that the span starts on (a replica that agreed
                # on a peer's signal has none: a point).
                since = _signal.signal_time()
                # graftcheck: disable=GC701 (the signal handler may
                # only store one clock, and the span's start must be
                # the machine's wall clock to align two processes)
                waited = time.time() - since if since else 0.0
                step_s = metrics.step_time_ewma()
                trace.record_span(
                    "exit.agree",
                    waited,
                    ts=since,
                    replicas=env.num_replicas(),
                    steps=round(waited / step_s, 2) if step_s else None,
                    notice=bool(notice),
                )
                if notice:
                    LOG.info(
                        "graceful exit (preemption notice): urgent "
                        "drain then exit 143"
                    )
                    preemption.urgent_drain()
                else:
                    LOG.info(
                        "graceful exit: saving states and exiting 143"
                    )
                    serve = env.handoff_enabled()
                    handle = checkpoint.save_all_states(
                        retain_snapshots=serve
                    )
                    # PLANNED rescale (no reclaim notice — the VM
                    # survives us): leave a detached shard server
                    # behind so the successor pulls state peer-to-peer
                    # instead of round-tripping through storage. The
                    # durable save above stays the fallback, and the
                    # server reuses ITS retained snapshots — one
                    # device->host pass, identical bytes both ways.
                    if serve:
                        from adaptdl_tpu import handoff

                        handoff.spawn_server(
                            snapshots=handle.snapshots
                        )
                # Closed by bootstrap's atexit hook, the last of the
                # program's: what its joins cost on the way out.
                trace.begin_pending("exit.atexit")
                sys.exit(_signal.GRACEFUL_EXIT_CODE)
        self._exit_future = collective.allreduce_async(
            bool(_signal.get_exit_flag()), lambda vs: any(vs)
        )

    # -- numeric-health guard hooks -----------------------------------

    def current_batch_span(self) -> tuple[int, int, int] | None:
        """(epoch, start, end) permutation span of the batch most
        recently yielded — the guard's data identity for the step it
        is grading. None before the first batch."""
        return self._last_span

    def add_skip_range(self, epoch: int, start: int, end: int) -> None:
        """Record a poisoned sample range the sampler must skip from
        now on (all replicas derive the same permutation, so the same
        call on every replica keeps batches aligned). Called by the
        guard after a skip/rollback decision; persisted by the next
        checkpoint save."""
        span = (int(epoch), int(start), int(end))
        if span not in self._skip_ranges:
            self._skip_ranges.append(span)
            LOG.warning(
                "guard: sampler will skip poisoned range "
                "epoch=%d [%d, %d)", *span
            )

    def _skip_bound(self, take: int) -> int | None:
        """Where the sampler should jump if its next ``take`` samples
        overlap a poisoned range; None when the batch is clean."""
        start = self.sampler.index
        end = start + take
        for epoch, s0, e0 in self._skip_ranges:
            if epoch == self.sampler.epoch and s0 < end and e0 > start:
                return e0
        return None

    # -- iteration -----------------------------------------------------

    def __len__(self) -> int:
        return max(self._size // self.current_batch_size, 1)

    def __iter__(self) -> Iterator[Any]:
        global _current_dataloader
        if _current_dataloader is not None:
            raise RuntimeError(
                "only one AdaptiveDataLoader loop may be active"
            )
        epoch = _loop_epoch()
        started = self._loops_started.get(epoch, 0)
        finished = self._loops_finished.get(epoch, 0)
        if started < finished:
            # This loop of this epoch completed before the restart.
            self._loops_started[epoch] = started + 1
            return
        self._loops_started[epoch] = started + 1
        if self.sampler.epoch != epoch:
            # A fresh epoch for this loader (the restored position only
            # applies to the epoch it was saved in).
            self.sampler.set_position(epoch, 0)
        _current_dataloader = self
        # The loader's own work, ``__next__`` entered -> batch yielded,
        # is the phase ``data_next`` of the step cycle (``trace.
        # StepCycle``); between two of them the caller has the clock.
        trace.step_cycle.mark(trace.DATA_NEXT)
        try:
            self._optimize_batch_size()
            steps = 0
            while True:
                remaining = self.sampler.remaining()
                global_bsz = self.current_batch_size
                if remaining == 0 or (
                    remaining < global_bsz and self.drop_last
                ):
                    break
                take = min(global_bsz, remaining)
                skip_to = self._skip_bound(take)
                if skip_to is not None:
                    # Poisoned range (guard): jump the deterministic
                    # position past it without yielding — the same
                    # decision replays identically on every replica
                    # and after every restart. The jump strictly
                    # advances the index, so this cannot loop.
                    self.sampler.index = skip_to
                    continue
                self._check_exit()
                self._last_span = (
                    self.sampler.epoch,
                    self.sampler.index,
                    self.sampler.index + take,
                )
                indices = self.sampler.next_indices(take)
                num_processes = env.num_processes()
                if num_processes > 1:
                    # Multi-host: each process materialises only its
                    # own replicas' rows (replica-major layout, so a
                    # process's block is contiguous); shard_batch
                    # assembles the global array from the local parts.
                    if take % num_processes:
                        raise RuntimeError(
                            "global batch not divisible across "
                            f"{num_processes} processes (take={take}); "
                            "use drop_last=True for multi-host jobs"
                        )
                    block = take // num_processes
                    start = env.process_rank() * block
                    indices = indices[start : start + block]
                batch = _gather(self.dataset, indices)
                config = (self._atomic_bsz, self._accum_steps)
                restore_gen = self._restore_gen
                trace.step_cycle.mark(trace.OUTSIDE)
                start = time.monotonic()
                yield batch
                elapsed = time.monotonic() - start
                trace.step_cycle.mark(trace.DATA_NEXT)
                if self._restore_gen != restore_gen:
                    # A rollback restored the loader mid-step: the
                    # restored position/shape is authoritative, and
                    # the aborted step must not move the cursor or
                    # record a profile sample.
                    continue
                if take == global_bsz:
                    if config == self._last_profiled_config:
                        metrics.profile_step(
                            self._atomic_bsz, self._accum_steps, elapsed
                        )
                    else:
                        # First step at a new shape includes XLA compile
                        # time; recording it would poison the perf fit.
                        self._last_profiled_config = config
                self.sampler.index += take
                steps += 1
                if steps % self._reoptimize_every == 0:
                    self._optimize_batch_size()
                if (
                    self._ckpt_every_steps
                    and steps % self._ckpt_every_steps == 0
                ):
                    checkpoint.save_all_states(wait=False)
            self._loops_finished[epoch] = finished + 1
            # Dead bookkeeping from earlier epochs never replays.
            for key in [k for k in self._loops_finished if k < epoch]:
                del self._loops_finished[key]
                self._loops_started.pop(key, None)
            self.sampler.index = 0
        finally:
            _current_dataloader = None
            trace.step_cycle.leave(trace.DATA_NEXT)


def _loop_epoch() -> int:
    from adaptdl_tpu import epoch as epoch_mod

    current = epoch_mod.current_epoch()
    return current if current is not None else 0


def _dataset_size(dataset) -> int:
    if isinstance(dataset, dict):
        return len(next(iter(dataset.values())))
    return len(dataset)


def _gather(dataset, index: np.ndarray):
    if isinstance(dataset, dict):
        return {k: v[index] for k, v in dataset.items()}
    samples = [dataset[int(i)] for i in index]
    first = samples[0]
    if isinstance(first, dict):
        return {
            k: np.stack([s[k] for s in samples]) for k in first
        }
    if isinstance(first, (tuple, list)):
        return type(first)(
            np.stack([s[j] for s in samples]) for j in range(len(first))
        )
    return np.stack(samples)


class _DataLoaderCheckpoint(checkpoint.State):
    """Persists loop/epoch position for mid-epoch resume (reference:
    data.py:547-575)."""

    def __init__(self, name: str, loader: AdaptiveDataLoader):
        super().__init__(name)
        self._loader = loader

    def save(self, fileobj):
        import pickle

        loader = self._loader
        pickle.dump(
            {
                "epoch": loader.sampler.epoch,
                "index": loader.sampler.index,
                "loops_finished": loader._loops_finished,
                "atomic_bsz": loader._atomic_bsz,
                "accum_steps": loader._accum_steps,
                "skip_ranges": list(loader._skip_ranges),
            },
            fileobj,
        )

    def load(self, fileobj):
        import pickle

        payload = pickle.load(fileobj)
        loader = self._loader
        loader.sampler.set_position(payload["epoch"], payload["index"])
        loader._loops_finished = payload["loops_finished"]
        loader._atomic_bsz = payload["atomic_bsz"]
        loader._accum_steps = payload["accum_steps"]
        # Pre-guard checkpoints carry no skip table.
        loader._skip_ranges = [
            tuple(r) for r in payload.get("skip_ranges", [])
        ]
        loader._restore_gen += 1
