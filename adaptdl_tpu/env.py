"""Environment configuration for elastic TPU jobs.

Every piece of scheduler→job communication happens through environment
variables set at (re)start time, exactly as in the reference design
(reference: adaptdl/adaptdl/env.py:23-173 and
sched/adaptdl_sched/controller.py:374-407): the cluster layer restarts a
job's processes with fresh ``ADAPTDL_*`` variables and the library reads
them here. Nothing else in the framework touches ``os.environ`` for
configuration.

Terminology on TPU:

- a *replica* is one data-parallel model replica. On TPU we use one
  replica per chip, so ``num_replicas`` equals the total chip count of
  the allocated slice(s).
- a *node* in the reference (a GPU host) maps to a *slice* here: the
  unit whose internal links (ICI) are fast and whose cross-unit links
  (DCN) are slow. ``num_nodes`` therefore reports the number of slices,
  which is what the goodput model's inter/intra-network split keys on.
- a *process* is one JAX host process. ``process_rank``/``num_processes``
  describe the multi-host layout (one process per TPU VM host).
"""

from __future__ import annotations

import os

# Env keys also WRITTEN by other modules (launchers assembling child
# process environments, the tuner driving trials) import these
# constants so the key spelling has exactly one home.
TRIAL_CONFIG_KEY = "ADAPTDL_TRIAL_CONFIG"
TRIAL_RESULT_KEY = "ADAPTDL_TRIAL_RESULT_FILE"


def _get_int(name: str, default: int) -> int:
    value = os.environ.get(name)
    return int(value) if value not in (None, "") else default


def _get_float(name: str, default: float) -> float:
    value = os.environ.get(name)
    return float(value) if value not in (None, "") else default


def _get_opt_int(name: str) -> int | None:
    value = os.environ.get(name)
    return int(value) if value not in (None, "") else None


def _get_opt_float(name: str) -> float | None:
    value = os.environ.get(name)
    return float(value) if value not in (None, "") else None


def _get_str(name: str, default: str | None = None) -> str | None:
    value = os.environ.get(name)
    return value if value not in (None, "") else default


def checkpoint_path() -> str | None:
    """Directory for elastic checkpoints, shared across restarts.

    Must be visible to all processes (typically GCS via gcsfuse or an
    NFS/Filestore mount on GKE).
    """
    return _get_str("ADAPTDL_CHECKPOINT_PATH")


def share_path() -> str | None:
    """Shared scratch directory (tensorboard output and the like)."""
    return _get_str("ADAPTDL_SHARE_PATH")


def job_id() -> str | None:
    """Unique job identifier, ``namespace/name`` under the k8s operator."""
    return _get_str("ADAPTDL_JOB_ID")


def master_addr() -> str:
    """Host that runs the control-plane reducer server (rank 0)."""
    return _get_str("ADAPTDL_MASTER_ADDR") or "127.0.0.1"


def master_port() -> int:
    """Port for the control-plane reducer server."""
    return _get_int("ADAPTDL_MASTER_PORT", 0)


def replica_rank() -> int:
    """This replica's rank in [0, num_replicas)."""
    return _get_int("ADAPTDL_REPLICA_RANK", 0)


def num_replicas() -> int:
    """Chips granted to this job at launch.

    The scheduler always exports the job's CHIP count here. Under a
    sharded topology (seq/model/stage/expert shards > 1) the
    data-parallel replica count is ``chips // (sp * tp * ss * ep)`` —
    use :func:`data_parallel_replicas` for that derived value (the
    examples rewrite ADAPTDL_NUM_REPLICAS to it before building the
    trainer, e.g. examples/transformer_lm.py). With every shard axis
    at 1 (the reference's only case) chips == replicas and the value
    can be used directly.
    """
    return _get_int("ADAPTDL_NUM_REPLICAS", 1)


def data_parallel_replicas() -> int:
    """Data-parallel replica groups: chips divided by the sharded-axes
    group size. Falls back to the raw chip count if it doesn't divide
    evenly (a misconfigured topology is surfaced by the mesh builder,
    not hidden here)."""
    group = seq_shards() * model_shards() * stage_shards() * expert_shards()
    chips = num_replicas()
    if group > 1 and chips % group == 0:
        return max(chips // group, 1)
    return chips


def seq_shards() -> int:
    """Sequence-parallel shards per replica group (ring attention).

    A seq-sharded group of chips forms ONE data-parallel replica; the
    scheduler advertises its chosen factorization here and launchers
    build the mesh accordingly. Not a reference concept — the reference
    has no parallelism axis beyond data (SURVEY §2.7).
    """
    return _get_int("ADAPTDL_SEQ_SHARDS", 1)


def model_shards() -> int:
    """Tensor-parallel shards per replica group (GSPMD model axis)."""
    return _get_int("ADAPTDL_MODEL_SHARDS", 1)


def stage_shards() -> int:
    """Pipeline stages per replica group (GPipe stage axis)."""
    return _get_int("ADAPTDL_STAGE_SHARDS", 1)


def expert_shards() -> int:
    """Expert-parallel shards per replica group (MoE all_to_all)."""
    return _get_int("ADAPTDL_EXPERT_SHARDS", 1)


def pipeline_micro() -> int:
    """Scheduler-chosen GPipe microbatch count M for the stage axis.

    Meaningful only when ``stage_shards() > 1``; the goodput topology
    search co-optimizes M with the factorization and publishes it
    here so ``gpipe_loss`` runs the schedule the model was priced at.
    """
    return _get_int(
        "ADAPTDL_PIPELINE_MICRO", 4 if stage_shards() > 1 else 1
    )


def num_nodes() -> int:
    """Number of slices (the reference's "nodes").

    Defaults to ``num_processes()`` — one slice per host process —
    when ``ADAPTDL_NUM_NODES`` is unset.
    """
    return _get_int("ADAPTDL_NUM_NODES", num_processes())


def process_rank() -> int:
    """This JAX host process's rank in [0, num_processes)."""
    return _get_int("ADAPTDL_PROCESS_RANK", replica_rank())


def num_processes() -> int:
    """Total JAX host processes participating in the job.

    Defaults to 1: under SPMD one process drives many replicas (chips),
    unlike the reference's one-process-per-replica model. Multi-host
    launchers must set ``ADAPTDL_NUM_PROCESSES`` explicitly.
    """
    return _get_int("ADAPTDL_NUM_PROCESSES", 1)


def num_restarts() -> int:
    """How many times this job has been restarted by the scheduler.

    Used to index checkpoint directories so that a partially-written
    checkpoint from a dying incarnation can never clobber the previous
    complete one (reference: adaptdl/adaptdl/checkpoint.py:106-133).
    """
    return _get_int("ADAPTDL_NUM_RESTARTS", 0)


def checkpoint_every_steps() -> int:
    """Periodic fault-tolerance checkpoint cadence, in dataloader
    steps (0 = disabled: only the final pre-exit save). Periodic
    saves use the pipelined non-blocking form — the snapshot phase
    blocks the loop briefly, the write overlaps the following steps —
    so the cost of surviving a power loss is the snapshot, not the
    full serialization."""
    return _get_int("ADAPTDL_CKPT_EVERY_STEPS", 0)


def ckpt_full_every() -> int:
    """Force a FULL checkpoint every Nth save; the saves in between
    write *differential* checkpoints (only the chunks whose content
    hash changed since the last full snapshot, Check-N-Run NSDI'22
    style). 1 — the default — disables deltas entirely: every save is
    a full checkpoint, the pre-delta behavior. A drain/preemption
    final save is always forced full regardless of this cadence."""
    return max(_get_int("ADAPTDL_CKPT_FULL_EVERY", 1), 1)


def handoff_enabled() -> bool:
    """Whether planned rescales use the peer-to-peer shard handoff:
    the doomed incarnation serves its in-memory snapshot chunks over
    a small HTTP shard server and the successor pulls exactly the
    chunks it needs, skipping the checkpoint-storage round-trip.
    Default OFF (unset/empty): the runners opt their jobs in; any
    handoff failure falls back to the durable checkpoint."""
    knob = os.environ.get("ADAPTDL_HANDOFF", "")
    return knob.lower() in ("on", "1", "true", "yes")


def handoff_url() -> str | None:
    """Explicit base URL of a predecessor's handoff shard server (the
    successor's discovery normally goes descriptor-file → supervisor;
    this override short-circuits both — tests, single-box)."""
    return _get_str("ADAPTDL_HANDOFF_URL")


def handoff_diff_enabled() -> bool:
    """Whether handoff pulls are *differential*: chunks whose content
    hash already sits in the warm-up prefetch cache are reused instead
    of re-fetched, so a warm successor pulls only the shards that
    changed between its prefetch and the incumbent's final drain
    snapshot. Default ON — a sha mismatch simply re-fetches, so the
    restored bytes are identical either way; the knob exists to pin
    the full-pull behavior in benchmarks and bisections."""
    knob = os.environ.get("ADAPTDL_HANDOFF_DIFF", "on")
    return knob.lower() in ("on", "1", "true", "yes")


def sharded_hash_enabled() -> bool:
    """Whether sharded (orbax-backed) saves hash each addressable
    shard and record a per-save ``shard_delta`` (changed shards /
    bytes vs the previous save) in the checkpoint pointer. Default ON;
    the hash pass is one host transfer of the state per save — turn
    off for jobs where that dominates the save path. Accounting only:
    restores never depend on the hash sidecar."""
    knob = os.environ.get("ADAPTDL_SHARDED_HASHES", "on")
    return knob.lower() in ("on", "1", "true", "yes")


def warmup_enabled() -> bool:
    """Whether the runners speculatively warm a successor for a
    planned rescale: when the allocator's published candidate matches
    the drifted launch config, the successor process is spawned —
    imports, jax init, AOT compile, differential shard prefetch —
    BEFORE the incumbent is signalled, and the commit epoch only cuts
    traffic over. Default OFF (unset/empty): any warm-up failure or a
    mispredicted candidate falls back to the cold planned path."""
    knob = os.environ.get("ADAPTDL_WARMUP_ENABLED", "")
    return knob.lower() in ("on", "1", "true", "yes")


def warmup_flag() -> bool:
    """Set by the runner IN the warm successor's environment
    (``ADAPTDL_WARMUP=1``): tells the job process it is a speculative
    warm-up — it must prepare (build, compile, prefetch), mark the
    ready file, and hold before restoring state until the runner
    writes the cutover file."""
    knob = os.environ.get("ADAPTDL_WARMUP", "")
    return knob.lower() in ("on", "1", "true", "yes")


def warmup_ready_file() -> str | None:
    """Path the warm successor touches once warm (runner-provided);
    the runner waits for it before signalling the incumbent."""
    return _get_str("ADAPTDL_WARMUP_READY_FILE")


def warmup_cutover_file() -> str | None:
    """Path the runner writes at cutover (``go``) or discard
    (``abort``); the held warm successor polls it to proceed or exit."""
    return _get_str("ADAPTDL_WARMUP_CUTOVER_FILE")


def supervisor_url() -> str | None:
    """Base URL of the cluster supervisor (rendezvous + sched hints)."""
    return _get_str("ADAPTDL_SUPERVISOR_URL")


def coordinator_addr() -> str | None:
    """``host:port`` for ``jax.distributed.initialize`` on multi-host."""
    return _get_str("ADAPTDL_COORDINATOR_ADDR")


def num_replicas_is_set() -> bool:
    """Whether the scheduler (or launcher) exported a replica count.

    Standalone runs bootstrap one replica per local device when unset
    (:func:`set_num_replicas`)."""
    return "ADAPTDL_NUM_REPLICAS" in os.environ


def set_num_replicas(count: int) -> None:
    """Export the replica count into this process's environment.

    The ONE sanctioned env write outside a launcher: standalone
    single-process runs (no scheduler) default to one replica per
    local device so the dataloader's batch math and the trainer's
    default mesh agree."""
    os.environ["ADAPTDL_NUM_REPLICAS"] = str(int(count))


def fit_interval() -> float:
    """Seconds between perf refits / sched-hint posts (reference
    cadence 30s, _metrics.py:60-66); override for tests and demos."""
    return _get_float("ADAPTDL_FIT_INTERVAL", 30.0)


def aot_cache_knob() -> str:
    """Raw AOT-executable-cache knob: a path overrides the location,
    ``off``/``0``/``false``/``none`` disables, empty means "beside the
    checkpoints" (aot_cache.cache_dir resolves the policy)."""
    return os.environ.get("ADAPTDL_AOT_CACHE", "")


def compile_cache_knob() -> str:
    """Raw XLA persistent-compilation-cache knob, same convention as
    :func:`aot_cache_knob` (bootstrap resolves the policy)."""
    return os.environ.get("ADAPTDL_COMPILE_CACHE", "")


def checkout_root() -> str:
    """Directory holding the ``adaptdl_tpu`` package: the last-resort
    home of the compile cache (``<root>/.jax_compile_cache``), and what
    ``chip_smoke.py`` names through ``ADAPTDL_COMPILE_CACHE`` so its
    throw-away checkpoint directories never become the cache path."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def trace_enabled() -> bool:
    """Whether the graftscope tracing subsystem records spans
    (``off``/``0``/``false``/``none`` disables — every ``trace.span``
    then costs one global read and an immediate return)."""
    knob = os.environ.get("ADAPTDL_TRACE", "")
    return knob.lower() not in ("off", "0", "false", "none")


def trace_dir() -> str | None:
    """Directory for the per-job structured trace journal (JSONL, one
    finished span/event per line). Unset — the default — keeps spans
    in the in-memory ring buffer only; set, every finished span is
    appended so a killed incarnation's spans survive for the next one
    (the cross-restart half of a rescale trace)."""
    return _get_str("ADAPTDL_TRACE_DIR")


def traceparent() -> str | None:
    """W3C ``traceparent`` inherited across the checkpoint-restart
    boundary: the launcher exports the rescale decision's trace
    context here so the restarted incarnation's restore/first-step
    spans land in the SAME trace as the allocator's decision and the
    doomed incarnation's final save."""
    return _get_str("ADAPTDL_TRACEPARENT")


def watch_slo_rho() -> float:
    """Per-tenant finish-time-fairness SLO: each watch sample where a
    tenant's mean slowdown rho (requested-ideal goodput over actual)
    exceeds this bumps the tenant's
    ``adaptdl_tenant_slo_burn_total`` burn counter."""
    return max(_get_float("ADAPTDL_WATCH_SLO_RHO", 3.0), 0.1)


def fault_spec_raw() -> str | None:
    """Fault-injection schedule for chaos testing, as the raw spec
    string (``faults.py`` parses the grammar). Unset — the production
    state — compiles every injection point to a no-op."""
    return _get_str("ADAPTDL_FAULT_SPEC")


def fault_seed() -> int:
    """Seed for the fault schedule's probabilistic clauses, so a
    chaos run's failures replay exactly."""
    return _get_int("ADAPTDL_FAULT_SEED", 0)


def heartbeat_interval() -> float:
    """Seconds between worker liveness heartbeats to the supervisor
    (0 disables the dedicated heartbeat thread; liveness then rides
    only on piggybacked hint/config traffic)."""
    return _get_float("ADAPTDL_HEARTBEAT_INTERVAL", 20.0)


def lease_ttl() -> float:
    """Seconds a worker's liveness lease stays valid without renewal
    before the supervisor declares it dead, marks the job degraded,
    and triggers reallocation (0 disables lease expiry)."""
    return _get_float("ADAPTDL_LEASE_TTL", 120.0)


def sched_state_dir() -> str | None:
    """Directory for the supervisor's durable cluster state (write-
    ahead journal + periodic snapshots). Unset — the default — keeps
    ``ClusterState`` purely in-memory; set, every mutation is journaled
    with an fsync and a restarted supervisor replays snapshot+journal
    to recover jobs, allocations, and leases."""
    return _get_str("ADAPTDL_SCHED_STATE_DIR")


def journal_group_commit_s() -> float:
    """Group-commit window (seconds) for the supervisor's write-ahead
    journal: appends landing within the window share ONE fsync instead
    of paying one each, bounding fsync latency on the mutation path at
    the cost of a power-loss window of at most this many seconds of
    acknowledged mutations (a plain process crash loses nothing —
    records are flushed to the OS per append). 0 — the default — keeps
    the strict fsync-per-record behavior."""
    return max(_get_float("ADAPTDL_JOURNAL_GROUP_COMMIT_S", 0.0), 0.0)


def preempt_notice_s() -> float:
    """Seconds of warning a preemption notice gives before the VM is
    reclaimed (GCE spot gives 30). The urgent drain budgets its final
    blocking checkpoint inside this window."""
    return _get_float("ADAPTDL_PREEMPT_NOTICE_S", 30.0)


def preempt_margin_s() -> float:
    """Safety margin subtracted from the notice window when budgeting
    the urgent drain's blocking save — covers exit/teardown time after
    the checkpoint lands."""
    return _get_float("ADAPTDL_PREEMPT_MARGIN_S", 5.0)


def preempt_poll_s() -> float:
    """Base cadence of the preemption-notice listener's metadata poll.
    0 — the default — disables the auto-started listener entirely
    (spot deployments opt in with e.g. 5); explicit
    ``start_listener`` callers pass their own interval."""
    return _get_float("ADAPTDL_PREEMPT_POLL_S", 0.0)


def spot_price_ratio() -> float | None:
    """Configured spot-vs-on-demand price ratio for the expander's
    capacity-mix policy (raw; the expander applies its default)."""
    return _get_opt_float("ADAPTDL_SPOT_PRICE_RATIO")


def guard_policy() -> str:
    """What the numeric-health guard does on an unhealthy step:
    ``off`` disables detection entirely, ``warn`` only logs and
    reports the incident, ``skip`` additionally drops the poisoned
    batch range from the epoch on the next pass, and ``rollback`` —
    the default — restores the last-known-good checkpoint and skips
    the poisoned range on resume."""
    policy = (_get_str("ADAPTDL_GUARD_POLICY") or "rollback").lower()
    if policy not in ("off", "warn", "skip", "rollback"):
        return "rollback"
    return policy


def guard_confirm_steps() -> int:
    """Consecutive healthy steps after a checkpoint save before that
    version earns the ``good`` marker ``load_state(prefer_good=True)``
    rolls back to — the quarantine period that keeps a checkpoint
    written just before the corruption surfaced from being trusted."""
    return max(_get_int("ADAPTDL_GUARD_CONFIRM_STEPS", 8), 1)


def checkpoint_verify() -> bool:
    """Whether ``load_state`` verifies per-state sha256/size against
    the checkpoint's integrity manifest before restoring (``off``/
    ``0``/``false``/``none`` disables — restores then trust storage,
    pre-manifest behavior)."""
    knob = os.environ.get("ADAPTDL_CKPT_VERIFY", "")
    return knob.lower() not in ("off", "0", "false", "none")


def trial_config_raw() -> str | None:
    """This tuner trial's hyperparameters as a JSON string, set by the
    trial scheduler (tune.py) in the worker's environment."""
    return _get_str(TRIAL_CONFIG_KEY)


def trial_result_file() -> str | None:
    """JSON-lines path trial workers append result rows to."""
    return _get_str(TRIAL_RESULT_KEY)


# ---- scheduler-side knobs -------------------------------------------
#
# The raw reads live here so the whole ADAPTDL_* surface round-trips
# through one module (graftcheck GC301 enforces it). These accessors
# are deliberately raw — None when unset — so the scheduler's POLICY
# (cluster-internal defaults, JSON validation) has exactly one home:
# sched/config.py, the API the operator/supervisor/expander call.


def namespace() -> str | None:
    """Kubernetes namespace the operator manages (raw; sched/config
    applies the default)."""
    return _get_str("ADAPTDL_NAMESPACE")


def job_image() -> str | None:
    """Worker image for rendered job manifests (raw)."""
    return _get_str("ADAPTDL_JOB_IMAGE")


def supervisor_port() -> int | None:
    """Port the supervisor's HTTP server binds (raw)."""
    return _get_opt_int("ADAPTDL_SUPERVISOR_PORT")


def webhook_port() -> int | None:
    """Port the validating-webhook HTTPS server binds (raw)."""
    return _get_opt_int("ADAPTDL_WEBHOOK_PORT")


def webhook_cert() -> str | None:
    """Path to the webhook's TLS serving certificate."""
    return _get_str("ADAPTDL_WEBHOOK_CERT")


def webhook_key() -> str | None:
    """Path to the webhook's TLS private key."""
    return _get_str("ADAPTDL_WEBHOOK_KEY")


def checkpoint_claim() -> str | None:
    """RWX PVC mounted into workers for checkpoints (raw)."""
    return _get_str("ADAPTDL_CHECKPOINT_CLAIM")


def allocator_interval() -> float | None:
    """Seconds between full Pollux re-optimizations (raw)."""
    return _get_opt_float("ADAPTDL_ALLOCATOR_INTERVAL")


def max_worker_failures() -> int | None:
    """Non-graceful worker failures tolerated before a job is Failed
    (raw)."""
    return _get_opt_int("ADAPTDL_MAX_FAILURES")


def expander_min_slices() -> int | None:
    """Floor for the cluster expander's desired slice count (raw)."""
    return _get_opt_int("ADAPTDL_MIN_SLICES")


def expander_max_slices() -> int | None:
    """Ceiling for the cluster expander's desired slice count (raw)."""
    return _get_opt_int("ADAPTDL_MAX_SLICES")


def expander_scale_down_delay() -> float | None:
    """Seconds a lower desired-slice count must persist before the
    provisioner shrinks (raw)."""
    return _get_opt_float("ADAPTDL_SCALE_DOWN_DELAY")


def slice_template_raw() -> str | None:
    """Provisionable slice shape as a raw JSON string (sched/config.py
    parses and validates)."""
    return _get_str("ADAPTDL_SLICE_TEMPLATE")


def default_job_resources_raw() -> str | None:
    """Per-replica resource-request default as a raw JSON string."""
    return _get_str("ADAPTDL_DEFAULT_RESOURCES")


def gke_node_pool_raw() -> str | None:
    """GKE autoscaling target as a raw JSON string."""
    return _get_str("ADAPTDL_GKE_NODE_POOL")


def shard_count() -> int | None:
    """Number of supervisor shards behind the router (raw; 1 or unset
    means the classic single-supervisor deployment)."""
    return _get_opt_int("ADAPTDL_SHARD_COUNT")


def shard_id() -> int | None:
    """This supervisor process's shard id in [0, shard_count) (raw)."""
    return _get_opt_int("ADAPTDL_SHARD_ID")


def shard_map_path() -> str | None:
    """Path the router journals its rendezvous shard map to (raw)."""
    return _get_str("ADAPTDL_SHARD_MAP_PATH")


