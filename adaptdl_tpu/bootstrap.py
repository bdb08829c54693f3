"""Job initialization: the ``init_process_group`` equivalent.

One call wires a training process into the elastic cluster (reference:
adaptdl/adaptdl/torch/__init__.py:51-127, whose steps were: supervisor
discovery, version check, object-collective init, torch.distributed
init). The TPU-native sequence:

1. install graceful-preemption signal handlers,
2. (multi-process) register with the supervisor and long-poll
   ``/discover`` until all processes of this restart group are known,
3. initialize the control-plane object collectives (star reducer),
4. (multi-host) ``jax.distributed.initialize`` so all hosts see the
   global device set — the NCCL-rendezvous equivalent; XLA collectives
   then ride ICI/DCN.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time

from adaptdl_tpu import _signal, collective, env, rpc, sched_hints, trace

LOG = logging.getLogger(__name__)

# Rendezvous retry budgets. Registration is small and idempotent, so
# it retries aggressively through transient supervisor blips (a 143
# restart storm is exactly when the supervisor is busiest); discover
# is a long poll with its own server-side timeout, so it gets few
# client-side attempts but a generous overall deadline.
_REGISTER_ATTEMPTS = 6
_REGISTER_DEADLINE = 120.0
_DISCOVER_ATTEMPTS = 3
_DISCOVER_DEADLINE = 700.0


def _discover_peers() -> dict[int, str] | None:  # wire: produces=register
    """Register with the supervisor and wait for all peer processes.

    Both calls ride the resilient rpc client: a transient supervisor
    error (connection reset, 5xx, restart blip) is retried with
    backoff inside a bounded deadline instead of raising out of
    ``initialize_job`` and killing the worker. Re-registration is
    idempotent — the supervisor keys workers by (group, rank) and
    overwrites the address — so a worker restarted after exit-143 (or
    a retry that raced a success) can blindly register again. A 404
    is retried too: after a supervisor restart the runner re-creates
    the job record a moment after workers come back.
    """
    import socket

    url = env.supervisor_url()
    job = env.job_id()
    if not url or not job or env.num_processes() <= 1:
        return None
    group = env.num_restarts()
    rank = env.process_rank()
    address = f"{socket.gethostbyname(socket.gethostname())}"
    client = rpc.default_client()
    client.put(
        f"{url}/register/{job}/{group}/{rank}",
        # The process count is the supervisor's commit quorum for a
        # pending allocation epoch: the new allocation only commits
        # once this many ranks have proven liveness.
        json={"address": address, "processes": env.num_processes()},
        endpoint=f"register/{job}",
        timeout=(5, 30),
        attempts=_REGISTER_ATTEMPTS,
        deadline=_REGISTER_DEADLINE,
        retry_statuses=rpc.RETRY_STATUSES + (404,),
    ).raise_for_status()
    response = client.get(
        f"{url}/discover/{job}/{group}",
        params={"replicas": env.num_processes()},
        endpoint=f"discover/{job}",
        timeout=(5, 330),
        attempts=_DISCOVER_ATTEMPTS,
        deadline=_DISCOVER_DEADLINE,
    )
    response.raise_for_status()
    return {int(r): addr for r, addr in response.json().items()}


_heartbeat_stop: threading.Event | None = None
_heartbeat_thread: threading.Thread | None = None
# The handoff-manifest prefetch rides a side thread during bootstrap;
# the handle is kept so teardown can prove it drained.
_prefetch_thread: threading.Thread | None = None
# The restart->first-step span opens at most once per incarnation:
# initialize_job is documented idempotent, and a repeat call must not
# re-arm a span that would then "measure" an arbitrary mid-training
# interval at the next profiled step.
_restart_span_armed = False


def start_heartbeat() -> threading.Event | None:
    """Start the liveness-heartbeat daemon thread (idempotent).

    Workers renew their supervisor lease every
    ``ADAPTDL_HEARTBEAT_INTERVAL`` seconds; hint posts and config
    fetches also renew it as a side effect (piggybacked liveness), so
    this thread only matters when a worker is alive but not talking —
    e.g. rank > 0, or a long compile. Returns the stop event, or None
    when heartbeating is not applicable (no supervisor, disabled)."""
    global _heartbeat_stop, _heartbeat_thread
    interval = env.heartbeat_interval()
    if not env.supervisor_url() or not env.job_id() or interval <= 0:
        return None
    if _heartbeat_stop is not None and not _heartbeat_stop.is_set():
        return _heartbeat_stop
    stop = threading.Event()
    rank = env.process_rank()

    # Imported here, not at module top: metrics pulls in the goodput
    # stack, which bootstrap must not load before jax is configured.
    from adaptdl_tpu import metrics

    def loop():
        sched_hints.send_heartbeat(rank=rank)
        while not stop.wait(interval):
            # The rank's smoothed step time rides the beat it already
            # sends — graftwatch turns per-rank outliers into the
            # adaptdl_slot_suspect straggler gauge.
            sched_hints.send_heartbeat(
                rank=rank,
                step_time_ewma=metrics.step_time_ewma(),
            )
            # Every rank's buffered spans reach the supervisor on the
            # heartbeat cadence — the hint-cadence flush only runs on
            # rank 0's fit thread, and a straggling rank>0 restore is
            # exactly what a rescale trace must be able to show.
            trace.flush_to_supervisor()

    _heartbeat_thread = threading.Thread(
        target=loop, name="adaptdl-heartbeat", daemon=True
    )
    _heartbeat_thread.start()
    _heartbeat_stop = stop
    return stop


def stop_heartbeat(timeout: float | None = 5.0) -> None:
    """Stop the heartbeat daemon and join it (tests, clean worker
    shutdown). Safe when no heartbeat is running; a later
    :func:`start_heartbeat` starts a fresh one."""
    if _heartbeat_stop is not None:
        _heartbeat_stop.set()
    if _heartbeat_thread is not None:
        _heartbeat_thread.join(timeout)
    if _prefetch_thread is not None:
        _prefetch_thread.join(timeout)


def _process_age() -> float | None:
    """Seconds since the kernel started this process: field 22 of
    ``/proc/self/stat`` (clock ticks since boot) against
    ``CLOCK_BOOTTIME``, to the tick (10 ms). None where there is no
    ``/proc``."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # Field 2, the command, may hold spaces and parentheses:
            # count from its closing one, after which field 3 follows.
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf(
            "SC_CLK_TCK"
        )
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return age if age >= 0 else None


def _record_boot_spans() -> None:
    """``boot.process``: the kernel starting this process -> now, the
    entry of ``initialize_job`` (interpreter start, the script's own
    imports, reaching the chip where the script does that first, and
    importing this package). Its child ``boot.import`` is the package
    import alone, from the clocks ``adaptdl_tpu/__init__.py`` took."""
    import adaptdl_tpu

    boot = adaptdl_tpu._boot
    bridge = sys.modules.get("jax._src.xla_bridge")
    age = _process_age()
    process = None
    if age is not None:
        process = trace.record_span(
            "boot.process",
            age,
            restarts=env.num_restarts(),
            jax_preloaded=boot["jax_preloaded"],
            backend_ready=bool(
                bridge is not None and bridge.backends_are_initialized()
            ),
        )
    trace.record_span(
        "boot.import",
        boot["seconds"],
        traceparent=process,
        ts=boot["start"],
        modules=boot["modules"],
    )


def _close_exit_trace() -> None:
    """atexit, registered by the first ``initialize_job``: before the
    program's lazily registered joins (checkpoint writer, AOT writer,
    fit thread), so LIFO order runs it after them. Closes
    ``exit.atexit`` (opened where ``_check_exit`` calls ``sys.exit``:
    what those joins cost) and hands the spans since the signal to the
    successor. What follows (jax's own hooks, interpreter finalisation,
    the runtime letting go of the chip) is visible only from outside."""
    trace.end_pending("exit.atexit")
    since = _signal.signal_time()
    if since is not None and env.process_rank() == 0:
        trace.write_handover(since)


def initialize_job(distributed: bool | None = None) -> None:
    """Initialize this process for (possibly multi-host) elastic
    training. Idempotent; safe to call in single-process jobs."""
    global _restart_span_armed, _prefetch_thread
    # Adopt the rescale trace context the launcher exported
    # (ADAPTDL_TRACEPARENT) BEFORE anything records a span: the
    # restore/first-step spans of this incarnation must land in the
    # same trace as the allocator decision that restarted it.
    trace.init_from_env()
    # jax's trace / lower / compile phases become jit.* spans from
    # here on, under whatever span is open where they happen.
    trace.install_jax_bridge()
    if not _restart_span_armed:
        _restart_span_armed = True
        if trace.enabled():
            import atexit

            atexit.register(_close_exit_trace)
            # The predecessor's signal -> save -> exit first, then this
            # process's own start -> here: the rescale in time order.
            if env.num_restarts() > 0 and env.process_rank() == 0:
                trace.adopt_handover()
            _record_boot_spans()
        # The restart->first-step window: opened here, closed by the
        # first profiled train step (metrics.profile_step) — the
        # end-to-end restart cost a rescale trace must account for.
        trace.begin_pending(
            "restart.first_step", restarts=env.num_restarts()
        )
    with trace.span("bootstrap.init", restarts=env.num_restarts()):
        _signal.install_handlers()
        if not env.num_replicas_is_set():
            # Standalone single-process run: one replica per local
            # device, so the dataloader's batch math and the trainer's
            # default mesh agree without any scheduler in the loop.
            import jax

            env.set_num_replicas(len(jax.devices()))
        peers = None
        try:
            peers = _discover_peers()
        except Exception:  # noqa: BLE001 - rendezvous best-effort local
            LOG.exception("supervisor discovery failed; continuing solo")
        start_heartbeat()
        # Spot deployments (ADAPTDL_PREEMPT_POLL_S > 0) get the
        # reclaim-notice listener: on notice it arms the urgent-drain
        # path and reports to the supervisor so re-placement overlaps
        # the drain. The default (0) starts nothing — dev boxes and CI
        # must not poll a metadata server that isn't there.
        from adaptdl_tpu.sched import preemption

        preemption.ensure_listener()
        if env.handoff_enabled() and env.num_restarts() > 0:
            # Successor of a planned rescale: warm the peer-to-peer
            # handoff discovery (supervisor advertisement / descriptor
            # file) and its manifest on a side thread, overlapping the
            # rest of bootstrap — by the time the trainer's
            # load_state runs, chunk pulls start immediately. A miss
            # costs nothing: the restore falls back to the durable
            # checkpoint.
            from adaptdl_tpu import handoff

            _prefetch_thread = threading.Thread(
                target=handoff.prefetch,
                name="adaptdl-handoff-prefetch",
                daemon=True,
            )
            _prefetch_thread.start()
        if not collective.initialized():
            master = peers.get(0) if peers else None
            collective.initialize(
                master_addr=master or env.master_addr(),
                master_port=env.master_port(),
                replica_rank=env.process_rank(),
                num_replicas=env.num_processes(),
            )
        should_distribute = (
            distributed
            if distributed is not None
            else env.num_processes() > 1
            and env.coordinator_addr() is not None
        )
        if should_distribute:
            import jax

            jax.distributed.initialize(
                coordinator_address=env.coordinator_addr(),
                num_processes=env.num_processes(),
                process_id=env.process_rank(),
            )
        _enable_compilation_cache()


def _enable_compilation_cache() -> None:
    """Persist XLA executables across elastic restarts.

    Every rescale is a process restart, and without a cache each
    incarnation pays full recompilation (tens of seconds per step
    configuration on TPU) before its first step — a direct tax on the
    rescale latency the goodput model's restart penalty prices.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, the deployment has
    placed the cache and this function sets no directory of its own
    (jax reads the variable itself). Otherwise the directory is
    ``.jax_compile_cache`` under the first of: ``ADAPTDL_COMPILE_CACHE``,
    the job's shared storage (``ADAPTDL_SHARE_PATH``, the cross-restart
    volume — the analog of the reference's checkpoint PVC, reference:
    cli/adaptdl_cli/pvc.py:37-78), the checkpoint directory, and last
    the checkout holding this package — always a fixed path, because
    the path is part of the cache key and a directory that moves never
    hits. ``ADAPTDL_COMPILE_CACHE=off`` disables.
    """
    import os

    knob = env.compile_cache_knob()
    if knob.lower() in ("off", "0", "false", "none"):
        return
    try:
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            path = (
                knob
                or env.share_path()
                or env.checkpoint_path()
                or env.checkout_root()
            )
            cache_dir = os.path.join(
                os.path.abspath(path), ".jax_compile_cache"
            )
            os.makedirs(cache_dir, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        # Cache EVERY compile: the default entry-size / compile-time
        # gates would skip the small-but-many configurations the
        # adaptive batch-size loop generates, which are exactly the
        # ones a restarted incarnation re-needs.
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", -1
        )
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0
        )
    except Exception:  # noqa: BLE001 - cache is an optimization only
        LOG.exception(
            "compilation cache setup failed; continuing without"
        )
