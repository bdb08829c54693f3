"""Local elastic runner: the one-machine job controller.

Runs a user training script elastically on the local machine's chips,
playing the part the reference splits between the k8s controller and
the Ray/AWS single-job controller (reference:
sched/adaptdl_sched/controller.py lifecycle +
ray/adaptdl_ray/aws/controller.py single-job form):

- hosts the supervisor (hints + rendezvous REST) and the Pollux
  allocator over one "local" slice whose capacity is the chip count,
- launches the script as a subprocess with the full ``ADAPTDL_*``
  environment of its current allocation,
- watches for allocation changes; on change delivers SIGTERM so the
  job checkpoints and exits 143 (treated as a graceful rescale, never
  a failure — reference: controller.py:276-283), then relaunches with
  ``ADAPTDL_NUM_RESTARTS + 1``,
- distinguishes real failures (nonzero, non-143) with a retry budget.

This is also the mechanism for verifying the whole elastic loop on a
dev box: job posts hints -> allocator re-optimizes -> SIGTERM ->
checkpoint-restart at the new replica count.
"""

from __future__ import annotations

import logging
import os
import signal
import subprocess
import sys
import time

from adaptdl_tpu import faults
from adaptdl_tpu import env as env_mod
from adaptdl_tpu._compat import pick_unused_port

from adaptdl_tpu._signal import GRACEFUL_EXIT_CODE
from adaptdl_tpu.sched import warmup
from adaptdl_tpu.sched.allocator import Allocator
from adaptdl_tpu.sched.policy import NodeInfo, PolluxPolicy
from adaptdl_tpu.sched.state import (
    FINISHED,
    ClusterState,
    normalize_topology,
)
from adaptdl_tpu.sched.supervisor import Supervisor

LOG = logging.getLogger(__name__)


class LocalElasticRunner:
    def __init__(
        self,
        script: str,
        num_chips: int,
        checkpoint_dir: str,
        job_name: str = "default/local",
        min_replicas: int = 0,
        max_replicas: int | None = None,
        allocator_interval: float = 5.0,
        max_failures: int = 2,
        extra_env: dict | None = None,
        pop_size: int = 24,
        generations: int = 20,
        term_grace_period: float = 120.0,
        state_dir: str | None = None,
        preemptible: bool = True,
        handoff: bool | None = None,
    ):
        self.term_grace_period = term_grace_period
        # None inherits the runner environment's ADAPTDL_HANDOFF;
        # True/False force peer-to-peer handoff on planned rescales.
        self.handoff = handoff
        self.script = script
        self.num_chips = num_chips
        self.checkpoint_dir = checkpoint_dir
        self.job_name = job_name
        self.max_replicas = max_replicas or num_chips
        self.min_replicas = min_replicas
        self.max_failures = max_failures
        self.extra_env = dict(extra_env or {})
        # ``state_dir`` (default: ADAPTDL_SCHED_STATE_DIR) makes the
        # controller crash-restartable: ClusterState journals every
        # mutation and a rerun recovers the job record instead of
        # starting over.
        self.state = ClusterState(state_dir=state_dir)
        spec = {
            "resources": {"tpu": 1},
            "min_replicas": min_replicas,
            "max_replicas": self.max_replicas,
            # Honors the caller's choice (it used to be hardcoded
            # True, which made Pollux's non-preemptible repair path —
            # pin the incumbent's allocation verbatim — unreachable
            # from the local runners).
            "preemptible": bool(preemptible),
        }
        from adaptdl_tpu.sched.validator import validate_job_spec

        validate_job_spec(spec)
        recovered = self.state.get_job(job_name)
        if recovered is not None and recovered.status in FINISHED:
            # Re-running a job that already finished: that run's
            # record is history, not something to resume.
            self.state.remove_job(job_name)
            recovered = None
        if recovered is None:
            self.state.create_job(job_name, spec=spec)
            self.restarts = 0
        else:
            # Recovered mid-run: keep allocations/hints/leases, adopt
            # the current spec, and bump the restart counter so the
            # next launch can never reuse (and clobber) a checkpoint
            # version index an earlier incarnation may have written.
            self.state.update(job_name, spec=spec)
            self.restarts = recovered.restarts + 1
        self.supervisor = Supervisor(self.state)
        # Outstanding speculative successor (sched.warmup), if any.
        self._warm: warmup.WarmSuccessor | None = None
        nodes = {"local": NodeInfo(resources={"tpu": num_chips})}
        self.allocator = Allocator(
            self.state,
            nodes,
            policy=PolluxPolicy(pop_size=pop_size, generations=generations),
            interval=allocator_interval,
        )

    def _job_env(
        self,
        num_replicas: int,
        topology: dict | None,
        restarts: int | None = None,
    ) -> dict:
        env = dict(os.environ)
        env.update(self.extra_env)
        env.update(
            {
                "ADAPTDL_JOB_ID": self.job_name,
                "ADAPTDL_CHECKPOINT_PATH": self.checkpoint_dir,
                "ADAPTDL_MASTER_ADDR": "127.0.0.1",
                "ADAPTDL_MASTER_PORT": str(pick_unused_port()),
                "ADAPTDL_REPLICA_RANK": "0",
                "ADAPTDL_NUM_REPLICAS": str(num_replicas),
                "ADAPTDL_NUM_PROCESSES": "1",
                "ADAPTDL_NUM_NODES": "1",
                # A warm successor is spawned for the NEXT incarnation
                # while this one still runs, so its restart index is
                # passed in rather than read off the runner.
                "ADAPTDL_NUM_RESTARTS": str(
                    self.restarts if restarts is None else restarts
                ),
                "ADAPTDL_SUPERVISOR_URL": self.supervisor.url,
            }
        )
        if self.handoff is not None:
            env["ADAPTDL_HANDOFF"] = "on" if self.handoff else "off"
        record = self.state.get_job(self.job_name)
        if record is not None and record.trace_parent:
            # Cross the checkpoint-restart boundary: the new
            # incarnation's restore/first-step spans join the trace of
            # the allocator decision that restarted it (graftscope).
            env["ADAPTDL_TRACEPARENT"] = record.trace_parent
        topology = topology or {}
        env["ADAPTDL_SEQ_SHARDS"] = str(topology.get("seqShards", 1))
        env["ADAPTDL_MODEL_SHARDS"] = str(topology.get("modelShards", 1))
        env["ADAPTDL_STAGE_SHARDS"] = str(topology.get("stageShards", 1))
        env["ADAPTDL_EXPERT_SHARDS"] = str(
            topology.get("expertShards", 1)
        )
        # Default matches normalize_topology: records that predate the
        # M search ran stage schedules at the old fixed M=4.
        default_micro = 4 if int(topology.get("stageShards", 1)) > 1 else 1
        env["ADAPTDL_PIPELINE_MICRO"] = str(
            topology.get("pipelineMicro", default_micro)
        )
        return env

    def run(self) -> int:
        """Run the job to completion; returns the final exit code."""
        self.supervisor.start()
        self.allocator.start()
        failures = 0
        try:
            # Fallback if the allocator's first cycle yielded nothing.
            if not self.state.get_allocation(self.job_name):
                initial = max(self.min_replicas, 1)
                self.state.update(
                    self.job_name, allocation=["local"] * initial
                )
            while True:
                allocation, topology = self.state.get_launch_config(
                    self.job_name
                )
                num_replicas = max(len(allocation), 1)
                LOG.info(
                    "starting %s: replicas=%d restarts=%d topology=%s",
                    self.job_name,
                    num_replicas,
                    self.restarts,
                    topology,
                )
                self.state.update(
                    self.job_name,
                    status="Running",
                    # Persisted so a crash-restarted controller resumes
                    # the counter instead of reusing version indices.
                    restarts=self.restarts,
                )
                proc = self._adopt_warm(allocation, topology)
                if proc is not None:
                    code, signalled = self._supervise(
                        proc, allocation, topology
                    )
                else:
                    try:
                        # An injected fault here models a failed worker
                        # launch (image pull error, node gone) — it
                        # rides the same retry budget as a crashing
                        # worker.
                        faults.maybe_fail("runner.launch.pre")
                        proc = subprocess.Popen(
                            [sys.executable, self.script],
                            env=self._job_env(num_replicas, topology),
                        )
                    except faults.InjectedFault:
                        LOG.warning(
                            "injected launch failure for %s",
                            self.job_name,
                        )
                        code, signalled = 1, False
                    else:
                        code, signalled = self._supervise(
                            proc, allocation, topology
                        )
                if code == 0:
                    self.state.update(self.job_name, status="Succeeded")
                    return 0
                if code == GRACEFUL_EXIT_CODE or (
                    # Our own SIGTERM landed before the job installed
                    # its handler (e.g. still importing jax): that is a
                    # rescale, not a failure.
                    signalled
                    and code == -signal.SIGTERM
                ):
                    self.restarts += 1
                    continue
                failures += 1
                # The incumbent died before cutover: the warm
                # successor (if any) was built against state the crash
                # never drained — discard it and restore cold from the
                # durable checkpoint.
                self._discard_warm("incumbent crashed before cutover")
                # A crash never ran the drain: withdraw any handoff
                # descriptor an older incarnation left behind so the
                # next launch goes straight to the durable checkpoint.
                from adaptdl_tpu import handoff

                handoff.withdraw_descriptor(self.checkpoint_dir)
                LOG.warning(
                    "%s failed with code %s (%d/%d)",
                    self.job_name,
                    code,
                    failures,
                    self.max_failures,
                )
                if failures > self.max_failures:
                    self.state.update(self.job_name, status="Failed")
                    return code
                self.restarts += 1
        finally:
            self._discard_warm("runner shutting down")
            self.allocator.stop()
            self.supervisor.stop()

    def _spawn_warm(self, allocation, topology) -> None:
        """Speculatively bring up the successor for a drifted launch
        config while the incumbent keeps training. Gated on the
        allocator's published candidate matching the drift: a config
        the allocator did not predict (or whose candidate a rollback
        cleared) is never warmed — the cold path handles it exactly as
        before. Blocks up to the warm-up deadline waiting for the
        successor to finish its cold start; only then does the caller
        signal the incumbent, so the overlap covers imports, jax init,
        AOT compile, and the differential prefetch."""
        candidate = self.state.get_candidate(self.job_name)
        if not warmup.candidate_matches(candidate, allocation, topology):
            LOG.info(
                "no matching candidate for %s; rescaling cold",
                self.job_name,
            )
            return
        self._discard_warm("superseded by a newer drift")
        warm = warmup.WarmSuccessor(
            [sys.executable, self.script],
            self._job_env(
                max(len(allocation), 1),
                topology,
                restarts=self.restarts + 1,
            ),
            allocation,
            topology,
            restarts=self.restarts + 1,
        )
        try:
            warm.spawn()
        except faults.InjectedFault:
            LOG.warning(
                "injected warm-up spawn failure for %s", self.job_name
            )
            warm.discard()
            return
        if warm.wait_ready(warmup.READY_DEADLINE_S):
            self._warm = warm
        else:
            warm.discard("never became ready")

    def _adopt_warm(self, allocation, topology):
        """The cutover: hand the pre-warmed successor the go signal
        and return its process, or None when there is nothing warm (or
        the speculation no longer matches what must launch — the
        mispredict fallback)."""
        warm, self._warm = self._warm, None
        if warm is None:
            return None
        if not warm.alive():
            warm.discard("died during warm-up")
            return None
        if not warm.matches(allocation, topology) or (
            warm.restarts != self.restarts
        ):
            warm.discard("candidate mispredicted")
            return None
        try:
            proc = warm.cutover()
        except faults.InjectedFault:
            warm.discard("injected cutover failure")
            return None
        LOG.info(
            "cutover: adopting warm successor for %s (replicas=%d)",
            self.job_name,
            max(len(allocation), 1),
        )
        return proc

    def _discard_warm(self, reason: str) -> None:
        warm, self._warm = self._warm, None
        if warm is not None:
            warm.discard(reason)

    def _supervise(
        self, proc: subprocess.Popen, allocation, topology=None
    ):
        """Wait for the process; SIGTERM it if the allocation or the
        chosen topology moves, escalating to SIGKILL if the grace
        period expires. Returns (exit_code, we_signalled_it).

        Batch-config-only decisions (the allocator's live re-tunes)
        deliberately do NOT signal the job: it adopts them in-process
        through the supervisor's /config endpoint, keeping its
        dataloader position and jit caches — a rescale with zero
        restarts. Only device-set or mesh-factorization changes pay
        the checkpoint-restart path."""
        signalled = False
        term_deadline = None
        seen_retunes = 0
        record = self.state.get_job(self.job_name)
        if record is not None:
            seen_retunes = record.retunes
        while True:
            # Chaos hook: inject latency into the supervision cadence
            # (a starved controller must still converge, just later).
            faults.maybe_fail("runner.supervise.poll")
            code = proc.poll()
            if code is not None:
                return code, signalled
            record = self.state.get_job(self.job_name)
            if record is not None and record.retunes > seen_retunes:
                LOG.info(
                    "live re-tune #%d for %s: batch config %s "
                    "(no restart)",
                    record.retunes,
                    self.job_name,
                    record.batch_config,
                )
                seen_retunes = record.retunes
            current, cur_topology = self.state.get_launch_config(
                self.job_name
            )
            # Topology-only drift (same chips, new sp/tp) also needs a
            # rescale; normalized so None == pure-DP {1,1} never
            # triggers a spurious restart when hints first arrive.
            drifted = list(current) != list(
                allocation
            ) or normalize_topology(cur_topology) != normalize_topology(
                topology
            )
            if not signalled and drifted:
                LOG.info(
                    "drift %s/%s -> %s/%s: requesting graceful rescale",
                    allocation,
                    topology,
                    current,
                    cur_topology,
                )
                if env_mod.warmup_enabled() and current:
                    # Successor first, signal second: the incumbent
                    # keeps taking steps for the whole warm-up window,
                    # so the only stopped time left is its drain plus
                    # the successor's differential pull. A withdrawal
                    # (empty config) has no successor to warm.
                    self._spawn_warm(current, cur_topology)
                proc.send_signal(signal.SIGTERM)
                signalled = True
                term_deadline = time.monotonic() + self.term_grace_period
            if (
                term_deadline is not None
                and time.monotonic() > term_deadline
            ):
                LOG.warning(
                    "grace period expired; killing %s", self.job_name
                )
                proc.kill()
                term_deadline = None
            time.sleep(0.2)


def count_local_chips(timeout: float = 120.0) -> int:
    """Chip count for launchers that were not given ``--chips``, taken
    in a short-lived child that has exited before any worker starts.
    A chip belongs to one process at a time: a launcher that asked
    ``jax.devices()`` itself would hold the chip for its whole life
    and every worker it launched would fail or hang at backend
    start-up."""
    try:
        out = subprocess.run(
            [
                sys.executable,
                "-c",
                "import jax; print(len(jax.devices()))",
            ],
            capture_output=True,
            text=True,
            timeout=timeout,
            check=True,
        )
        return int(out.stdout.strip().splitlines()[-1])
    except (
        subprocess.SubprocessError, ValueError, IndexError
    ) as exc:
        detail = getattr(exc, "stderr", None) or exc
        raise SystemExit(
            f"could not count the local chips ({detail}); pass --chips"
        ) from exc


def main() -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="Run a training script elastically on this machine."
    )
    parser.add_argument("script")
    parser.add_argument("--chips", type=int, default=None)
    parser.add_argument("--checkpoint-dir", required=True)
    parser.add_argument("--min-replicas", type=int, default=0)
    parser.add_argument("--max-replicas", type=int, default=None)
    parser.add_argument(
        "--non-preemptible",
        action="store_true",
        help="pin the job's allocation once granted (the scheduler "
        "never shrinks or moves it to make room for other jobs)",
    )
    args = parser.parse_args()
    runner = LocalElasticRunner(
        args.script,
        num_chips=(
            args.chips if args.chips is not None else count_local_chips()
        ),
        checkpoint_dir=args.checkpoint_dir,
        min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        preemptible=not args.non_preemptible,
    )
    return runner.run()


if __name__ == "__main__":
    sys.exit(main())
