"""Multi-job elastic runner: Pollux co-scheduling on one machine.

Runs several training jobs concurrently on one slice's chips with ONE
shared allocator co-optimizing all their allocations from their posted
goodput hints — the cluster-level behavior that is the reference's
core value proposition (reference: the scheduler stack of
sched/adaptdl_sched as a whole; the trial-scheduler form of
ray/adaptdl_ray/tune/adaptdl_trial_sched.py:60-127 maps onto this by
treating each hyperparameter trial as one job).

Each job gets the same lifecycle as
:class:`~adaptdl_tpu.sched.local_runner.LocalElasticRunner` (SIGTERM on
allocation drift, exit-143 graceful restart, retry budget), supervised
by its own thread; the shared Pollux cycle shifts chips between jobs
as their gradient-noise statistics and throughput models evolve.
"""

from __future__ import annotations

import logging
import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field

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
from adaptdl_tpu.sched.validator import validate_job_spec

LOG = logging.getLogger(__name__)


@dataclass
class JobSpec:
    name: str  # "namespace/name"
    script: str
    checkpoint_dir: str
    min_replicas: int = 0
    max_replicas: int | None = None
    # False pins the job's allocation once granted: Pollux's repair
    # step keeps non-preemptible incumbents on their base allocation
    # verbatim instead of shrinking/moving them for other jobs.
    preemptible: bool = True
    # None inherits the runner environment's ADAPTDL_HANDOFF; True /
    # False force peer-to-peer state handoff on planned rescales on
    # or off for this job's workers.
    handoff: bool | None = None
    extra_env: dict = field(default_factory=dict)


class MultiJobRunner:
    def __init__(
        self,
        jobs: list[JobSpec],
        num_chips: int,
        allocator_interval: float = 5.0,
        max_failures: int = 2,
        term_grace_period: float = 120.0,
        pop_size: int = 24,
        generations: int = 20,
        state_dir: str | None = None,
    ):
        self.jobs = {job.name: job for job in jobs}
        self.num_chips = num_chips
        self.max_failures = max_failures
        self.term_grace_period = term_grace_period
        # Durable when state_dir (or ADAPTDL_SCHED_STATE_DIR) is set:
        # a crash-restarted runner recovers every job's record —
        # allocation, hints, restart counter — from the journal.
        self.state = ClusterState(state_dir=state_dir)
        recovered_restarts: dict[str, int] = {}
        for job in jobs:
            spec = {
                "resources": {"tpu": 1},
                "min_replicas": job.min_replicas,
                "max_replicas": job.max_replicas or num_chips,
                # From the JobSpec (was hardcoded True — the policy's
                # non-preemptible pinning was unreachable here).
                "preemptible": bool(job.preemptible),
            }
            validate_job_spec(spec)
            record = self.state.get_job(job.name)
            if record is not None and record.status in FINISHED:
                self.state.remove_job(job.name)
                record = None
            if record is None:
                self.state.create_job(job.name, spec=spec)
            else:
                self.state.update(job.name, spec=spec)
                # Never reuse a checkpoint version index a previous
                # controller incarnation may have handed out.
                recovered_restarts[job.name] = record.restarts + 1
        # Recovered jobs absent from THIS run's job list have no
        # supervising thread: left in place they would compete for
        # chips forever (the allocator iterates the state, not our
        # thread table).
        for key in list(self.state.jobs()):
            if key not in self.jobs:
                LOG.info(
                    "dropping recovered job %s: not in this runner's "
                    "job list", key,
                )
                self.state.remove_job(key)
        self.supervisor = Supervisor(self.state)
        self.allocator = Allocator(
            self.state,
            {"local": NodeInfo(resources={"tpu": num_chips})},
            policy=PolluxPolicy(
                pop_size=pop_size, generations=generations
            ),
            interval=allocator_interval,
        )
        self.exit_codes: dict[str, int] = {}
        self.restart_counts: dict[str, int] = {
            job.name: recovered_restarts.get(job.name, 0)
            for job in jobs
        }
        self._stopped: set[str] = set()
        # Live worker process per job (soak/fault-injection harnesses
        # SIGKILL through this; entries go stale after exit).
        self.procs: dict[str, subprocess.Popen] = {}
        # Outstanding speculative successor per job (sched.warmup);
        # touched only by the job's own supervising thread.
        self._warms: dict[str, warmup.WarmSuccessor] = {}

    def stop_job(self, name: str) -> None:
        """Externally terminate a job (e.g. a tuning trial that lost
        its rung): its allocation is withdrawn, the supervising thread
        SIGTERMs it for a graceful checkpoint, and it is not
        relaunched (status Stopped, exit code 143 recorded). Status
        flips terminal SYNCHRONOUSLY — the allocator skips FINISHED
        jobs, so it can never re-grant chips to a stopped job in the
        window before the supervising thread notices."""
        self._stopped.add(name)
        self.state.update(
            name, allocation=[], topology=None, status="Stopped"
        )

    # -- per-job lifecycle (one thread each) --------------------------

    def _job_env(
        self,
        job: JobSpec,
        num_replicas: int,
        topology: dict | None,
        restarts: int | None = None,
    ) -> dict:
        env = dict(os.environ)
        env.update(job.extra_env)
        env.update(
            {
                "ADAPTDL_JOB_ID": job.name,
                "ADAPTDL_CHECKPOINT_PATH": job.checkpoint_dir,
                "ADAPTDL_MASTER_ADDR": "127.0.0.1",
                "ADAPTDL_MASTER_PORT": str(
                    pick_unused_port()
                ),
                "ADAPTDL_REPLICA_RANK": "0",
                "ADAPTDL_NUM_REPLICAS": str(num_replicas),
                "ADAPTDL_NUM_PROCESSES": "1",
                "ADAPTDL_NUM_NODES": "1",
                # A warm successor is spawned for the NEXT incarnation
                # while this one still runs, so its restart index is
                # passed in rather than read off the counter.
                "ADAPTDL_NUM_RESTARTS": str(
                    self.restart_counts[job.name]
                    if restarts is None
                    else restarts
                ),
                "ADAPTDL_SUPERVISOR_URL": self.supervisor.url,
            }
        )
        if job.handoff is not None:
            # Explicit per-job choice beats the inherited environment:
            # workers spawn the handoff shard server on planned
            # rescales (and their successors discover it through the
            # supervisor advertisement above) only when this is on.
            env["ADAPTDL_HANDOFF"] = "on" if job.handoff else "off"
        record = self.state.get_job(job.name)
        if record is not None and record.trace_parent:
            # Same graftscope propagation as the single-job runner:
            # the new incarnation joins the rescale decision's trace.
            env["ADAPTDL_TRACEPARENT"] = record.trace_parent
        topology = topology or {}
        env["ADAPTDL_SEQ_SHARDS"] = str(topology.get("seqShards", 1))
        env["ADAPTDL_MODEL_SHARDS"] = str(
            topology.get("modelShards", 1)
        )
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

    def _run_job(self, job: JobSpec) -> None:
        failures = 0
        while True:
            if job.name in self._stopped:
                self._discard_warm(job.name, "job stopped")
                self.state.update(job.name, status="Stopped")
                self.exit_codes.setdefault(
                    job.name, GRACEFUL_EXIT_CODE
                )
                return
            allocation, topology = self.state.get_launch_config(
                job.name
            )
            if not allocation:
                # Wait until the allocator gives this job chips.
                self.state.wait_for(
                    lambda jobs: bool(jobs[job.name].allocation),
                    timeout=5.0,
                )
                continue
            num_replicas = len(allocation)
            if job.name in self._stopped:
                continue  # stop_job raced the launch-config read
            LOG.info(
                "starting %s: replicas=%d restarts=%d topology=%s",
                job.name,
                num_replicas,
                self.restart_counts[job.name],
                topology,
            )
            # No-op if stop_job already made the status terminal
            # (ClusterState keeps terminal statuses sticky). The
            # restart counter is persisted alongside so a recovered
            # controller resumes it.
            self.state.update(
                job.name,
                status="Running",
                restarts=self.restart_counts[job.name],
            )
            proc = self._adopt_warm(job, allocation, topology)
            if proc is None:
                try:
                    # Same injected-launch-failure path as the local
                    # runner: counted against the job's retry budget.
                    faults.maybe_fail("runner.launch.pre")
                    proc = subprocess.Popen(
                        [sys.executable, job.script],
                        env=self._job_env(job, num_replicas, topology),
                    )
                except faults.InjectedFault:
                    LOG.warning(
                        "injected launch failure for %s", job.name
                    )
                    proc = None
            if proc is None:
                code, signalled = 1, False
            else:
                self.procs[job.name] = proc
                code, signalled = self._supervise(
                    proc, job, allocation, topology
                )
            if code == 0:
                self._discard_warm(job.name, "job succeeded")
                self.state.update(job.name, status="Succeeded")
                self.exit_codes[job.name] = 0
                return
            if code == GRACEFUL_EXIT_CODE or (
                signalled and code == -signal.SIGTERM
            ):
                self.restart_counts[job.name] += 1
                continue
            failures += 1
            # The incumbent died before cutover: any warm successor
            # was built against state the crash never drained.
            self._discard_warm(
                job.name, "incumbent crashed before cutover"
            )
            # A non-graceful death never ran the drain, so any handoff
            # descriptor in the checkpoint dir is from an older
            # incarnation — withdraw it rather than let a successor
            # spend its probe budget on a dead peer (the successor's
            # exact-predecessor group check also rejects it).
            from adaptdl_tpu import handoff

            handoff.withdraw_descriptor(job.checkpoint_dir)
            LOG.warning(
                "%s failed code=%s (%d/%d)",
                job.name,
                code,
                failures,
                self.max_failures,
            )
            if failures > self.max_failures:
                self.state.update(job.name, status="Failed")
                self.exit_codes[job.name] = code
                return
            self.restart_counts[job.name] += 1

    def _spawn_warm(self, job: JobSpec, allocation, topology) -> None:
        """Same speculation as the single-job runner: bring the
        successor all the way up while the incumbent keeps training,
        gated on the allocator's published candidate matching the
        drifted config. Runs on the job's supervising thread, so the
        warm-up window of one job never delays another's."""
        candidate = self.state.get_candidate(job.name)
        if not warmup.candidate_matches(candidate, allocation, topology):
            LOG.info(
                "no matching candidate for %s; rescaling cold",
                job.name,
            )
            return
        self._discard_warm(job.name, "superseded by a newer drift")
        warm = warmup.WarmSuccessor(
            [sys.executable, job.script],
            self._job_env(
                job,
                max(len(allocation), 1),
                topology,
                restarts=self.restart_counts[job.name] + 1,
            ),
            allocation,
            topology,
            restarts=self.restart_counts[job.name] + 1,
        )
        try:
            warm.spawn()
        except faults.InjectedFault:
            LOG.warning(
                "injected warm-up spawn failure for %s", job.name
            )
            warm.discard()
            return
        if warm.wait_ready(warmup.READY_DEADLINE_S):
            self._warms[job.name] = warm
        else:
            warm.discard("never became ready")

    def _adopt_warm(self, job: JobSpec, allocation, topology):
        """Cutover (or mispredict fallback) for one job — see the
        single-job runner's `_adopt_warm`."""
        warm = self._warms.pop(job.name, None)
        if warm is None:
            return None
        if not warm.alive():
            warm.discard("died during warm-up")
            return None
        if not warm.matches(allocation, topology) or (
            warm.restarts != self.restart_counts[job.name]
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
            job.name,
            max(len(allocation), 1),
        )
        return proc

    def _discard_warm(self, name: str, reason: str) -> None:
        warm = self._warms.pop(name, None)
        if warm is not None:
            warm.discard(reason)

    def _supervise(self, proc, job, allocation, topology=None):
        signalled = False
        term_deadline = None
        while True:
            code = proc.poll()
            if code is not None:
                return code, signalled
            current, cur_topology = self.state.get_launch_config(
                job.name
            )
            # A topology-only change (same chips, new sp/tp) also
            # requires a rescale; normalized so None == pure-DP {1,1}
            # never restarts a job just because hints arrived.
            drifted = list(current) != list(
                allocation
            ) or normalize_topology(cur_topology) != normalize_topology(
                topology
            )
            if not signalled and drifted:
                LOG.info(
                    "%s drift: %d -> %d replicas, topology %s -> %s",
                    job.name,
                    len(allocation),
                    len(current),
                    topology,
                    cur_topology,
                )
                if env_mod.warmup_enabled() and current:
                    # Successor first, signal second — the incumbent
                    # keeps taking steps through the warm-up window.
                    self._spawn_warm(job, current, cur_topology)
                proc.send_signal(signal.SIGTERM)
                signalled = True
                term_deadline = (
                    time.monotonic() + self.term_grace_period
                )
            if (
                term_deadline is not None
                and time.monotonic() > term_deadline
            ):
                proc.kill()
                term_deadline = None
            time.sleep(0.2)

    # -- whole-run lifecycle ------------------------------------------

    def run(self) -> dict[str, int]:
        """Run all jobs to completion; returns exit codes by job."""
        self.supervisor.start()
        self.allocator.start()
        threads = [
            threading.Thread(
                target=self._run_job, args=(job,), daemon=True,
                name=f"job-{job.name}",
            )
            for job in self.jobs.values()
        ]
        try:
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join()
            return dict(self.exit_codes)
        finally:
            for name in list(self._warms):
                self._discard_warm(name, "runner shutting down")
            self.allocator.stop()
            self.supervisor.stop()
