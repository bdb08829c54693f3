"""Speculative successor warm-up: the zero-downtime rescale protocol.

A planned rescale used to serialize decide -> drain -> handoff ->
restore -> compile, so every planned rescale lost steps. This module
overlaps the successor's entire cold start with the incumbent's last
steps instead (CheckFreq FAST'21 moves serialization off the critical
path; we move the *successor startup* off it):

- The allocator publishes its decision as a CANDIDATE first
  (``ClusterState.publish_candidate`` / ``GET /candidate/{job}``), so
  when the runner sees the launch config drift it finds a matching
  warm-up target.
- The runner spawns the successor with ``ADAPTDL_WARMUP=1`` BEFORE
  signalling the incumbent (``WarmSuccessor``). The successor runs its
  whole cold start — imports, jax init, trainer build, AOT compile,
  differential chunk prefetch from the incumbent's shard server — then
  touches the READY file and holds (``maybe_hold``).
- Only then is the incumbent SIGTERMed; once it drains gracefully the
  runner revalidates the launch config against what the successor was
  built for and writes ``go`` into the CUTOVER file — the successor
  pulls just the chunks that changed since its prefetch and takes its
  first step within about one step interval.
- Anything else — warm successor dies mid-warm-up, candidate
  mispredicted, candidate from a rolled-back epoch (the state machine
  clears it), incumbent crashes before cutover — discards the warm
  successor (``abort`` + SIGKILL) and falls back to the existing
  planned path bit-identically.

The file-based ready/cutover channel keeps the protocol transport-free
on the one-box runners: both ends share a filesystem by construction
(they share a checkpoint dir), and a killed runner leaves nothing a
successor could mistake for a go signal.

Limit: the warm successor runs "jax init" while the incumbent still
trains, i.e. two JAX processes on the job's chips at once. A chip
serves one process at a time, so where successor and incumbent would
share a chip (the one-box runners on directly attached TPUs) the
successor fails or hangs at backend start-up and the rescale takes the
cold path. Warm-up only helps where the successor gets chips the
incumbent does not hold; it is off by default
(``ADAPTDL_WARMUP_ENABLED``) and exercised on the CPU backend only.
"""

from __future__ import annotations

import logging
import os
import shutil
import subprocess
import tempfile
import time

from adaptdl_tpu import env, faults, rpc, trace
from adaptdl_tpu._signal import GRACEFUL_EXIT_CODE
from adaptdl_tpu.sched.state import normalize_topology

LOG = logging.getLogger(__name__)

# Longest the runner waits for a warm successor to mark itself ready
# before discarding it and rescaling cold: warm-up must never delay a
# rescale by more than it saves. The held successor waits six times
# as long for the cutover file — its hold spans the incumbent's whole
# drain (the final save), not just the warm-up window — and takes an
# expired wait for an abort.
READY_DEADLINE_S = 20.0
HOLD_DEADLINE_S = 6.0 * READY_DEADLINE_S

# Cutover-file verdicts (the whole wire format of the runner ->
# successor channel).
GO = "go"
ABORT = "abort"


def candidate_matches(
    candidate: dict | None, allocation, topology
) -> bool:  # wire: consumes=candidate_alloc
    """Whether a published candidate predicts exactly this launch
    config — the runner warms a successor only for a config the
    allocator told it to expect, so a vanished candidate (rolled-back
    epoch, superseding decision) disables warm-up instead of racing
    it."""
    if not candidate:
        return False
    return list(candidate.get("allocation") or []) == list(
        allocation or []
    ) and normalize_topology(
        candidate.get("topology")
    ) == normalize_topology(topology)


def fetch_candidate(  # wire: consumes=candidate_alloc
    supervisor_url: str | None = None, job: str | None = None
) -> dict | None:
    """The supervisor's published warm-up target for this job
    (``GET /candidate/{job}``), or None if nothing is predicted. The
    remote-runner half of what one-box runners read straight off
    ``ClusterState.get_candidate``: an agent on another host polls
    this to decide whether (and against which config) to pre-warm a
    successor. Best-effort by design — a dead supervisor means "warm
    nothing, rescale cold", never an error."""
    sup = supervisor_url or env.supervisor_url()
    job = job or env.job_id()
    if not sup or not job:
        return None
    try:
        response = rpc.default_client().get(
            f"{sup}/candidate/{job}",
            endpoint=f"candidate/{job}",
            timeout=(2, 5),
            attempts=2,
            deadline=5.0,
            use_circuit=False,
        )
        if response.status_code != 200:
            return None
        body = response.json()
    except Exception:  # noqa: BLE001 - speculation is best-effort
        LOG.debug("candidate readback failed", exc_info=True)
        return None
    if not isinstance(body, dict) or not body.get("allocation"):
        return None
    return {
        "allocation": list(body["allocation"]),
        "topology": body.get("topology"),
        "batchConfig": body.get("batchConfig"),
        "epoch": int(body.get("epoch", -1)),
    }


class WarmSuccessor:
    """One speculatively-spawned successor process and its cutover
    channel. The runner owns the lifecycle: ``spawn`` ->
    ``wait_ready`` -> (incumbent drains) -> ``matches`` ->
    ``cutover`` | ``discard``."""

    def __init__(
        self,
        argv: list[str],
        job_env: dict,
        allocation,
        topology: dict | None,
        restarts: int,
    ):
        self.argv = list(argv)
        self.allocation = list(allocation or [])
        self.topology = normalize_topology(topology)
        self.restarts = int(restarts)
        self.workdir = tempfile.mkdtemp(prefix="adaptdl-warmup-")
        self.ready_file = os.path.join(self.workdir, "ready")
        self.cutover_file = os.path.join(self.workdir, "cutover")
        self.env = dict(job_env)
        self.env["ADAPTDL_WARMUP"] = "1"
        self.env["ADAPTDL_WARMUP_READY_FILE"] = self.ready_file
        self.env["ADAPTDL_WARMUP_CUTOVER_FILE"] = self.cutover_file
        self.proc: subprocess.Popen | None = None

    def spawn(self) -> None:
        """Start the successor in warm-up mode (raises InjectedFault
        under a ``warmup.spawn`` schedule — the caller falls back to
        the cold path)."""
        faults.maybe_fail("warmup.spawn")
        self.proc = subprocess.Popen(  # detached: warm-successor
            self.argv, env=self.env
        )

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def wait_ready(self, deadline_s: float) -> bool:
        """Block (while the incumbent keeps training) until the
        successor marks itself warm, it dies, or the deadline
        expires — warm-up must never delay a rescale by more than it
        saves."""
        deadline = time.monotonic() + max(deadline_s, 0.0)
        while time.monotonic() < deadline:
            if os.path.exists(self.ready_file):
                return True
            if not self.alive():
                return False
            time.sleep(0.05)
        return os.path.exists(self.ready_file)

    def matches(self, allocation, topology) -> bool:
        """Whether this successor was built for exactly the launch
        config now published — anything else is a misprediction and
        must be discarded, never adopted."""
        return list(allocation or []) == self.allocation and (
            normalize_topology(topology) == self.topology
        )

    def cutover(self) -> subprocess.Popen:
        """Adopt: release the held successor (raises InjectedFault
        under a ``warmup.cutover`` schedule — the caller discards and
        relaunches cold)."""
        faults.maybe_fail("warmup.cutover")
        _write_atomic(self.cutover_file, GO)
        return self.proc

    def discard(self, reason: str = "") -> None:
        """Abandon the speculation: tell a held successor to exit,
        kill it regardless (it may be wedged mid-import), and remove
        the channel directory. Falling back costs exactly the cold
        path — the successor never registered, restored, or wrote
        anything durable."""
        if reason:
            LOG.info("discarding warm successor: %s", reason)
        try:
            _write_atomic(self.cutover_file, ABORT)
        except OSError:
            pass
        if self.alive():
            self.proc.kill()
        if self.proc is not None:
            try:
                self.proc.wait(timeout=5)
            except Exception:  # noqa: BLE001 - best-effort reap
                pass
        shutil.rmtree(self.workdir, ignore_errors=True)


def _write_atomic(path: str, verdict: str) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(verdict)
    os.replace(tmp, path)


# ---- job side (runs inside the successor process) --------------------

_held = False


def maybe_hold() -> bool:
    """The warm successor's half of the protocol, called from
    ``checkpoint.load_state`` (so any conforming script warms
    everything up to its state restore for free) and callable directly
    from scripts that want a later hold point. In a normal launch this
    is a no-op; under ``ADAPTDL_WARMUP=1`` it prefetches the peer's
    chunks into the differential cache, touches the ready file, and
    blocks until the runner's verdict: ``go`` returns (the restore
    then pulls only changed chunks), ``abort`` exits with the graceful
    rescale code so nothing counts it as a failure. Idempotent — the
    first call holds, later calls return immediately."""
    global _held
    if _held or not env.warmup_flag():
        return False
    _held = True
    if env.handoff_enabled():
        from adaptdl_tpu import handoff

        try:
            handoff.warm_prefetch()
        except Exception:  # noqa: BLE001 - speculation is best-effort
            LOG.debug("warm prefetch failed", exc_info=True)
    with trace.span("warmup.hold") as attrs:
        ready = env.warmup_ready_file()
        if ready:
            _write_atomic(ready, "ready")
        verdict = _await_cutover(env.warmup_cutover_file())
        attrs["verdict"] = verdict
    if verdict != GO:
        LOG.info("warm-up discarded (%s); exiting gracefully", verdict)
        # os._exit: mid-bootstrap there may be no exception path that
        # reaches a clean interpreter shutdown, and atexit hooks must
        # not write anything durable from a discarded speculation.
        os._exit(GRACEFUL_EXIT_CODE)
    # The GO verdict is consumed and this process is the channel dir's
    # last reader (the incumbent is deep in its final drain, possibly
    # already gone — discard() covers the abort side), so the adopted
    # successor removes the dir.
    cutover_path = env.warmup_cutover_file()
    if cutover_path:
        shutil.rmtree(
            os.path.dirname(cutover_path), ignore_errors=True
        )
    return True


def _await_cutover(path: str | None) -> str:
    """Poll the cutover file until the runner renders a verdict. An
    unset path (direct test use, no runner) proceeds immediately; an
    expired deadline counts as ``abort`` — the runner is gone, and
    proceeding could fight an incumbent that still owns the chips."""
    if not path:
        return GO
    deadline = time.monotonic() + HOLD_DEADLINE_S
    while time.monotonic() < deadline:
        try:
            with open(path, encoding="utf-8") as f:
                return f.read().strip() or GO
        except OSError:
            time.sleep(0.05)
    return ABORT
