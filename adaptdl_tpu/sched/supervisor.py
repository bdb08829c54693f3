"""Supervisor: the cluster's REST face toward running jobs.

Endpoints (URL shapes match the reference so trainer-side code is
backend-agnostic; reference: sched/adaptdl_sched/supervisor.py:45-80):

- ``GET /discover/{namespace}/{name}/{group}?replicas=N`` — long-polls
  until all N workers of restart-group ``group`` have registered,
  then returns their addresses by rank (rank-0 rendezvous).
- ``PUT /register/{namespace}/{name}/{group}/{rank}`` — worker
  self-registration (the k8s backend gets this from pod IPs instead).
- ``PUT /hints/{namespace}/{name}`` — validated sched-hints intake.
- ``PUT /heartbeat/{namespace}/{name}/{rank}[?group=N]`` — liveness
  lease renewal (register/hints/config traffic also renews, so
  heartbeats piggyback on whatever the worker is already saying). The
  optional ``group`` lets the state layer reject a doomed
  incarnation's dying beats and lets single-process jobs — which
  never register — prove a pending allocation epoch alive.
- ``GET /hints/{namespace}/{name}``, ``GET /healthz``.
- ``POST /preempt/{namespace}/{name}`` — reclaim-notice intake: the
  worker reports a preemption notice the moment it lands; the
  supervisor withdraws the doomed slots from inventory, updates the
  per-slot-kind hazard EWMA, and kicks the allocator so the
  successor's allocation epoch opens *during* the notice window.
  Idempotent per drain (retries and sibling ranks fold into one).
- ``GET /status`` — operator-facing JSON: per-job phase, degraded
  flag, allocation epoch/state, lease ages, plus slot strikes,
  quarantine, and recovery info (the ``adaptdl-tpu status`` CLI).
- ``PUT /trace/{namespace}/{name}`` — graftscope span intake: workers
  flush their buffered rescale-lifecycle spans here (piggybacked on
  the sched-hints cadence); the supervisor stores them per job (a
  bounded ring) and folds their durations into its /metrics
  histograms.
- ``GET /trace/{namespace}/{name}`` — the stitched per-job timeline:
  worker-posted spans merged with this process's own spans for the
  job (allocator decide/publish, epoch prepare/commit/rollback,
  journal appends), deduplicated by span id. The ``adaptdl-tpu
  trace`` CLI renders it as a phase waterfall and a Perfetto file.

``/metrics`` is assembled with :class:`trace.PromBuilder`, so every
series carries ``# HELP``/``# TYPE`` and escaped label values — the
Prometheus exposition-format conformance test parses the output with
a strict grammar and fails on any malformed series.

Liveness: each worker rank holds a lease of ``lease_ttl`` seconds; a
background sweeper expires stale leases, marks the job degraded, and
withdraws its allocation so the allocator re-places it — a vanished
worker costs one TTL, not forever. The same sweeper drives the
transactional-rescale clock: pending allocation epochs whose commit
deadline lapsed are rolled back to the last-committed allocation
(``ClusterState.expire_overdue_allocations``). Handlers are also
fault-injection points (``sup.*.pre``): the chaos suite turns
injected faults into 500s to prove the client side retries through
supervisor blips.

Runs its own thread + aiohttp event loop so trainers and the local
runner can use it without an async main.
"""

from __future__ import annotations

import asyncio
import functools
import logging
import os
import threading
import time
from collections import deque

from aiohttp import web

from adaptdl_tpu import env, faults, sched_hints, trace
from adaptdl_tpu.sched.http_server import (
    ThreadedHttpServer,
    faultable as _faultable,
)
from adaptdl_tpu.sched.state import ClusterState

LOG = logging.getLogger(__name__)

_POLL_INTERVAL = 0.25
_DISCOVER_TIMEOUT = 300.0


def _group_param(request: web.Request) -> int | None:
    """The worker's restart group, when the request reports it."""
    raw = request.query.get("group")
    return int(raw) if raw not in (None, "") else None


class Supervisor(ThreadedHttpServer):
    def __init__(
        self,
        state: ClusterState,
        host="127.0.0.1",
        port=0,
        lease_ttl: float | None = None,
        sweep_interval: float | None = None,
        shard_id: int | None = None,
        slices_fn=None,
    ):
        super().__init__(host=host, port=port)
        self._state = state
        # Sharded control plane (sched/shard.py): which shard this
        # supervisor is, and a callable yielding the slice names this
        # shard owns — published over GET /shard/inventory so the
        # merged-inventory view can run full allocation cycles across
        # shard boundaries. Both stay inert in the classic unsharded
        # deployment (shard 0, no slices published).
        self._shard_id = (
            shard_id
            if shard_id is not None
            else (env.shard_id() or 0)
        )
        self._slices_fn = slices_fn
        self._lease_ttl = (
            env.lease_ttl() if lease_ttl is None else lease_ttl
        )
        # Per-job store of worker-posted trace spans (graftscope).
        # Bounded like the in-process ring buffer; written by the
        # trace-intake executor thread, read by GET /trace.
        self._trace_lock = threading.Lock()  # lock-order: 50
        self._trace_store: dict[str, deque] = {}  # guarded-by: _trace_lock
        # Default cadence: a quarter of whichever expiry clock is
        # active (lease TTL, else the allocation-commit timeout).
        clock = self._lease_ttl
        if clock <= 0:
            clock = getattr(state, "alloc_commit_timeout", 0.0)
        self._sweep_interval = (
            sweep_interval
            if sweep_interval is not None
            else max(min(clock / 4.0, 5.0), 0.05)
        )

    def _renew(
        self, key: str, rank: int, group: int | None = None
    ) -> None:
        """Piggybacked lease renewal: any authenticated-enough traffic
        from a worker proves it alive. ``group`` (when the request
        reports it) gets the same stale-incarnation guard as a
        heartbeat — a doomed incarnation's hints/config traffic must
        not renew leases or satisfy the commit quorum of the
        allocation epoch replacing it."""
        self._state.renew_lease(key, rank, self._lease_ttl, group=group)

    @staticmethod
    async def _offload(fn, *args, **kwargs):
        """Run a journaled state mutation off the event loop: every
        journal append fsyncs (and each 256th rewrites a full
        snapshot), so running it inline would stall heartbeats and
        discover long-polls behind disk latency. ``ClusterState`` is
        lock-protected, so executor threads are safe callers."""
        return await asyncio.get_event_loop().run_in_executor(
            None, functools.partial(fn, *args, **kwargs)
        )

    # -- handlers -----------------------------------------------------

    @_faultable("sup.discover.pre")
    async def _discover(self, request: web.Request) -> web.Response:
        key = "{namespace}/{name}".format(**request.match_info)
        group = int(request.match_info["group"])
        want = int(request.query.get("replicas", "0"))
        deadline = (
            asyncio.get_event_loop().time() + _DISCOVER_TIMEOUT
        )

        def probe():
            # State reads take _cond, which journal appends hold
            # across fsync — poll from the executor, not the loop.
            record = self._state.get_job(key)
            if record is None or record.group != group:
                return None
            return self._state.get_workers(key) or {}

        while True:
            workers = await self._offload(probe)
            if workers is not None and (
                (want and len(workers) >= want)
                or (not want and workers)
            ):
                return web.json_response(
                    {str(rank): addr for rank, addr in workers.items()}
                )
            if asyncio.get_event_loop().time() > deadline:
                return web.json_response(
                    {"error": "discover timeout"}, status=408
                )
            await asyncio.sleep(_POLL_INTERVAL)

    @_faultable("sup.register.pre")
    async def _register(  # idempotent: keyed-by=rank # wire: consumes=register
        self, request: web.Request
    ) -> web.Response:
        key = "{namespace}/{name}".format(**request.match_info)
        group = int(request.match_info["group"])
        rank = int(request.match_info["rank"])
        body = await request.json()

        def mutate() -> bool:
            if self._state.get_job(key) is None:
                return False
            if self._state.register_worker(
                key,
                group,
                rank,
                body["address"],
                # Reported process count = the commit quorum for a
                # pending allocation epoch (how many ranks must prove
                # liveness).
                processes=body.get("processes"),
            ):
                # Only an ACCEPTED registration earns a lease: a
                # stale-group retry must not plant a phantom lease for
                # a rank the current incarnation doesn't run (its
                # expiry would degrade a healthy job).
                self._renew(key, rank)
            return True

        if not await self._offload(mutate):
            return web.json_response({"error": "no such job"}, status=404)
        return web.json_response({"ok": True})

    @_faultable("sup.heartbeat.pre")
    async def _heartbeat(  # idempotent # wire: consumes=heartbeat
        self, request: web.Request
    ) -> web.Response:
        key = "{namespace}/{name}".format(**request.match_info)
        rank = int(request.match_info["rank"])
        group = _group_param(request)
        # Optional piggyback payload: the rank's step-time EWMA rides
        # the beat it already sends (straggler detection's intake —
        # graftwatch turns per-rank outliers into a per-slot
        # adaptdl_slot_suspect gauge). A beat without a body stays a
        # plain lease renewal.
        step_ewma = None
        if request.can_read_body:
            try:
                body = await request.json()
            except ValueError:
                body = None
            if isinstance(body, dict):
                raw = body.get("stepTimeEwma")
                if (
                    isinstance(raw, (int, float))
                    and not isinstance(raw, bool)
                    and raw > 0
                ):
                    step_ewma = float(raw)

        def mutate() -> bool:
            renewed = self._state.renew_lease(
                key, rank, self._lease_ttl, group=group
            )
            if renewed and step_ewma is not None:
                self._state.note_step_time(key, rank, step_ewma)
            return renewed

        if not await self._offload(mutate):
            return web.json_response({"error": "no such job"}, status=404)
        return web.json_response(
            {"ok": True, "ttl": self._lease_ttl}
        )

    @_faultable("sup.hints.pre")
    async def _put_hints(  # idempotent # wire: consumes=sched_hints
        self, request: web.Request
    ) -> web.Response:
        key = "{namespace}/{name}".format(**request.match_info)
        hints = await request.json()
        try:
            sched_hints.validate_hints(hints)
        except ValueError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        group = _group_param(request)

        def mutate() -> bool:
            if self._state.get_job(key) is None:
                return False
            self._state.update(key, hints=hints)
            # graftwatch: the trainer-measured goodput rides the hint
            # post; the watch store pairs it with the model's
            # prediction each allocator cycle (the drift monitor).
            measured = hints.get("measuredGoodput")
            if isinstance(measured, (int, float)) and measured >= 0:
                self._state.observe_measured(key, float(measured))
            # Hints are posted from rank 0's fit thread: count them as
            # a liveness beat so chatty jobs never need a dedicated
            # beat.
            self._renew(key, 0, group=group)
            return True

        if not await self._offload(mutate):
            return web.json_response({"error": "no such job"}, status=404)
        return web.json_response({"ok": True})

    @_faultable("sup.hints.get.pre")
    async def _get_hints(self, request: web.Request) -> web.Response:
        key = "{namespace}/{name}".format(**request.match_info)
        record = await self._offload(self._state.get_job, key)
        if record is None:
            return web.json_response({"error": "no such job"}, status=404)
        return web.json_response(record.hints or {})

    @_faultable("sup.config.pre")
    async def _get_config(self, request: web.Request) -> web.Response:
        """The cluster's current decision for a job, as one snapshot:
        allocation + topology (changes mean checkpoint-restart) and
        the batch config + re-tune counter (changes are adopted live,
        in-process — the re-tune fast path). Jobs poll this from the
        dataloader's re-optimization cadence."""
        key = "{namespace}/{name}".format(**request.match_info)
        group = _group_param(request)

        def fetch():
            snapshot = self._state.get_config_snapshot(key)
            if snapshot is not None:
                # Config polls run on rank 0's re-optimization cadence
                # — more piggybacked liveness (first lease = journal).
                self._renew(key, 0, group=group)
            return snapshot

        snapshot = await self._offload(fetch)
        if snapshot is None:
            return web.json_response({"error": "no such job"}, status=404)
        return web.json_response(snapshot)

    @_faultable("sup.preempt.pre")
    async def _preempt(  # idempotent: keyed-by=group # wire: consumes=preempt
        self, request: web.Request
    ) -> web.Response:
        """Reclaim-notice intake (``POST /preempt/{job}``): the worker
        reports the notice the moment it lands, so the supervisor
        withdraws the doomed slots and the allocator opens the
        successor's epoch DURING the notice window — re-placement
        overlaps the drain instead of waiting for lease expiry.
        Idempotent: rpc retries and sibling ranks of the same doomed
        incarnation fold into one drain."""
        key = "{namespace}/{name}".format(**request.match_info)
        try:
            body = await request.json()
        except ValueError:
            body = {}
        if not isinstance(body, dict):
            body = {}

        def mutate() -> bool | None:
            if self._state.get_job(key) is None:
                return None
            accepted = self._state.report_preemption(
                key,
                group=body.get("group"),
                rank=body.get("rank"),
                slot=body.get("slot"),
                notice_s=body.get("noticeS"),
                trace_parent=body.get("traceParent"),
            )
            if accepted and body.get("rank") is not None:
                # The notice is also proof of life (for a few more
                # seconds): piggyback the lease renewal like any
                # other worker traffic.
                self._renew(
                    key, int(body["rank"]), group=body.get("group")
                )
            return accepted

        accepted = await self._offload(mutate)
        if accepted is None:
            return web.json_response(
                {"error": "no such job"}, status=404
            )
        return web.json_response(
            {"ok": True, "draining": bool(accepted)}
        )

    @_faultable("sup.incident.pre")
    async def _incident(  # idempotent: keyed-by=(group,step,kind) # wire: consumes=incident
        self, request: web.Request
    ) -> web.Response:
        """Numeric-incident intake (``POST /incident/{job}``): a
        worker's guard reports a NaN/spike the moment it fires, the
        journaled apply classifies blame (same slot across different
        data => strike toward quarantine; same data across slots =>
        data blame, no hardware action), and the allocator is kicked
        so a quarantined slot's occupant is re-placed immediately.
        Idempotent: rpc retries of the same (group, step, kind)
        identity fold into one count and at most one strike."""
        key = "{namespace}/{name}".format(**request.match_info)
        group = _group_param(request)
        try:
            body = await request.json()
        except ValueError:
            body = {}
        if not isinstance(body, dict):
            body = {}
        kind = body.get("kind")
        if not kind:
            return web.json_response(
                {"error": "kind required"}, status=400
            )

        def mutate() -> dict | None:
            if self._state.get_job(key) is None:
                return None
            verdict = self._state.report_incident(
                key,
                str(kind),
                group=group,
                rank=body.get("rank"),
                step=body.get("step"),
                data=body.get("data"),
                action=body.get("action"),
            )
            if body.get("rank") is not None:
                # The report is also proof of life: piggyback the
                # lease renewal like any other worker traffic.
                self._renew(key, int(body["rank"]), group=group)
            if verdict is None:
                return {"duplicate": True}
            blame, slot = verdict
            return {"duplicate": False, "blame": blame, "slot": slot}

        verdict = await self._offload(mutate)
        if verdict is None:
            return web.json_response(
                {"error": "no such job"}, status=404
            )
        return web.json_response({"ok": True, **verdict})

    @_faultable("sup.handoff.pre")
    async def _put_handoff(  # idempotent: keyed-by=group # wire: consumes=handoff_ad
        self, request: web.Request
    ) -> web.Response:
        """Shard-server advertisement (``PUT /handoff/{job}``): the
        draining incarnation's spawned handoff server reports its URL
        + restart group so the successor — possibly on another host —
        discovers its predecessor's in-memory state through the
        control plane during the allocation epoch."""
        key = "{namespace}/{name}".format(**request.match_info)
        try:
            body = await request.json()
        except ValueError:
            body = {}
        url = body.get("url") if isinstance(body, dict) else None
        if not url:
            return web.json_response(
                {"error": "url required"}, status=400
            )
        try:
            group = int(body.get("group", 0))
        except (TypeError, ValueError):
            return web.json_response(
                {"error": "group must be an integer"}, status=400
            )
        accepted = await self._offload(
            self._state.advertise_handoff,
            key,
            str(url),
            group,
        )
        if not accepted:
            return web.json_response(
                {"error": "no such job (or stale group)"}, status=404
            )
        return web.json_response({"ok": True})

    @_faultable("sup.handoff.get.pre")
    async def _get_handoff(self, request: web.Request) -> web.Response:
        key = "{namespace}/{name}".format(**request.match_info)

        def fetch():
            if self._state.get_job(key) is None:
                return None
            return self._state.get_handoff(key) or {}

        handoff = await self._offload(fetch)
        if handoff is None:
            return web.json_response(
                {"error": "no such job"}, status=404
            )
        return web.json_response(handoff)

    @_faultable("sup.candidate.pre")
    async def _get_candidate(  # wire: produces=candidate_alloc,envelope
        self, request: web.Request
    ) -> web.Response:
        """Speculative warm-up readback (``GET /candidate/{job}``):
        the allocator's PREDICTED next launch config, published just
        ahead of the decision. A runner (possibly on another host)
        polls this to pre-warm a successor; 404 with no candidate
        means nothing is predicted — warm nothing, rescale cold."""
        key = "{namespace}/{name}".format(**request.match_info)

        def fetch():
            if self._state.get_job(key) is None:
                return None
            return (self._state.get_candidate(key),)

        found = await self._offload(fetch)
        if found is None:
            return web.json_response(
                {"error": "no such job"}, status=404
            )
        candidate = found[0]
        if candidate is None:
            return web.json_response(
                {"error": "no candidate"}, status=404
            )
        return web.json_response(candidate)

    async def _healthz(self, request: web.Request) -> web.Response:
        return web.json_response({"ok": True})

    @_faultable("sup.status.pre")
    async def _status(self, request: web.Request) -> web.Response:
        """Operator-facing cluster view: per-job phase + degraded flag
        + allocation epoch/state + lease ages, slot strikes and
        quarantine, and durable-state recovery info — what
        ``adaptdl-tpu status`` renders so an operator can see WHY an
        allocation was withdrawn or rolled back. Assembled entirely on
        the executor: every section takes _cond (or the watch lock),
        and a mid-append fsync must not stall heartbeats behind it."""
        return web.json_response(
            await self._offload(self._status_payload)
        )

    def _status_payload(self) -> dict:
        payload = self._state.status_snapshot()
        for job in payload["jobs"].values():
            # Remaining seconds -> age since last renewal (operators
            # reason about "how long since this rank last spoke").
            job["leaseAgeS"] = {
                rank: round(max(self._lease_ttl - remaining, 0.0), 3)
                for rank, remaining in job.pop(
                    "leaseRemainingS"
                ).items()
            }
        health = self._state.slot_health()
        payload["slotStrikes"] = health["strikes"]
        payload["quarantinedSlots"] = {
            slot: round(remaining, 3)
            for slot, remaining in health["quarantined"].items()
        }
        payload["rollbacks"] = health["rollbacks"]
        payload["recovery"] = self._state.recovery_info()
        # Preemption survival: which slots are draining under an
        # active notice, the per-kind hazard estimate, and notice
        # counts — the operator's answer to "why did that job move
        # off spot".
        preempt = self._state.preemption_info()
        payload["drainingSlots"] = {
            slot: round(remaining, 3)
            for slot, remaining in preempt["drainingSlots"].items()
        }
        payload["hazardRates"] = {
            kind: round(rate, 9)
            for kind, rate in preempt["hazardRates"].items()
        }
        payload["preemptionNotices"] = preempt["noticesByKind"]
        # graftguard: numeric-health incidents by kind plus the blame
        # tables — "which slot (or which data) keeps going bad".
        incidents = self._state.incident_info()
        payload["incidentsByKind"] = incidents["incidentsByKind"]
        payload["incidentSlotBlame"] = incidents["slotBlame"]
        payload["incidentDataBlame"] = incidents["dataBlame"]
        # graftwatch: measured vs predicted goodput, drift, and the
        # re-profiling flag per job — "is this job healthy" answered
        # from /status alone, no Prometheus scrape needed.
        watch_fields = self._state.watch.status_fields()
        for key, job in payload["jobs"].items():
            job.update(watch_fields.get(key, {}))
        return payload

    # -- graftwatch: goodput accounting + decision provenance ---------

    @_faultable("sup.watch.pre")
    async def _watch(self, request: web.Request) -> web.Response:
        """The watch store's bounded snapshot: cluster utilization and
        per-tenant goodput-share/fairness series, per-job goodput
        triple + drift, suspect slots, provenance cycle summaries
        (the ``adaptdl-tpu top`` payload)."""
        return web.json_response(
            await self._offload(self._state.watch.snapshot)
        )

    @_faultable("sup.shard.inventory.pre")
    async def _shard_inventory(  # wire: produces=shard_inventory
        self, request: web.Request
    ) -> web.Response:
        """This shard's slice of the merged inventory view: the jobs
        it owns, the dirty subset awaiting an allocator cycle (a
        non-consuming peek — publication must not steal the local
        allocator's work), and the slice names partitioned to it.
        The router/allocator merges these across shards; PR 11's
        partitioned full cycle maps 1:1 onto the boundaries."""

        def build() -> dict:
            return {
                "shard": self._shard_id,
                "jobs": sorted(self._state.jobs()),
                "dirtyJobs": self._state.dirty_jobs(),
                "slices": (
                    sorted(self._slices_fn())
                    if self._slices_fn is not None
                    else []
                ),
            }

        return web.json_response(await self._offload(build))

    # -- live resharding (sched/shard.py migration protocol) ----------

    @_faultable("sup.reshard.pre")
    async def _reshard_stream(  # wire: produces=reshard
        self, request: web.Request
    ) -> web.Response:
        """One tenant-migration stream batch (source side): a
        snapshot-mode export when ``from_seq`` is absent, else the
        seq-ordered delta tail above it — both sha-stamped. An
        injected ``reshard.stream.batch`` fault is a retryable 500,
        like every other supervisor blip the rpc client rides out."""
        tenant = request.match_info["tenant"]
        raw = request.query.get("from_seq")
        from_seq = int(raw) if raw not in (None, "") else None
        raw_limit = request.query.get("limit")
        limit = (
            int(raw_limit) if raw_limit not in (None, "") else None
        )
        try:
            batch = await self._offload(
                self._state.stream_tenant, tenant, from_seq, limit
            )
        except faults.InjectedFault as exc:
            return web.json_response(
                {"error": f"injected fault: {exc}"}, status=500
            )
        return web.json_response(batch)

    @_faultable("sup.reshard.pre")
    async def _reshard_import(  # idempotent: keyed-by=epoch # wire: consumes=reshard # wire: produces=reshard
        self, request: web.Request
    ) -> web.Response:
        """Destination-side batch intake: journals + applies one
        stream batch (the body is the batch itself plus the migration
        ``epoch``) and acks the new durable watermark. Idempotent per
        (epoch, seq): a re-delivered batch at or below the watermark
        journals nothing and re-acks. A sha mismatch is a 400 — the
        coordinator rolls the migration back rather than retrying
        corruption."""
        tenant = request.match_info["tenant"]
        try:
            body = await request.json()
        except ValueError:
            return web.json_response(
                {"error": "body must be JSON"}, status=400
            )
        if not isinstance(body, dict) or not body.get("epoch"):
            return web.json_response(
                {"error": "body must carry the migration epoch"},
                status=400,
            )
        epoch = str(body.get("epoch"))
        try:
            watermark = await self._offload(
                self._state.reshard_import_batch, tenant, epoch, body
            )
        except faults.InjectedFault as exc:
            return web.json_response(
                {"error": f"injected fault: {exc}"}, status=500
            )
        except (KeyError, TypeError, ValueError) as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response(
            {
                "tenant": tenant,
                "epoch": epoch,
                "watermark": int(watermark),
            }
        )

    @_faultable("sup.reshard.pre")
    async def _reshard_fence(  # idempotent: keyed-by=tenant # wire: consumes=reshard # wire: produces=reshard
        self, request: web.Request
    ) -> web.Response:
        """Raise (or release, with ``{"release": true}``) the
        tenant's write fence on the source shard. The response
        carries the fence budget left and the source journal head —
        the seq the destination's watermark must reach before the
        flip. Re-raising an active fence just re-arms the deadline
        (idempotent for the coordinator's retry path)."""
        tenant = request.match_info["tenant"]
        body = None
        if request.can_read_body:
            try:
                body = await request.json()
            except ValueError:
                body = None
        body = body if isinstance(body, dict) else {}

        def mutate():
            if body.get("release"):
                self._state.unfence_tenant(tenant)
                return {
                    "tenant": tenant,
                    "fenced": False,
                    "seq": self._state.last_journal_seq(),
                }
            raw = body.get("deadlineS")
            timeout_s = None if raw is None else float(raw)
            self._state.fence_tenant(tenant, timeout_s)
            return {
                "tenant": tenant,
                "fenced": True,
                "deadlineS": self._state.fence_remaining(tenant),
                "seq": self._state.last_journal_seq(),
            }

        try:
            payload = await self._offload(mutate)
        except (TypeError, ValueError) as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response(payload)

    @_faultable("sup.reshard.pre")
    async def _reshard_commit(  # idempotent: keyed-by=epoch # wire: consumes=reshard # wire: produces=reshard
        self, request: web.Request
    ) -> web.Response:
        """Commit one side of a migration epoch. ``role: "dest"``
        promotes the caught-up import to ordinary records; ``role:
        "source"`` (post-flip) drops the tenant's jobs, plants the
        durable moved marker behind the 409 redirect, and releases
        the fence. Both idempotent per epoch — re-running a crashed
        plan journals nothing the second time."""
        tenant = request.match_info["tenant"]
        try:
            body = await request.json()
        except ValueError:
            return web.json_response(
                {"error": "body must be JSON"}, status=400
            )
        if not isinstance(body, dict) or not body.get("epoch"):
            return web.json_response(
                {"error": "body must carry the migration epoch"},
                status=400,
            )
        epoch = str(body.get("epoch"))

        def mutate():
            if body.get("role") == "dest":
                fresh = self._state.reshard_commit_dest(tenant, epoch)
                return {
                    "tenant": tenant,
                    "epoch": epoch,
                    "role": "dest",
                    "committed": bool(fresh),
                }
            removed = self._state.reshard_commit_source(
                tenant,
                epoch,
                int(body.get("toShard", -1)),
                int(body.get("mapVersion", 0)),
            )
            return {
                "tenant": tenant,
                "epoch": epoch,
                "role": "source",
                "committed": True,
                "moved": len(removed),
            }

        try:
            payload = await self._offload(mutate)
        except faults.InjectedFault as exc:
            return web.json_response(
                {"error": f"injected fault: {exc}"}, status=500
            )
        except (TypeError, ValueError) as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response(payload)

    @_faultable("sup.reshard.pre")
    async def _reshard_abort(  # idempotent: keyed-by=epoch # wire: consumes=reshard # wire: produces=reshard
        self, request: web.Request
    ) -> web.Response:
        """Roll the migration epoch back. On the destination the
        epoch's partially-imported jobs are discarded (journaled); on
        the source (``role: "source"``) the fence is released — the
        map never flipped, so the source simply resumes serving.
        Idempotent: an unknown epoch journals nothing."""
        tenant = request.match_info["tenant"]
        try:
            body = await request.json()
        except ValueError:
            return web.json_response(
                {"error": "body must be JSON"}, status=400
            )
        if not isinstance(body, dict) or not body.get("epoch"):
            return web.json_response(
                {"error": "body must carry the migration epoch"},
                status=400,
            )
        epoch = str(body.get("epoch"))

        def mutate():
            if body.get("role") == "source":
                self._state.unfence_tenant(tenant)
                return {
                    "tenant": tenant,
                    "epoch": epoch,
                    "role": "source",
                    "aborted": True,
                }
            dropped = self._state.reshard_abort(tenant, epoch)
            return {
                "tenant": tenant,
                "epoch": epoch,
                "role": "dest",
                "aborted": bool(dropped),
            }

        try:
            payload = await self._offload(mutate)
        except faults.InjectedFault as exc:
            return web.json_response(
                {"error": f"injected fault: {exc}"}, status=500
            )
        return web.json_response(payload)

    @_faultable("sup.reshard.pre")
    async def _reshard_status(  # wire: produces=reshard
        self, request: web.Request
    ) -> web.Response:
        """Migration observability for this shard: journal head seq,
        pending imports with watermarks, moved-tenant markers, active
        fences (the ``adaptdl-tpu reshard status`` payload)."""

        def build() -> dict:
            info = self._state.reshard_info()
            info["shard"] = self._shard_id
            return info

        return web.json_response(await self._offload(build))

    @_faultable("sup.explain.pre")
    async def _explain(self, request: web.Request) -> web.Response:
        """Decision provenance for one job: the latest allocator-cycle
        explain record (winning allocation, mesh shape, objective
        terms) plus retained history and the cycle's top-k losers."""
        key = "{namespace}/{name}".format(**request.match_info)
        if await self._offload(self._state.get_job, key) is None:
            return web.json_response(
                {"error": "no such job"}, status=404
            )
        payload = await self._offload(
            self._state.watch.explain_for, key
        )
        if payload is None:
            return web.json_response(
                {
                    "error": (
                        "no explain record yet (no allocator cycle "
                        "has covered this job)"
                    )
                },
                status=404,
            )
        return web.json_response(payload)

    # -- graftscope: worker span intake + stitched per-job timeline --

    @staticmethod
    def _valid_span_record(rec) -> bool:
        """Intake-side schema guard: everything downstream float()s
        ``dur``/``ts`` and strings ``name``/``span`` — a poison record
        must bounce here as a 400, not 500 every later GET."""
        return (
            isinstance(rec, dict)
            and isinstance(rec.get("name"), str)
            and bool(rec.get("name"))
            and isinstance(rec.get("dur", 0.0), (int, float))
            and isinstance(rec.get("ts", 0.0), (int, float))
        )

    @_faultable("sup.trace.pre")
    async def _put_trace(  # idempotent: keyed-by=span # wire: consumes=trace_payload,trace_span
        self, request: web.Request
    ) -> web.Response:
        key = "{namespace}/{name}".format(**request.match_info)
        try:
            body = await request.json()
        except ValueError:
            return web.json_response(
                {"error": "body must be JSON"}, status=400
            )
        spans = (body or {}).get("spans")
        if not isinstance(spans, list) or not all(
            self._valid_span_record(rec) for rec in spans
        ):
            return web.json_response(
                {"error": "body must be {\"spans\": [{...}, ...]}"},
                status=400,
            )
        if await self._offload(self._state.get_job, key) is None:
            return web.json_response(
                {"error": "no such job"}, status=404
            )

        def absorb() -> list:
            # Idempotent intake: a worker whose flush response was
            # lost re-sends the same batch — only spans not already in
            # the store are appended and observed, so retries can't
            # double-count histogram durations or duplicate the
            # waterfall.
            with self._trace_lock:
                store = self._trace_store.get(key)
                if store is None:
                    store = deque(maxlen=trace.BUFFER_SIZE)
                    self._trace_store[key] = store
                seen = {rec.get("span") for rec in store}
                fresh = []
                for rec in spans:
                    span_id = rec.get("span")
                    if span_id is not None and span_id in seen:
                        continue
                    seen.add(span_id)
                    fresh.append(rec)
                store.extend(fresh)
            # Fold the worker-side phase durations into THIS process's
            # Prometheus registry: /metrics then covers both halves of
            # a rescale from one scrape point. Spans this very process
            # recorded (an in-process worker flushing to its own
            # supervisor) were observed at record time — absorbing
            # them again would double-count the histograms.
            trace.absorb(
                [rec for rec in fresh if rec.get("pid") != os.getpid()]
            )
            return fresh

        fresh = await self._offload(absorb)
        return web.json_response({"ok": True, "accepted": len(fresh)})

    def _job_trace_spans(  # wire: consumes=trace_span
        self, key: str
    ) -> list[dict]:
        """Worker-posted spans merged with this process's own spans
        for the job, deduplicated by span id (in-process workers flush
        spans the local buffer also holds)."""
        with self._trace_lock:
            store = self._trace_store.get(key)
            merged = list(store) if store else []
        seen = {rec.get("span") for rec in merged}
        local = trace.snapshot_spans()
        # Pass 1: spans explicitly tagged with the job. Pass 2: any
        # span sharing a trace id with the job's spans (the rescale
        # trace stitches supervisor-side spans that carry no job attr).
        tagged = [
            rec
            for rec in local
            if (rec.get("attrs") or {}).get("job") == key
            and rec.get("span") not in seen
        ]
        merged.extend(tagged)
        seen.update(rec.get("span") for rec in tagged)
        trace_ids = {rec.get("trace") for rec in merged}
        record = self._state.get_job(key)
        if record is not None and record.trace_parent:
            parsed = trace.parse_traceparent(record.trace_parent)
            if parsed is not None:
                trace_ids.add(parsed[0])
        merged.extend(
            rec
            for rec in local
            if rec.get("trace") in trace_ids
            and rec.get("span") not in seen
        )
        merged.sort(key=lambda rec: float(rec.get("ts", 0.0)))
        return merged

    @_faultable("sup.trace.get.pre")
    async def _get_trace(  # wire: produces=trace_payload,envelope
        self, request: web.Request
    ) -> web.Response:
        key = "{namespace}/{name}".format(**request.match_info)
        record = await self._offload(self._state.get_job, key)
        if record is None:
            return web.json_response(
                {"error": "no such job"}, status=404
            )
        spans = await self._offload(self._job_trace_spans, key)
        return web.json_response(
            {
                "job": key,
                "traceParent": record.trace_parent,
                "spans": spans,
            }
        )

    @_faultable("sup.metrics.pre")
    async def _metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition (reference exports job counters
        from the controller on :9091, controller.py:35-41; here the
        supervisor serves cluster-visible gauges directly). Built with
        :class:`trace.PromBuilder` so HELP/TYPE coverage and label
        escaping hold for every series by construction. Rendered on
        the executor: the assembly walks every state section under
        _cond and the trace registry locks, and a scrape must not
        stall the loop's heartbeats behind them."""
        return web.Response(
            text=await self._offload(self._metrics_text),
            content_type="text/plain",
        )

    def _metrics_text(self) -> str:
        b = trace.PromBuilder()
        b.family(
            "adaptdl_jobs", "gauge", "Known jobs by lifecycle status."
        )
        b.family(
            "adaptdl_job_replicas",
            "gauge",
            "Chips currently allocated to each job.",
        )
        b.family(
            "adaptdl_job_degraded",
            "gauge",
            "1 while a job runs short-handed after a lease expiry.",
        )
        b.family(
            "adaptdl_job_batch_size",
            "gauge",
            "Initial global batch size from the job's sched hints.",
        )
        b.family(
            "adaptdl_job_retunes_total",
            "counter",
            "Live batch-config re-tunes adopted without a restart.",
        )
        b.family(
            "adaptdl_job_submissions_total",
            "counter",
            "Jobs ever submitted to this cluster.",
        )
        b.family(
            "adaptdl_job_completion_seconds",
            "summary",
            "Time from submission to a terminal status.",
        )
        b.family(
            "adaptdl_alloc_epoch",
            "gauge",
            "Allocation epoch counter (bumped at every prepare).",
        )
        b.family(
            "adaptdl_alloc_pending",
            "gauge",
            "1 while an allocation epoch awaits its commit quorum.",
        )
        b.family(
            "adaptdl_alloc_rollbacks_total",
            "counter",
            "Allocation epochs rolled back at the commit deadline.",
        )
        b.family(
            "adaptdl_slot_strikes",
            "gauge",
            "Consecutive failed-allocation strikes per slot.",
        )
        b.family(
            "adaptdl_slot_quarantined",
            "gauge",
            "1 for slots quarantined away from the allocator.",
        )
        b.family(
            "adaptdl_preemption_notices_total",
            "counter",
            "Reclaim notices observed, by slot kind.",
        )
        b.family(
            "adaptdl_slot_draining",
            "gauge",
            "1 for slots draining under an active reclaim notice.",
        )
        b.family(
            "adaptdl_job_draining",
            "gauge",
            "1 while a job drains after a preemption notice.",
        )
        b.family(
            "adaptdl_hazard_rate",
            "gauge",
            "EWMA reclaim hazard per slot kind (notices per "
            "slot-second).",
        )
        b.family(
            "adaptdl_ckpt_delta_ratio",
            "gauge",
            "Last delta checkpoint's bytes over the last full "
            "snapshot's (from restartStats; 1 until a delta lands).",
        )
        b.family(
            "adaptdl_ckpt_save_bytes",
            "gauge",
            "Serialized bytes of the job's last checkpoint save, by "
            "kind (full vs delta).",
        )
        b.family(
            "adaptdl_handoff_seconds",
            "gauge",
            "Duration of the job's last peer-to-peer state handoff "
            "fetch (successor side).",
        )
        b.family(
            "adaptdl_handoff_bytes",
            "gauge",
            "Bytes transferred in the job's last peer-to-peer state "
            "handoff.",
        )
        b.family(
            "adaptdl_alloc_decide_seconds",
            "histogram",
            "Allocator decision latency per cycle, by mode "
            "(full Pollux search vs incremental dirty-job "
            "re-optimization).",
        )
        b.family(
            "adaptdl_alloc_dirty_jobs",
            "gauge",
            "Dirty jobs consumed by the last allocator cycle.",
        )
        b.family(
            "adaptdl_goodput_measured",
            "gauge",
            "Trainer-measured goodput (useful examples/s) per job, "
            "from the measuredGoodput sched hint.",
        )
        b.family(
            "adaptdl_goodput_predicted",
            "gauge",
            "Model-predicted goodput per job at its PUBLISHED "
            "allocation — what the scheduler believed when it "
            "allocated.",
        )
        b.family(
            "adaptdl_goodput_drift",
            "gauge",
            "Rolling measured/predicted goodput ratio per job "
            "(1 = the fitted model is right; the drift monitor's "
            "signal).",
        )
        b.family(
            "adaptdl_goodput_reprofile_flag",
            "gauge",
            "1 while a job's goodput drift sits outside the "
            "[1/1.25, 1.25] band — the model needs "
            "re-profiling (observability-only signal).",
        )
        b.family(
            "adaptdl_tenant_goodput_share",
            "gauge",
            "Each tenant's share of the cluster's current total "
            "goodput.",
        )
        b.family(
            "adaptdl_tenant_fairness_rho",
            "gauge",
            "Mean finish-time-fairness slowdown per tenant "
            "(requested-ideal goodput over actual; 1 = running at "
            "the ask).",
        )
        b.family(
            "adaptdl_tenant_jobs",
            "gauge",
            "Active jobs per tenant, by whether they hold an "
            "allocation.",
        )
        b.family(
            "adaptdl_tenant_slo_burn_total",
            "counter",
            "Watch samples in which the tenant's fairness rho "
            "exceeded the ADAPTDL_WATCH_SLO_RHO target.",
        )
        b.family(
            "adaptdl_slot_suspect",
            "gauge",
            "Step-time EWMA of the slot's rank over its job's "
            "median — above the straggler factor the slot is "
            "suspect.",
        )
        b.family(
            "adaptdl_cluster_utilization",
            "gauge",
            "Allocated chips over total inventory chips at the last "
            "allocator cycle.",
        )
        b.family(
            "adaptdl_supervisor_recoveries_total",
            "counter",
            "Durable-state recoveries this cluster has performed.",
        )
        b.family(
            "adaptdl_supervisor_recovery_seconds",
            "gauge",
            "Duration of the last snapshot+journal replay.",
        )
        b.family(
            "adaptdl_journal_torn_records_total",
            "counter",
            "Torn journal records dropped during recovery.",
        )
        # graftguard: numeric-health incident/rollback observability.
        b.family(
            "adaptdl_incidents_total",
            "counter",
            "Numeric-health incidents accepted by the supervisor, "
            "by kind (nan_loss/nan_grad/loss_spike).",
        )
        b.family(
            "adaptdl_job_incidents_total",
            "counter",
            "Numeric-health incidents accepted per job.",
        )
        b.family(
            "adaptdl_guard_rollbacks_total",
            "counter",
            "Last-known-good checkpoint rollbacks performed per job "
            "(from the guardStats sched hint).",
        )
        b.family(
            "adaptdl_ckpt_last_good_age_seconds",
            "gauge",
            "Age of the job's newest health-confirmed (good-marked) "
            "checkpoint.",
        )
        b.family(
            "adaptdl_goodput_raw",
            "gauge",
            "Unguarded throughput-EWMA goodput per job — includes "
            "the unhealthy/rolled-back steps the guarded "
            "adaptdl_goodput_measured excludes.",
        )
        lifecycle = self._state.lifecycle_metrics()
        b.sample(
            "adaptdl_job_submissions_total",
            value=lifecycle["submitted_total"],
        )
        for status, (count, total) in sorted(
            lifecycle["completions"].items()
        ):
            b.sample(
                "adaptdl_job_completion_seconds",
                {"status": status},
                count,
                suffix="_count",
            )
            b.sample(
                "adaptdl_job_completion_seconds",
                {"status": status},
                round(total, 3),
                suffix="_sum",
            )
        jobs = self._state.jobs()
        by_status: dict[str, int] = {}
        for record in jobs.values():
            by_status[record.status] = by_status.get(record.status, 0) + 1
        for status, count in sorted(by_status.items()):
            b.sample("adaptdl_jobs", {"status": status}, count)
        for key, record in sorted(jobs.items()):
            labels = {"job": key}
            b.sample(
                "adaptdl_job_replicas", labels, len(record.allocation)
            )
            b.sample(
                "adaptdl_job_retunes_total", labels, record.retunes
            )
            b.sample(
                "adaptdl_job_degraded", labels, int(record.degraded)
            )
            hints = record.hints or {}
            if hints.get("initBatchSize"):
                b.sample(
                    "adaptdl_job_batch_size",
                    labels,
                    hints["initBatchSize"],
                )
            stats = hints.get("restartStats") or {}
            if stats.get("saveBytes") is not None:
                b.sample(
                    "adaptdl_ckpt_save_bytes",
                    {**labels, "kind": stats.get("saveKind", "full")},
                    stats["saveBytes"],
                )
            if stats.get("deltaRatio") is not None:
                b.sample(
                    "adaptdl_ckpt_delta_ratio",
                    labels,
                    stats["deltaRatio"],
                )
            if stats.get("handoffS") is not None:
                b.sample(
                    "adaptdl_handoff_seconds",
                    labels,
                    stats["handoffS"],
                )
                b.sample(
                    "adaptdl_handoff_bytes",
                    labels,
                    stats.get("handoffBytes", 0),
                )
            b.sample("adaptdl_alloc_epoch", labels, record.alloc_epoch)
            b.sample(
                "adaptdl_alloc_pending",
                labels,
                int(record.alloc_state == "pending"),
            )
            b.sample(
                "adaptdl_job_draining", labels, int(record.draining)
            )
        # Transactional-rescale + durable-state observability: the
        # rollback/quarantine gauges the chaos acceptance checks read.
        health = self._state.slot_health()
        for key, count in sorted(health["rollbacks"].items()):
            b.sample(
                "adaptdl_alloc_rollbacks_total", {"job": key}, count
            )
        for slot, count in sorted(health["strikes"].items()):
            b.sample("adaptdl_slot_strikes", {"slot": slot}, count)
        for slot in sorted(health["quarantined"]):
            b.sample("adaptdl_slot_quarantined", {"slot": slot}, 1)
        preempt = self._state.preemption_info()
        for kind, count in sorted(
            preempt["noticesByKind"].items()
        ):
            b.sample(
                "adaptdl_preemption_notices_total",
                {"kind": kind},
                count,
            )
        for slot in sorted(preempt["drainingSlots"]):
            b.sample("adaptdl_slot_draining", {"slot": slot}, 1)
        for kind, rate in sorted(preempt["hazardRates"].items()):
            b.sample(
                "adaptdl_hazard_rate", {"kind": kind}, round(rate, 9)
            )
        incidents = self._state.incident_info()
        for kind, count in sorted(
            incidents["incidentsByKind"].items()
        ):
            b.sample(
                "adaptdl_incidents_total", {"kind": kind}, count
            )
        # Incremental-allocator telemetry: per-mode decision-latency
        # histograms + the last cycle's dirty-job count.
        alloc = self._state.alloc_cycle_metrics()
        for mode in sorted(alloc["modes"]):
            raw = alloc["modes"][mode]
            snap = trace.Histogram(tuple(alloc["buckets"]))
            snap.counts = list(raw["counts"])
            snap.total = raw["sum"]
            snap.count = raw["count"]
            b.histogram(
                "adaptdl_alloc_decide_seconds", {"mode": mode}, snap
            )
        b.sample("adaptdl_alloc_dirty_jobs", value=alloc["last_dirty"])
        # graftwatch: goodput accounting, per-tenant fairness/SLO, the
        # drift monitor's flags, straggler suspects, and cluster
        # utilization — the ROADMAP's multi-tenant observability
        # surface.
        watch = self._state.watch.metrics_view()
        for key, job in sorted(watch["jobs"].items()):
            labels = {"job": key, "tenant": job["tenant"]}
            if job["measured"] is not None:
                b.sample(
                    "adaptdl_goodput_measured", labels, job["measured"]
                )
            if job["predicted"] is not None:
                b.sample(
                    "adaptdl_goodput_predicted",
                    labels,
                    job["predicted"],
                )
            if job["drift"] is not None:
                b.sample(
                    "adaptdl_goodput_drift", labels, job["drift"]
                )
                b.sample(
                    "adaptdl_goodput_reprofile_flag",
                    labels,
                    int(job["reprofile"]),
                )
            if job.get("incidents"):
                b.sample(
                    "adaptdl_job_incidents_total",
                    labels,
                    job["incidents"],
                )
            if job.get("rollbacks"):
                b.sample(
                    "adaptdl_guard_rollbacks_total",
                    labels,
                    job["rollbacks"],
                )
            if job.get("lastGoodAge") is not None:
                b.sample(
                    "adaptdl_ckpt_last_good_age_seconds",
                    labels,
                    job["lastGoodAge"],
                )
            if job.get("rawGoodput") is not None:
                b.sample(
                    "adaptdl_goodput_raw", labels, job["rawGoodput"]
                )
        for tenant, agg in sorted(watch["tenants"].items()):
            labels = {"tenant": tenant}
            if agg.get("share") is not None:
                b.sample(
                    "adaptdl_tenant_goodput_share",
                    labels,
                    agg["share"],
                )
            if agg.get("rho") is not None:
                b.sample(
                    "adaptdl_tenant_fairness_rho", labels, agg["rho"]
                )
            if agg.get("jobs") is not None:
                b.sample(
                    "adaptdl_tenant_jobs",
                    {**labels, "state": "running"},
                    agg.get("running", 0),
                )
                b.sample(
                    "adaptdl_tenant_jobs",
                    {**labels, "state": "queued"},
                    agg["jobs"] - agg.get("running", 0),
                )
            b.sample(
                "adaptdl_tenant_slo_burn_total",
                labels,
                agg.get("burn", 0),
            )
        for slot, suspect in sorted(watch["suspects"].items()):
            b.sample(
                "adaptdl_slot_suspect",
                {"slot": slot, "job": suspect["job"]},
                suspect["ratio"],
            )
        if watch["cluster"] is not None:
            b.sample(
                "adaptdl_cluster_utilization",
                value=watch["cluster"]["utilization"],
            )
        recovery = self._state.recovery_info()
        b.sample(
            "adaptdl_supervisor_recoveries_total",
            value=recovery["recoveries"],
        )
        if recovery["lastRecoveryS"] is not None:
            b.sample(
                "adaptdl_supervisor_recovery_seconds",
                value=round(recovery["lastRecoveryS"], 4),
            )
        b.sample(
            "adaptdl_journal_torn_records_total",
            value=recovery["tornRecords"],
        )
        # graftscope: per-phase latency histograms + event counters
        # (supervisor-side spans recorded locally, worker-side spans
        # absorbed on PUT /trace).
        trace.render_into(b)
        return b.render()

    # -- lifecycle ----------------------------------------------------

    async def _lease_sweeper(self, app: web.Application) -> None:
        """Expire stale worker leases AND overdue allocation epochs on
        a fixed cadence. Skipped entirely only when both clocks are
        disabled (lease TTL 0 and commit timeout 0)."""
        commit_timeout = getattr(
            self._state, "alloc_commit_timeout", 0.0
        )
        if self._lease_ttl <= 0 and commit_timeout <= 0:
            return

        def sweep():
            # Both expirers are journaled mutators (fsync per append)
            # — sweep from the executor so the cadence timer never
            # blocks the loop serving heartbeats.
            expired = (
                self._state.expire_stale_leases()
                if self._lease_ttl > 0
                else []
            )
            rolled = self._state.expire_overdue_allocations()
            return expired, rolled

        try:
            while True:
                await asyncio.sleep(self._sweep_interval)
                try:
                    expired, rolled = await self._offload(sweep)
                except Exception:  # noqa: BLE001 - sweeper must survive
                    LOG.exception("lease/epoch sweep failed")
                    continue
                for key, rank in expired:
                    LOG.warning(
                        "lease expired for %s rank %d: job marked "
                        "degraded, allocation withdrawn for "
                        "re-placement",
                        key, rank,
                    )
                for key in rolled:
                    LOG.warning(
                        "allocation epoch for %s missed its commit "
                        "deadline: rolled back to the last-committed "
                        "allocation, failing slots struck",
                        key,
                    )
        except asyncio.CancelledError:
            pass

    async def _start_sweeper(self, app: web.Application) -> None:
        self._sweeper_task = asyncio.ensure_future(
            self._lease_sweeper(app)
        )

    async def _stop_sweeper(self, app: web.Application) -> None:
        task = getattr(self, "_sweeper_task", None)
        if task is not None:
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass

    @web.middleware
    async def _reshard_gate(self, request, handler):
        """Per-tenant migration gate on every job-scoped route (the
        ones whose path carries ``{namespace}``; the ``/shard/*``
        control plane is structurally exempt). A migrated tenant's
        request — any method, reads included: the jobs left with the
        flip — is answered 409 ``{"error": "moved", "shard",
        "version"}`` so the router re-forwards it exactly once to the
        new owner. A mutation landing inside the live-migration write
        fence is answered 503 with Retry-After: the worker's retrying
        rpc client rides the bounded fence out, and reads keep
        flowing off the still-authoritative source."""
        tenant = request.match_info.get("namespace")
        if tenant is None:
            return await handler(request)
        is_read = request.method == "GET"

        def gate():
            # State reads take _cond (held across journal fsyncs) —
            # off the loop, like every other state access here.
            moved = self._state.moved_owner(tenant)
            if moved is not None:
                return "moved", moved
            if not is_read:
                remaining = self._state.fence_remaining(tenant)
                if remaining > 0:
                    return "fenced", remaining
            return None, None

        verdict, info = await self._offload(gate)
        if verdict == "moved":
            return web.json_response(
                {
                    "error": "moved",
                    "tenant": tenant,
                    "shard": int(info["shard"]),
                    "version": int(info["version"]),
                },
                status=409,
            )
        if verdict == "fenced":
            return web.json_response(
                {"error": "fenced", "tenant": tenant},
                status=503,
                headers={"Retry-After": f"{max(info, 0.05):.3f}"},
            )
        return await handler(request)

    @web.middleware
    async def _time_endpoint(self, request, handler):
        """Server-side per-endpoint latency histogram
        (``adaptdl_trace_phase_seconds{phase="sup.endpoint.<seg>"}``)
        — the signal the per-shard Grafana endpoint-p99 panel rates
        once the router relabels it with ``shard``. Keyed by the
        first path segment so cardinality stays at the route count."""
        start = time.monotonic()
        try:
            return await handler(request)
        finally:
            parts = request.path.split("/", 2)
            segment = parts[1] if len(parts) > 1 and parts[1] else "root"
            # record_span journals the span (file IO under the trace
            # journal lock) when ADAPTDL_TRACE_DIR is set — off
            # the loop, like every other blocking call here.
            await self._offload(
                trace.record_span,
                f"sup.endpoint.{segment}",
                time.monotonic() - start,
            )

    def build_app(self) -> web.Application:
        app = web.Application(
            middlewares=[self._time_endpoint, self._reshard_gate],
            # Snapshot-mode reshard imports carry a whole tenant's job
            # table in one body; aiohttp's 1 MiB default 413s any
            # real-sized tenant mid-migration.
            client_max_size=64 * 1024 * 1024,
        )
        app.add_routes(
            [
                web.get(
                    "/discover/{namespace}/{name}/{group}", self._discover
                ),
                web.put(
                    "/register/{namespace}/{name}/{group}/{rank}",
                    self._register,
                ),
                web.put(
                    "/heartbeat/{namespace}/{name}/{rank}",
                    self._heartbeat,
                ),
                web.put("/hints/{namespace}/{name}", self._put_hints),
                web.get("/hints/{namespace}/{name}", self._get_hints),
                web.get("/config/{namespace}/{name}", self._get_config),
                web.put("/trace/{namespace}/{name}", self._put_trace),
                web.get("/trace/{namespace}/{name}", self._get_trace),
                web.post(
                    "/preempt/{namespace}/{name}", self._preempt
                ),
                web.post(
                    "/incident/{namespace}/{name}", self._incident
                ),
                web.put(
                    "/handoff/{namespace}/{name}", self._put_handoff
                ),
                web.get(
                    "/handoff/{namespace}/{name}", self._get_handoff
                ),
                web.get(
                    "/candidate/{namespace}/{name}",
                    self._get_candidate,
                ),
                web.get("/healthz", self._healthz),
                web.get("/status", self._status),
                web.get("/watch", self._watch),
                web.get("/shard/inventory", self._shard_inventory),
                web.get(
                    "/shard/stream/{tenant}", self._reshard_stream
                ),
                web.post(
                    "/shard/reshard/import/{tenant}",
                    self._reshard_import,
                ),
                web.post(
                    "/shard/reshard/fence/{tenant}",
                    self._reshard_fence,
                ),
                web.post(
                    "/shard/reshard/commit/{tenant}",
                    self._reshard_commit,
                ),
                web.post(
                    "/shard/reshard/abort/{tenant}",
                    self._reshard_abort,
                ),
                web.get(
                    "/shard/reshard/status", self._reshard_status
                ),
                web.get(
                    "/explain/{namespace}/{name}", self._explain
                ),
                web.get("/metrics", self._metrics),
            ]
        )
        app.on_startup.append(self._start_sweeper)
        app.on_cleanup.append(self._stop_sweeper)
        return app

