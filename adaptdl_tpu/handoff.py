"""Peer-to-peer state handoff for planned rescales.

A planned rescale (the runner's SIGTERM → save → exit-143 → relaunch
cycle) round-trips the full training state through checkpoint storage
even though the predecessor held every byte in memory moments before
the successor asks for it. This module closes that loop: during the
prepare→commit allocation epoch the doomed incarnation serves its
in-memory snapshot chunks over a small HTTP *shard server*, and the
successor pulls exactly the chunks its registered states need —
range-addressed by ``(state, chunk)``, each chunk sha256-verified —
skipping the storage round-trip entirely. Any failure (peer death,
timeout, hash mismatch, injected fault) makes ``try_restore`` return
False and ``checkpoint.load_state`` falls back to the durable
checkpoint with zero correctness loss: the served chunks are snapshot
at drain time *after* the final blocking save, so peer and storage
hold the same version.

Server side (doomed incarnation):

- :func:`collect_chunks` snapshots every registered ``State`` into
  named chunks — per-leaf for chunk-capable states
  (``State.snapshot_chunks``), one opaque ``__payload__`` blob for the
  rest — so the successor can fetch at whatever granularity its new
  sharding needs (a re-sharding successor re-materializes leaves onto
  its own mesh exactly as the storage restore path does).
- :class:`HandoffServer` serves ``GET /manifest`` (chunk orders +
  sha256 tables), ``GET /chunk/{state}/{chunk}`` (raw bytes), and
  ``POST /done`` (the successor's "got everything" signal).
- :func:`spawn_server` forks the server into a *detached child
  process* holding only host bytes, so it survives the doomed
  process's exit-143 (the runner relaunches only after that exit).
  The child writes a discovery descriptor beside the checkpoints,
  advertises itself to the supervisor (``PUT /handoff/{job}``), and
  exits after the successor's ``/done`` or a TTL.

Client side (successor): discovery goes explicit URL
(``ADAPTDL_HANDOFF_URL`` / :func:`set_source`) → supervisor
(``GET /handoff/{job}``) → descriptor file; all fetches ride the
resilient rpc client with an overall deadline
(``FETCH_TIMEOUT_S``). Measured transfer time and bytes
feed ``metrics.record_handoff`` and ride ``restartStats`` so Pollux
prices planned rescales at their new, storage-free cost.

Reshard-aware range pulls: large leaf chunks are additionally
advertised in ``RANGE_PARTS`` row parts (per-part sha256 in
the manifest, served as ``GET /chunk/{state}/{leaf}@p{i}`` by
re-slicing the whole-leaf bytes on demand). A successor state that
declares a shard map (``State.handoff_shard_plan``; see
:func:`fraction_plan`) pulls only the parts covering ITS row spans of
each leaf instead of bulk-fetching full leaves — a resharding
(dp, tp)-change successor's handoff bytes ~ its shard fraction of the
state. The manifest also carries the writer's mesh shape
(:func:`peer_topology`) so a successor can see it is resharding.
"""

from __future__ import annotations

import io
import json
import logging
import os
import pickle
import subprocess
import sys
import threading
import time
from typing import Any

from aiohttp import web

from adaptdl_tpu import checkpoint, env, faults, rpc, trace
from adaptdl_tpu.sched.http_server import (
    ThreadedHttpServer,
    faultable as _faultable,
)

LOG = logging.getLogger(__name__)

# Chunk id for states that don't implement snapshot_chunks: the whole
# write_snapshot byte stream as one opaque blob, applied via
# State.load on the successor.
RAW_CHUNK = "__payload__"

# Sentinel recorded in checkpoint._loaded_from for handoff-sourced
# restores (never equal to any on-disk dir, so dir poisoning can't
# try to "re-load" a peer-sourced state from storage mid-fallback).
HANDOFF_SOURCE = "<handoff>"

DESCRIPTOR_NAME = ".handoff.json"

# Seconds the spawned shard server lingers for the successor before
# giving up (the durable checkpoint then serves the restore).
SERVER_TTL_S = 60.0

# Overall deadline of the successor's fetch (manifest + chunks); past
# it the restore falls back to the durable checkpoint rather than
# stall the restart on a dead or slow peer.
FETCH_TIMEOUT_S = 10.0

# Row parts a large leaf chunk is range-addressable in, and the size
# under which a leaf is never split: a part's round-trip would cost
# more than the bytes it saves.
RANGE_PARTS = 8
RANGE_PART_MIN_BYTES = 65536


def _descriptor_path(root: str | None = None) -> str | None:
    root = root if root is not None else env.checkpoint_path()
    if not root:
        return None
    return os.path.join(root, DESCRIPTOR_NAME)


# ---- server side -----------------------------------------------------


def _part_bytes(arr, lo: int, hi: int) -> bytes:
    """Serialized row range ``arr[lo:hi]`` — ONE definition shared by
    the collect-time sha table and the serve-time slicing, so the
    bytes a part endpoint returns always hash to what the manifest
    promised (pickle of the same contiguous slice is deterministic
    within one interpreter)."""
    import numpy as np

    return pickle.dumps(np.ascontiguousarray(arr[lo:hi]))


def _partition_chunk(  # wire: produces=handoff_manifest
    data: bytes, max_parts: int, min_bytes: int
) -> dict | None:
    """Row-part metadata for one chunk payload, or None when the
    chunk is not worth (or not capable of) range addressing: too
    small, not a pickled ndarray, or fewer leading-axis rows than
    two. ``bounds`` are the balanced part boundaries; per-part sha256
    and byte counts let the client verify each range pull exactly
    like a whole-chunk fetch."""
    if max_parts <= 1 or len(data) < max(min_bytes, 1):
        return None
    import numpy as np

    try:
        value = pickle.loads(data)
    except Exception:  # noqa: BLE001 - opaque chunk: serve whole
        return None
    if not isinstance(value, np.ndarray) or value.ndim < 1:
        return None
    rows = int(value.shape[0])
    if rows < 2:
        return None
    k = min(int(max_parts), rows)
    bounds = [(i * rows) // k for i in range(k + 1)]
    sha: dict[str, str] = {}
    nbytes: dict[str, int] = {}
    for i in range(k):
        part = _part_bytes(value, bounds[i], bounds[i + 1])
        sha[str(i)] = checkpoint._chunk_sha(part)
        nbytes[str(i)] = len(part)
    return {"rows": rows, "bounds": bounds, "sha": sha, "bytes": nbytes}


def collect_chunks(  # wire: produces=handoff_manifest
    states=None, snapshots=None
) -> dict[str, dict]:
    """Snapshot every registered state into its handoff chunk set:
    ``{name: {"order": [ids], "chunks": {id: bytes}, "sha": {id:
    hex}}}``. Chunk-capable states chunk per-leaf (their
    ``snapshot_chunks``); the rest contribute one ``__payload__``
    blob. Runs on the caller's thread — at drain time that is the
    main thread, after the final blocking save, so the served bytes
    equal the durable checkpoint's. ``snapshots`` (``{name:
    snapshot}``, e.g. ``AsyncSaveHandle.snapshots`` from a
    ``retain_snapshots=True`` save) reuses already-captured host
    copies instead of paying a second device->host pass."""
    if states is None:
        states = list(checkpoint._registry.values())
    payload: dict[str, dict] = {}
    for state in states:
        if snapshots is not None and state.name in snapshots:
            snap = snapshots[state.name]
        else:
            snap = state.snapshot()
        chunks = state.snapshot_chunks(snap)
        if chunks is None:
            buf = io.BytesIO()
            state.write_snapshot(snap, buf)
            chunks = [(RAW_CHUNK, buf.getvalue())]
        payload[state.name] = {
            "order": [cid for cid, _ in chunks],
            "chunks": dict(chunks),
            "sha": {
                cid: checkpoint._chunk_sha(data)
                for cid, data in chunks
            },
        }
    return payload


def attach_parts(  # wire: produces=handoff_manifest # wire: consumes=handoff_manifest
    payload: dict[str, dict]
) -> dict[str, dict]:
    """Attach range-addressing part metadata to a collected payload:
    big ndarray chunks advertise row parts so a resharding successor
    can pull only ITS slices of each leaf. Runs in the SERVER
    (HandoffServer construction — for a planned rescale that is the
    detached child, which idles waiting for the successor), never on
    the doomed incarnation's drain-critical collect path: the
    re-pickle + sha pass over every large leaf must not race the
    preemption notice. Only metadata is retained — part bytes are
    re-sliced from the whole-leaf payload at serve time, so server
    memory stays one copy of the state."""
    for entry in payload.values():
        if "parts" in entry:
            continue
        parts: dict[str, dict] = {}
        for cid in entry["order"]:
            meta = _partition_chunk(
                entry["chunks"][cid], RANGE_PARTS, RANGE_PART_MIN_BYTES
            )
            if meta is not None:
                parts[cid] = meta
        if parts:
            entry["parts"] = parts
    return payload


class HandoffServer(ThreadedHttpServer):
    """The doomed incarnation's shard server: an immutable chunk
    payload behind three tiny endpoints. The payload dict is built
    before ``start()`` and never mutated, so handlers read it without
    locks."""

    def __init__(
        self, payload: dict[str, dict], group: int | None = None,
        host: str = "127.0.0.1", port: int = 0,
        topology: list | None = None,
    ):
        super().__init__(host=host, port=port)
        self._payload = attach_parts(payload)
        self._group = (
            env.num_restarts() if group is None else int(group)
        )
        # The WRITER's mesh shape: computed where the state lived
        # (the doomed incarnation's active topology) and carried into
        # the detached child, which has no trainer of its own.
        self._topology = (
            checkpoint.writer_topology()
            if topology is None
            else list(topology)
        )
        self.done = threading.Event()

    @property
    def group(self) -> int:
        return self._group

    @_faultable("handoff.serve")
    async def _manifest(  # wire: produces=handoff_manifest
        self, request: web.Request
    ) -> web.Response:
        states = {}
        for name, entry in self._payload.items():
            desc = {
                "order": entry["order"],
                "sha": entry["sha"],
                "bytes": {
                    cid: len(entry["chunks"][cid])
                    for cid in entry["order"]
                },
            }
            if entry.get("parts"):
                desc["parts"] = entry["parts"]
            states[name] = desc
        return web.json_response(
            {
                "group": self._group,
                # The predecessor's mesh shape [dp, sp, tp, ss, ep]:
                # a successor compares it with its own to see it is
                # resharding (and dashboards see what shape served).
                "topology": self._topology,
                "states": states,
            }
        )

    @_faultable("handoff.serve")
    async def _chunk(self, request: web.Request) -> web.Response:
        """Range endpoint: ``{chunk}`` addresses a whole chunk, or a
        row part ``{chunk}@p{i}`` of one — the unit a resharding
        successor pulls per its shard map. Part bytes are re-sliced
        from the whole-leaf payload on demand (one state copy in
        memory; the slice+pickle runs only for ranges actually
        requested)."""
        entry = self._payload.get(request.match_info["state"])
        if entry is None:
            return web.json_response(
                {"error": "no such state"}, status=404
            )
        chunk_id = request.match_info["chunk"]
        data = entry["chunks"].get(chunk_id)
        if data is None and "@p" in chunk_id:
            cid, _, index = chunk_id.rpartition("@p")
            meta = (entry.get("parts") or {}).get(cid)
            whole = entry["chunks"].get(cid)
            if meta is not None and whole is not None:
                try:
                    i = int(index)
                    bounds = meta["bounds"]
                    if 0 <= i < len(bounds) - 1:
                        data = _part_bytes(
                            pickle.loads(whole),
                            bounds[i],
                            bounds[i + 1],
                        )
                except Exception:  # noqa: BLE001 - malformed part id
                    data = None
        if data is None:
            return web.json_response(
                {"error": "no such chunk"}, status=404
            )
        return web.Response(
            body=data, content_type="application/octet-stream"
        )

    @_faultable("handoff.serve")
    async def _state(self, request: web.Request) -> web.Response:
        """Bulk form: one state's whole chunk container in a single
        response — the successor's default when it needs every chunk
        (pure data parallelism), saving a per-chunk round-trip per
        pytree leaf; the range-addressed ``/chunk`` endpoint remains
        for partial pulls."""
        entry = self._payload.get(request.match_info["state"])
        if entry is None:
            return web.json_response(
                {"error": "no such state"}, status=404
            )
        return web.Response(
            body=pickle.dumps(
                {"order": entry["order"], "chunks": entry["chunks"]}
            ),
            content_type="application/octet-stream",
        )

    @_faultable("handoff.serve")
    async def _done(  # idempotent
        self, request: web.Request
    ) -> web.Response:
        self.done.set()
        return web.json_response({"ok": True})

    def build_app(self) -> web.Application:
        app = web.Application()
        app.add_routes(
            [
                web.get("/manifest", self._manifest),
                web.get("/state/{state}", self._state),
                web.get("/chunk/{state}/{chunk:.+}", self._chunk),
                web.post("/done", self._done),
            ]
        )
        return app


def serve_states(
    group: int | None = None, states=None, host: str = "127.0.0.1"
) -> HandoffServer:
    """Collect chunks from the registered states and serve them
    in-process (bench, tests, and the spawned child all build on
    this). Returns the started server; ``server.url`` is the base."""
    server = HandoffServer(
        collect_chunks(states), group=group, host=host
    )
    server.start()
    return server


def _advertise(url: str, group: int) -> None:  # wire: produces=handoff_ad
    """Best-effort advertisement of the shard server: the discovery
    descriptor beside the checkpoints, and the supervisor's
    ``PUT /handoff/{job}`` so a successor on another host finds the
    peer through the control plane during the allocation epoch."""
    descriptor = _descriptor_path()
    if descriptor:
        try:
            tmp = descriptor + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(
                    {"url": url, "group": group, "ts": time.time()}, f
                )
            os.replace(tmp, descriptor)
        except OSError:
            LOG.warning(
                "could not write handoff descriptor", exc_info=True
            )
    sup = env.supervisor_url()
    job = env.job_id()
    if sup and job:
        try:
            rpc.default_client().put(
                f"{sup}/handoff/{job}",
                endpoint=f"handoff/{job}",
                json={"url": url, "group": group},
                timeout=(2, 5),
                attempts=2,
                deadline=5.0,
                use_circuit=False,
            )
        except Exception:  # noqa: BLE001 - advertisement best-effort
            LOG.warning(
                "could not advertise handoff to the supervisor",
                exc_info=True,
            )


def withdraw_descriptor(root: str | None = None) -> None:
    """Remove the discovery descriptor (the spawned server's own
    wind-down, and the runners' stale-descriptor cleanup after a
    non-graceful worker death)."""
    descriptor = _descriptor_path(root)
    if descriptor:
        try:
            os.remove(descriptor)
        except OSError:
            pass


def spawn_server(  # wire: produces=handoff_payload
    states=None, snapshots=None
) -> "subprocess.Popen | None":
    """Fork the shard server into a detached child so it outlives
    this (doomed) process's exit-143: the child inherits only the
    pickled chunk payload over stdin — no devices, no jax — serves
    until the successor's ``/done`` or ``SERVER_TTL_S``,
    then withdraws its descriptor and exits. Rank 0 only (mirroring
    the save pipeline's writer — one peer per job, and the served
    bytes must be the same rank's view the durable checkpoint
    holds). ``snapshots`` reuses a retained save's host copies (see
    :func:`collect_chunks`). Returns the Popen (the caller never
    waits on it) or None when handoff is disabled, this is not rank
    0, or nothing is registered. Memory note: the chunk payload is
    one serialized copy of the registered states, held in this
    process only for the moments between collection and the exit-143
    that follows; the detached child's copy is the single serving
    copy."""
    if not env.handoff_enabled() or env.replica_rank() != 0:
        return None
    try:
        payload = collect_chunks(states, snapshots=snapshots)
    except Exception:  # noqa: BLE001 - handoff is an optimization
        LOG.warning(
            "handoff snapshot failed; planned rescale falls back to "
            "the durable checkpoint",
            exc_info=True,
        )
        return None
    if not payload:
        return None
    try:
        proc = subprocess.Popen(  # detached: handoff-child-server
            [sys.executable, "-m", "adaptdl_tpu.handoff"],
            stdin=subprocess.PIPE,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
            start_new_session=True,
        )
        pickle.dump(
            {
                "group": env.num_restarts(),
                "topology": checkpoint.writer_topology(),
                "states": payload,
            },
            proc.stdin,
        )
        proc.stdin.close()
    except Exception:  # noqa: BLE001 - handoff is an optimization
        LOG.warning("could not spawn handoff server", exc_info=True)
        return None
    LOG.info(
        "handoff shard server spawned (pid %d, %d states)",
        proc.pid, len(payload),
    )
    return proc


def _serve_main() -> int:  # wire: consumes=handoff_payload
    """Entry point of the spawned child: read the payload, serve,
    advertise, linger until fetched or TTL. In cluster mode (a
    supervisor is configured, so the successor may land on another
    host) the server binds all interfaces and advertises this host's
    routable address; standalone it stays on loopback."""
    import socket

    payload = pickle.load(sys.stdin.buffer)
    cluster = bool(env.supervisor_url())
    server = HandoffServer(
        payload["states"],
        group=int(payload["group"]),
        host="0.0.0.0" if cluster else "127.0.0.1",
        topology=payload.get("topology"),
    )
    server.start()
    advertise_url = server.url
    if cluster:
        try:
            address = socket.gethostbyname(socket.gethostname())
        except OSError:
            address = "127.0.0.1"
        advertise_url = f"http://{address}:{server._port}"
    _advertise(advertise_url, server.group)
    try:
        server.done.wait(SERVER_TTL_S)
        if server.done.is_set():
            # Grace for trailing chunk fetches racing the /done post.
            time.sleep(0.2)
    finally:
        withdraw_descriptor()
        server.stop()
    return 0


# ---- client side -----------------------------------------------------

# Successor-side fetch state. Discovery + manifest fetch may race
# between the restore path and bootstrap's prefetch thread, so both
# go through _ensure_manifest under _manifest_lock; chunk fetch and
# apply stay on the restore thread.
_manifest_lock = threading.Lock()
_source_url: str | None = None  # guarded-by: _manifest_lock
_manifest: dict | None = None  # guarded-by: _manifest_lock
_manifest_url: str | None = None  # guarded-by: _manifest_lock
_peer_topology: list | None = None  # guarded-by: _manifest_lock
_unavailable = False  # guarded-by: _manifest_lock (sticky failure)
_fetch_stats = {"bytes": 0, "seconds": 0.0, "reused": 0}
_states_applied: set[str] = set()
# Speculative warm-up chunk cache: ``{state: {chunk_id: (sha, bytes)}}``
# filled by ``warm_prefetch`` BEFORE the incumbent's final drain. The
# restore path reuses a cached chunk only when its sha still matches
# the (final) manifest — so the differential pull moves exactly the
# chunks that changed between prefetch and drain, and a stale or
# mispredicted cache degrades to the full pull bit-identically.
_warm_cache: dict[str, dict[str, tuple[str, bytes]]] = {}  # guarded-by: _manifest_lock


def _reset_client_state() -> None:
    """Forget fetched manifests, caches, and the sticky-unavailable
    verdict (test isolation; checkpoint._reset_registry calls it)."""
    global _source_url, _manifest, _manifest_url, _unavailable
    global _peer_topology
    with _manifest_lock:
        _source_url = None
        _manifest = None
        _manifest_url = None
        _peer_topology = None
        _unavailable = False
        _warm_cache.clear()
    _fetch_stats["bytes"] = 0
    _fetch_stats["seconds"] = 0.0
    _fetch_stats["reused"] = 0
    _states_applied.clear()


def _warm_chunks(name: str, sha_table: dict) -> dict[str, bytes]:
    """The warm-cache chunks for ``name`` whose content hash still
    matches the authoritative manifest's — exactly the chunks a
    differential pull may skip. Empty when differential pulls are
    disabled or nothing was prefetched."""
    if not env.handoff_diff_enabled():
        return {}
    with _manifest_lock:
        cached = _warm_cache.get(name)
        if not cached:
            return {}
        return {
            cid: data
            for cid, (sha, data) in cached.items()
            if sha is not None and sha == sha_table.get(cid)
        }


def peer_topology() -> list | None:
    """The predecessor's mesh shape ``[dp, sp, tp, ss, ep]`` as its
    shard server advertised it, or None before a manifest was
    fetched (or from a pre-mesh-key peer). A successor whose own
    ``checkpoint.writer_topology()`` differs is resharding — its
    states' shard plans decide what fraction of each leaf to pull."""
    with _manifest_lock:
        return list(_peer_topology) if _peer_topology else None


def set_source(url: str | None) -> None:
    """Point the restore path at a known shard server (bench and
    tests; production discovery is env → supervisor → descriptor)."""
    global _source_url, _unavailable
    with _manifest_lock:
        _source_url = url
        _unavailable = False


def _advertised_group(body) -> int | None:
    try:
        return int(body.get("group"))
    except (TypeError, ValueError, AttributeError):
        return None


def discover_url() -> str | None:  # wire: consumes=handoff_ad
    """Where the predecessor's shard server lives, if anywhere:
    explicit override (``set_source`` / ``ADAPTDL_HANDOFF_URL``),
    then the supervisor's advertisement, then the descriptor file
    beside the checkpoints. Supervisor/descriptor sources must report
    EXACTLY this incarnation's immediate predecessor (group ==
    num_restarts - 1): anything older is some earlier epoch's
    leftover whose state may predate newer durable checkpoints — a
    crash between that drain and this launch must never roll
    training back to it."""
    with _manifest_lock:
        if _source_url:
            return _source_url
    if not env.handoff_enabled():
        return None
    override = env.handoff_url()
    if override:
        return override
    predecessor = env.num_restarts() - 1
    sup = env.supervisor_url()
    job = env.job_id()
    if sup and job:
        try:
            response = rpc.default_client().get(
                f"{sup}/handoff/{job}",
                endpoint=f"handoff/{job}",
                timeout=(2, 5),
                attempts=2,
                deadline=5.0,
                use_circuit=False,
            )
            if response.status_code == 200:
                body = response.json()
                if (
                    isinstance(body, dict)
                    and body.get("url")
                    and _advertised_group(body) == predecessor
                ):
                    return body["url"]
        except Exception:  # noqa: BLE001 - discovery best-effort
            LOG.debug("supervisor handoff discovery failed", exc_info=True)
    descriptor = _descriptor_path()
    if descriptor and os.path.isfile(descriptor):
        try:
            with open(descriptor, encoding="utf-8") as f:
                body = json.load(f)
            if (
                isinstance(body, dict)
                and body.get("url")
                and _advertised_group(body) == predecessor
            ):
                return body["url"]
        except (OSError, ValueError):
            LOG.debug("unreadable handoff descriptor", exc_info=True)
    return None


def _fetch_manifest(  # wire: consumes=handoff_manifest
    url: str, deadline_s: float
) -> tuple[dict, list | None] | None:
    response = rpc.default_client().get(
        f"{url}/manifest",
        endpoint="handoff/manifest",
        timeout=(2, deadline_s),
        attempts=2,
        deadline=deadline_s,
        use_circuit=False,
    )
    if response.status_code != 200:
        return None
    body = response.json()
    states = body.get("states")
    if not isinstance(states, dict):
        return None
    topology = body.get("topology")
    return states, topology if isinstance(topology, list) else None


def _fetch_state_chunks(  # wire: consumes=handoff_manifest
    url: str, name: str, entry: dict, deadline: float
) -> tuple[list[tuple[str, bytes]], int, int]:
    """Pull one state's chunks, sha256-verifying each against the
    manifest table; returns ``(chunks, fetched_bytes, reused_bytes)``.
    Chunks whose content hash already sits in the warm-up cache are
    reused without touching the network (the differential pull); when
    nothing is cached the bulk ``/state`` form is tried first (one
    round-trip for the whole container — the full-pull common case),
    then per-chunk ``/chunk`` fetches. Raises on any mismatch,
    timeout, or server error — the caller treats every raise as
    "fall back to storage"."""
    client = rpc.default_client()
    sha_table = entry.get("sha") or {}
    cached = _warm_chunks(name, sha_table)
    reused = 0
    if not cached:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise TimeoutError("handoff fetch deadline exceeded")
        faults.maybe_fail("handoff.fetch")
        try:
            response = client.get(
                f"{url}/state/{name}",
                endpoint=f"handoff/state/{name}",
                timeout=(2, max(remaining, 0.1)),
                attempts=2,
                deadline=remaining,
                use_circuit=False,
            )
        except rpc.RpcError:
            response = None  # try the per-chunk form below
        if response is not None and response.status_code == 200:
            container = pickle.loads(response.content)
            chunks = container.get("chunks") or {}
            assembled = []
            for cid in entry["order"]:
                data = chunks.get(cid)
                if data is None:
                    raise RuntimeError(
                        f"handoff bulk fetch of {name} is missing "
                        f"chunk {cid!r}"
                    )
                if checkpoint._chunk_sha(data) != sha_table.get(cid):
                    raise ValueError(
                        f"handoff chunk {name}/{cid} failed sha256"
                    )
                assembled.append((cid, data))
            nbytes = sum(len(data) for _, data in assembled)
            return assembled, nbytes, 0
    # Differential (or bulk-unavailable) path: verified cache hits
    # cost zero wire bytes; only the changed chunks are fetched.
    assembled = []
    nbytes = 0
    for cid in entry["order"]:
        data = cached.get(cid)
        if data is not None:
            reused += len(data)
            assembled.append((cid, data))
            continue
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise TimeoutError("handoff fetch deadline exceeded")
        faults.maybe_fail("handoff.fetch")
        response = client.get(
            f"{url}/chunk/{name}/{cid}",
            endpoint=f"handoff/chunk/{name}",
            timeout=(2, max(remaining, 0.1)),
            attempts=2,
            deadline=remaining,
            use_circuit=False,
        )
        if response.status_code != 200:
            raise RuntimeError(
                f"handoff chunk {name}/{cid} returned "
                f"{response.status_code}"
            )
        data = response.content
        if checkpoint._chunk_sha(data) != sha_table.get(cid):
            raise ValueError(
                f"handoff chunk {name}/{cid} failed sha256"
            )
        nbytes += len(data)
        assembled.append((cid, data))
    return assembled, nbytes, reused


def _fetch_chunk(
    client, url: str, name: str, chunk_id: str, deadline: float
) -> bytes:
    """One range-endpoint GET with the shared deadline/fault plumbing;
    raises on any non-200 (the caller falls back to storage)."""
    remaining = deadline - time.monotonic()
    if remaining <= 0:
        raise TimeoutError("handoff fetch deadline exceeded")
    faults.maybe_fail("handoff.fetch")
    response = client.get(
        f"{url}/chunk/{name}/{chunk_id}",
        endpoint=f"handoff/chunk/{name}",
        timeout=(2, max(remaining, 0.1)),
        attempts=2,
        deadline=remaining,
        use_circuit=False,
    )
    if response.status_code != 200:
        raise RuntimeError(
            f"handoff chunk {name}/{chunk_id} returned "
            f"{response.status_code}"
        )
    return response.content


def _normalize_plan(  # wire: consumes=handoff_manifest
    plan: dict, parts_meta: dict
) -> dict:
    """Sanitize a state's shard plan: only chunks the peer actually
    advertises parts for, spans clamped to the row count, and only
    STRICT subsets kept — a full-span (or degenerate) request is
    cheaper as a whole-chunk fetch."""
    normalized = {}
    for cid, span in (plan or {}).items():
        meta = parts_meta.get(cid)
        if meta is None:
            continue
        try:
            lo, hi = int(span[0]), int(span[1])
        except (TypeError, ValueError, IndexError):
            continue
        rows = int(meta["rows"])
        lo, hi = max(lo, 0), min(hi, rows)
        if lo >= hi or (lo == 0 and hi == rows):
            continue
        normalized[cid] = (lo, hi)
    return normalized


def _fetch_state_ranges(  # wire: consumes=handoff_manifest
    url: str, name: str, entry: dict, plan: dict, deadline: float
) -> tuple[list, list, int, int]:
    """The shard-map-keyed pull: chunks in ``plan`` are fetched as
    the row PARTS covering the requested span (each part
    sha256-verified against the manifest's per-part table, then
    concatenated); every other chunk is fetched whole. Returns
    ``(whole_chunks, partial, nbytes, reused)`` where ``partial``
    entries are ``(chunk_id, cover_lo, cover_hi, total_rows,
    ndarray)`` — the covering range is part-aligned, so it may extend
    slightly past the plan's span — and ``reused`` counts bytes
    satisfied from the warm-up cache instead of the wire (a verified
    cache hit beats even a range pull: zero round-trips).
    Raises on any mismatch/timeout/server error (caller falls back to
    storage)."""
    import numpy as np

    client = rpc.default_client()
    sha_table = entry.get("sha") or {}
    parts_meta = entry.get("parts") or {}
    cached = _warm_chunks(name, sha_table)
    whole: list[tuple[str, bytes]] = []
    partial: list[tuple[str, int, int, Any]] = []
    nbytes = 0
    reused = 0
    for cid in entry["order"]:
        data = cached.get(cid)
        if data is not None:
            reused += len(data)
            whole.append((cid, data))
            continue
        span = plan.get(cid)
        if span is None:
            data = _fetch_chunk(client, url, name, cid, deadline)
            if checkpoint._chunk_sha(data) != sha_table.get(cid):
                raise ValueError(
                    f"handoff chunk {name}/{cid} failed sha256"
                )
            nbytes += len(data)
            whole.append((cid, data))
            continue
        meta = parts_meta[cid]
        bounds = meta["bounds"]
        part_sha = meta.get("sha") or {}
        lo, hi = span
        picked = [
            i
            for i in range(len(bounds) - 1)
            if bounds[i + 1] > lo and bounds[i] < hi
        ]
        pieces = []
        for i in picked:
            data = _fetch_chunk(
                client, url, name, f"{cid}@p{i}", deadline
            )
            if checkpoint._chunk_sha(data) != part_sha.get(str(i)):
                raise ValueError(
                    f"handoff part {name}/{cid}@p{i} failed sha256"
                )
            nbytes += len(data)
            pieces.append(pickle.loads(data))
        cover_lo, cover_hi = bounds[picked[0]], bounds[picked[-1] + 1]
        partial.append(
            (
                cid,
                cover_lo,
                cover_hi,
                int(meta["rows"]),
                np.concatenate(pieces, axis=0),
            )
        )
    return whole, partial, nbytes, reused


def _signal_done(url: str) -> None:
    try:
        rpc.default_client().post(
            f"{url}/done",
            endpoint="handoff/done",
            timeout=(2, 2),
            attempts=1,
            use_circuit=False,
        )
    except Exception:  # noqa: BLE001 - courtesy signal only
        pass


def _ensure_manifest() -> tuple[dict, str] | None:
    """Discover the peer and fetch its manifest once (idempotent,
    thread-safe — bootstrap's prefetch thread and the restore path
    both land here). None when no peer is configured/reachable; the
    failure verdict is sticky."""
    global _manifest, _manifest_url, _unavailable, _peer_topology
    with _manifest_lock:
        if _unavailable:
            return None
        if _manifest is not None:
            return _manifest, _manifest_url
    # Discovery and the manifest RPC run outside the lock (they can
    # block for seconds); the verdict is committed under it.
    url = discover_url()
    if url is None:
        # Sticky: with no peer discoverable, later states' restores
        # must not re-pay the supervisor RPC + descriptor probe each
        # (set_source re-arms for tests/bench).
        with _manifest_lock:
            _unavailable = True
        return None
    deadline_s = FETCH_TIMEOUT_S
    t0 = time.monotonic()
    try:
        fetched = _fetch_manifest(url, deadline_s)
    except Exception:  # noqa: BLE001 - peer gone -> storage
        LOG.info(
            "handoff peer at %s unreachable; using the durable "
            "checkpoint", url,
        )
        fetched = None
    with _manifest_lock:
        if fetched is None:
            _unavailable = True
            return None
        if _manifest is None:
            _manifest, _peer_topology = fetched
            _manifest_url = url
            _fetch_stats["seconds"] += time.monotonic() - t0
        return _manifest, _manifest_url


def fraction_plan(
    chunk_rows: dict, shard: int, num_shards: int
) -> dict:
    """The balanced shard map for shard ``shard`` of ``num_shards``:
    for every range-addressable chunk, the row span
    ``[shard * rows // num_shards, (shard + 1) * rows // num_shards)``
    — the slice a successor process owning that fraction of each leaf
    needs. The canonical ``shard_plan_fn`` for launchers whose
    resharded successors split leaves evenly (and the unit the range-
    pull acceptance bench measures bytes against)."""
    num_shards = max(int(num_shards), 1)
    shard = min(max(int(shard), 0), num_shards - 1)
    plan = {}
    for cid, rows in chunk_rows.items():
        rows = int(rows)
        lo = (shard * rows) // num_shards
        hi = ((shard + 1) * rows) // num_shards
        if hi > lo:
            plan[cid] = (lo, hi)
    return plan


def prefetch() -> bool:
    """Warm the handoff discovery + manifest while the rest of
    bootstrap (jax init, compile-cache setup) runs — the restore
    path then starts pulling chunks immediately. Best-effort."""
    return _ensure_manifest() is not None


def warm_prefetch(  # wire: consumes=handoff_manifest
    url: str | None = None,
) -> int:
    """Speculative CHUNK prefetch for a warm successor: pull the
    peer's current manifest and every chunk it advertises into the
    warm cache, so the post-cutover restore only re-fetches chunks
    whose content changed between now and the incumbent's final drain
    snapshot. Deliberately does NOT touch the restore path's manifest
    or its sticky-unavailable verdict — the chunks cached here are
    provisional (the authoritative manifest is fetched fresh at
    restore time, and every reuse is gated on a sha match against
    it), and a failed speculation must not poison the real restore.
    Returns the number of bytes cached (0 when nothing was
    prefetched); best-effort — any failure leaves whatever was cached
    so far and falls through to the full pull."""
    if url is None:
        url = discover_url()
    if url is None:
        return 0
    total = 0
    try:
        faults.maybe_fail("warmup.prefetch")
        with trace.span("warmup.prefetch") as attrs:
            fetched = _fetch_manifest(url, FETCH_TIMEOUT_S)
            if fetched is None:
                return 0
            manifest, _ = fetched
            deadline = time.monotonic() + FETCH_TIMEOUT_S
            for name, entry in manifest.items():
                chunks, nbytes, reused = _fetch_state_chunks(
                    url, name, entry, deadline
                )
                sha_table = entry.get("sha") or {}
                with _manifest_lock:
                    _warm_cache[name] = {
                        cid: (sha_table.get(cid), data)
                        for cid, data in chunks
                    }
                total += nbytes + reused
            attrs["bytes"] = total
            attrs["states"] = len(manifest)
    except Exception:  # noqa: BLE001 - speculation is best-effort
        LOG.debug("warm prefetch from %s failed", url, exc_info=True)
    return total


def mark_unavailable() -> None:
    """Stop serving further restores from the peer. Checkpoint's
    version-consistency healing calls this when a storage dir proves
    corrupt: peer-sourced states must re-load through the same
    storage fallback as everyone else, not re-fetch the version
    being reconciled away."""
    global _unavailable
    with _manifest_lock:
        _unavailable = True


def try_restore(  # wire: consumes=handoff_manifest,handoff_fetch_stats
    state: "checkpoint.State"
) -> bool:
    """Restore one state from the predecessor's shard server; False
    when no peer is configured/discoverable, the state isn't in the
    peer's manifest, or anything at all fails — the caller
    (``checkpoint.load_state``) then proceeds with the durable scan.
    The manifest is fetched once and reused across states; one
    failure marks the peer unavailable for the whole process (mixing
    peer-sourced and storage-sourced states would be version-safe —
    both hold the final save's version — but re-probing a dead peer
    for every state would stall the restart it exists to speed up)."""
    global _unavailable
    found = _ensure_manifest()
    if found is None:
        return False
    manifest, manifest_url = found
    entry = manifest.get(state.name)
    if entry is None:
        return False
    # Shard-map-keyed range pull: a state that knows it only needs a
    # row fraction of the peer's leaves (a resharding successor)
    # returns spans here, and only the covering parts cross the wire.
    # Everything else (plan None, peer without parts, any plan error)
    # takes the full-pull path unchanged.
    plan: dict = {}
    parts_meta = entry.get("parts") or {}
    if parts_meta:
        try:
            raw_plan = state.handoff_shard_plan(
                {
                    cid: int(meta["rows"])
                    for cid, meta in parts_meta.items()
                }
            )
        except Exception:  # noqa: BLE001 - plan is an optimization
            LOG.warning(
                "handoff shard plan failed for state %r; pulling "
                "full leaves", state.name, exc_info=True,
            )
            raw_plan = None
        if raw_plan:
            plan = _normalize_plan(raw_plan, parts_meta)
    deadline = time.monotonic() + FETCH_TIMEOUT_S
    t0 = time.monotonic()
    nbytes = 0
    reused = 0
    fetched = False
    if plan:
        # The range pull is an OPTIMIZATION over the same peer: any
        # failure here (part 404, part-sha mismatch, a state whose
        # plan outran its load_chunk_rows) retries as a full-leaf
        # pull before anything falls back to storage — a client-side
        # plan bug must not cost the whole process its fast restart.
        try:
            with trace.span(
                "handoff.fetch", state=state.name, ranged=True
            ) as attrs:
                whole, partial, nbytes, reused = _fetch_state_ranges(
                    manifest_url, state.name, entry, plan, deadline
                )
                attrs["bytes"] = nbytes
                attrs["reused"] = reused
                with trace.span(
                    "handoff.restore", state=state.name
                ):
                    state.load_chunk_rows(whole, partial)
            fetched = True
        except Exception:  # noqa: BLE001 - downgrade to full pull
            LOG.warning(
                "handoff range pull failed for state %r; retrying "
                "the full-leaf pull from the same peer",
                state.name,
                exc_info=True,
            )
    if not fetched:
        try:
            with trace.span(
                "handoff.fetch", state=state.name, ranged=False
            ) as attrs:
                chunks, nbytes, reused = _fetch_state_chunks(
                    manifest_url, state.name, entry, deadline
                )
                attrs["bytes"] = nbytes
                attrs["reused"] = reused
                with trace.span(
                    "handoff.restore", state=state.name
                ):
                    if [cid for cid, _ in chunks] == [RAW_CHUNK]:
                        state.load(io.BytesIO(chunks[0][1]))
                    else:
                        state.load_chunks(chunks)
        except Exception:  # noqa: BLE001 - peer failure -> storage
            LOG.warning(
                "handoff fetch failed for state %r; falling back to "
                "the durable checkpoint",
                state.name,
                exc_info=True,
            )
            with _manifest_lock:
                _unavailable = True
            return False
    elapsed = time.monotonic() - t0
    _fetch_stats["bytes"] += nbytes
    _fetch_stats["seconds"] += elapsed
    _fetch_stats["reused"] += reused
    _states_applied.add(state.name)
    try:
        from adaptdl_tpu import metrics as metrics_mod

        metrics_mod.record_handoff(
            _fetch_stats["seconds"], _fetch_stats["bytes"]
        )
        metrics_mod.record_checkpoint_restore(state.name, elapsed)
    except Exception:  # noqa: BLE001 - observability best-effort
        pass
    if _states_applied >= set(manifest):
        _signal_done(manifest_url)
    return True


if __name__ == "__main__":
    sys.exit(_serve_main())
