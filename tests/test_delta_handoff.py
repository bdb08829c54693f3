"""Differential checkpoints + peer-to-peer shard handoff.

Delta-chain correctness (apply(full, d1..dn) == a direct full
snapshot; crash mid-delta-write leaves the prior chain loadable; a
broken link falls back version-consistently; drain forces a full) and
the planned-rescale handoff path (hash-verified chunk fetch, fallback
to the durable checkpoint on every failure mode, the rescale-fast
gate's zero-storage-reads property, supervisor advertisement, child
shard-server lifecycle).
"""

from __future__ import annotations

import json
import os
import pickle
import time

import pytest

from adaptdl_tpu import checkpoint, env, faults, handoff, rpc, trace
from adaptdl_tpu.sched.state import ClusterState
from adaptdl_tpu.sched.supervisor import Supervisor

SEED = 1234


@pytest.fixture(autouse=True)
def _clean_fault_state():
    faults.reset()
    rpc.reset_default_client()
    yield
    faults.reset()
    rpc.reset_default_client()


class Chunky(checkpoint.State):
    """Delta-capable state: one chunk per named part."""

    def __init__(self, name, parts=None):
        super().__init__(name)
        self.parts = dict(parts or {})

    def save(self, fileobj):
        pickle.dump(self.parts, fileobj)

    def load(self, fileobj):
        self.parts = pickle.load(fileobj)

    def snapshot_chunks(self, snapshot):
        parts = pickle.loads(snapshot)
        return [
            (key, pickle.dumps(value))
            for key, value in sorted(parts.items())
        ]

    def load_chunks(self, chunks):
        self.parts = {
            key: pickle.loads(data) for key, data in chunks
        }


class Raw(checkpoint.State):
    """Non-chunkable state: always a full opaque payload."""

    def __init__(self, name, value=None):
        super().__init__(name)
        self.value = value

    def save(self, fileobj):
        pickle.dump(self.value, fileobj)

    def load(self, fileobj):
        self.value = pickle.load(fileobj)


def _manifest(ckpt_dir):
    with open(
        os.path.join(ckpt_dir, checkpoint.MANIFEST_NAME),
        encoding="utf-8",
    ) as f:
        return json.load(f)


def _dirs(root):
    return sorted(
        entry
        for entry in os.listdir(root)
        if entry.startswith("checkpoint-")
    )


# ---- delta-chain correctness -----------------------------------------


def test_delta_chain_apply_equals_direct_full(tmp_path, monkeypatch):
    """full + d1..dn reconstructs EXACTLY the state a direct full
    snapshot would have written at dn's save point."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "10")
    state = Chunky("c", {"a": 1, "b": [2, 2], "c": "x"})
    checkpoint.save_all_states()  # full
    state.parts["a"] = 10
    checkpoint.save_all_states()  # d1
    state.parts["b"] = [20, 20]
    state.parts["d"] = "new"
    checkpoint.save_all_states()  # d2 (adds a chunk)
    del state.parts["c"]
    checkpoint.save_all_states()  # d3 (drops a chunk)
    expected = dict(state.parts)
    newest = _dirs(tmp_path)[-1]
    manifest = _manifest(tmp_path / newest)
    assert manifest["kind"] == "delta"
    assert manifest["states"]["c"]["kind"] == "delta"
    assert manifest["chain"] == [_dirs(tmp_path)[0]]
    state.parts = None
    assert checkpoint.load_state(state)
    assert state.parts == expected


def test_full_every_cadence_and_chain_pruning(tmp_path, monkeypatch):
    """Every Nth save is full; the chain's base survives pruning
    until the next full supersedes the whole chain."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "3")
    state = Chunky("c", {"a": 0})
    checkpoint.save_all_states()  # full (base)
    base = _dirs(tmp_path)[0]
    for i in range(1, 3):
        state.parts["a"] = i
        checkpoint.save_all_states()  # d1, d2
        dirs = _dirs(tmp_path)
        assert base in dirs, "delta chain keeps its full base alive"
        assert len(dirs) == 2, "superseded deltas are pruned"
    state.parts["a"] = 99
    checkpoint.save_all_states()  # cadence forces a full
    dirs = _dirs(tmp_path)
    assert len(dirs) == 1 and base not in dirs
    assert _manifest(tmp_path / dirs[0])["kind"] == "full"
    state.parts = None
    assert checkpoint.load_state(state)
    assert state.parts == {"a": 99}


def test_crash_mid_delta_write_leaves_prior_chain_loadable(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "10")
    state = Chunky("c", {"a": 1})
    checkpoint.save_all_states()  # full
    state.parts["a"] = 2
    checkpoint.save_all_states()  # d1
    state.parts["a"] = 3
    faults.configure("ckpt.delta_write=fail@1", seed=SEED)
    with pytest.raises(faults.InjectedFault):
        checkpoint.save_all_states()  # d2 dies mid-write
    faults.configure(None)
    state.parts = None
    assert checkpoint.load_state(state)
    assert state.parts == {"a": 2}, "prior chain (full+d1) intact"
    leftovers = [
        entry
        for entry in os.listdir(tmp_path)
        if entry.startswith("_tmp-checkpoint-")
    ]
    assert not leftovers


def test_broken_delta_link_falls_back_to_full_base(
    tmp_path, monkeypatch
):
    """A corrupt delta payload poisons its dir; the restore drops
    back to the chain's full base — an older but consistent version."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "10")
    state = Chunky("c", {"a": 1})
    checkpoint.save_all_states()  # full
    state.parts["a"] = 2
    checkpoint.save_all_states()  # d1
    delta_dir = _dirs(tmp_path)[-1]
    path = tmp_path / delta_dir / "c"
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    path.write_bytes(bytes(blob))
    state.parts = None
    assert checkpoint.load_state(state)
    assert state.parts == {"a": 1}, "fell back to the full base"


def test_corrupt_base_breaks_the_whole_chain(tmp_path, monkeypatch):
    """A corrupt full base means no link of the chain can prove
    itself: the restore must refuse to cold-start, not serve a
    half-reconstructed state."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "10")
    state = Chunky("c", {"a": 1})
    checkpoint.save_all_states()  # full
    state.parts["a"] = 2
    checkpoint.save_all_states()  # d1
    base_dir = _dirs(tmp_path)[0]
    path = tmp_path / base_dir / "c"
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    path.write_bytes(bytes(blob))
    state.parts = None
    with pytest.raises(checkpoint.CheckpointUnreadableError):
        checkpoint.load_state(state)


def test_delta_chain_verifies_chunk_shas(tmp_path, monkeypatch):
    """A delta whose recorded chunk sha disagrees with the base's
    bytes (the broken-link case the per-file digests can't see) is
    rejected by the per-chunk verification."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "10")
    state = Chunky("c", {"a": 1, "b": 2})
    checkpoint.save_all_states()  # full
    state.parts["a"] = 10
    checkpoint.save_all_states()  # d1 (b unchanged, served from base)
    delta_dir = _dirs(tmp_path)[-1]
    path = tmp_path / delta_dir / "c"
    with open(path, "rb") as f:
        container = pickle.load(f)
    container["chunk_sha"]["b"] = "0" * 64  # lie about the base link
    with open(path, "wb") as f:
        pickle.dump(container, f)
    # Re-align the dir's own file digest so ONLY the chain check can
    # catch the lie.
    manifest_path = tmp_path / delta_dir / checkpoint.MANIFEST_NAME
    manifest = json.loads(manifest_path.read_text())
    sha, size = checkpoint._hash_file(str(path))
    manifest["states"]["c"].update({"sha256": sha, "bytes": size})
    manifest_path.write_text(json.dumps(manifest))
    state.parts = None
    assert checkpoint.load_state(state)
    assert state.parts == {"a": 1, "b": 2}, "fell back to the base"


def test_urgent_drain_forces_full_checkpoint(tmp_path, monkeypatch):
    """The drain/preemption final save never rides a delta chain."""
    from adaptdl_tpu.sched import preemption

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "100")
    state = Chunky("c", {"a": 1})
    checkpoint.save_all_states()  # full
    state.parts["a"] = 2
    checkpoint.save_all_states()  # delta
    assert _manifest(tmp_path / _dirs(tmp_path)[-1])["kind"] == "delta"
    state.parts["a"] = 3
    preemption.reset_notice()
    try:
        preemption.urgent_drain()
    finally:
        preemption.reset_notice()
    dirs = _dirs(tmp_path)
    assert len(dirs) == 1, "a full save prunes the whole chain"
    manifest = _manifest(tmp_path / dirs[0])
    assert manifest["kind"] == "full"
    state.parts = None
    assert checkpoint.load_state(state)
    assert state.parts == {"a": 3}


def test_full_every_one_keeps_legacy_raw_payloads(
    tmp_path, monkeypatch
):
    """The default cadence (1 = deltas off) writes the pre-delta raw
    payload format even for chunk-capable states."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    state = Chunky("c", {"a": 1})
    checkpoint.save_all_states()
    newest = _dirs(tmp_path)[-1]
    manifest = _manifest(tmp_path / newest)
    assert manifest["kind"] == "full"
    assert "kind" not in manifest["states"]["c"]
    with open(tmp_path / newest / "c", "rb") as f:
        assert pickle.load(f) == {"a": 1}, "raw State.save bytes"


def test_save_bytes_reported_in_restart_stats(tmp_path, monkeypatch):
    from adaptdl_tpu import metrics

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "4")
    metrics._reset_state()
    state = Chunky("c", {"a": list(range(1000)), "b": 0})
    checkpoint.save_all_states()
    stats = metrics.restart_stats()
    assert stats["saveKind"] == "full"
    full_bytes = stats["saveBytes"]
    assert full_bytes > 0
    state.parts["b"] = 1  # only the small chunk changes
    checkpoint.save_all_states()
    stats = metrics.restart_stats()
    assert stats["saveKind"] == "delta"
    assert stats["saveBytes"] < full_bytes
    assert 0 < stats["deltaRatio"] < 1
    metrics._reset_state()


# ---- peer-to-peer handoff --------------------------------------------


@pytest.fixture
def served(tmp_path, monkeypatch):
    """A predecessor's worth of states behind a live shard server,
    an EMPTY checkpoint dir (so any storage read would fail), and
    the client pointed at the peer."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    chunky = Chunky("hand-c", {"w": [1.0, 2.0], "step": 7})
    raw = Raw("hand-r", {"epoch": 3})
    server = handoff.serve_states()
    handoff.set_source(server.url)
    yield chunky, raw, server
    server.stop()


def test_handoff_roundtrip_restores_both_state_kinds(served):
    chunky, raw, server = served
    expected_parts, expected_value = dict(chunky.parts), dict(raw.value)
    chunky.parts, raw.value = None, None
    assert checkpoint.load_state(chunky)
    assert checkpoint.load_state(raw)
    assert chunky.parts == expected_parts
    assert raw.value == expected_value
    assert server.done.wait(2.0), "successor signalled completion"


def test_rescale_fast_gate_zero_storage_reads(served):
    """The CI rescale-fast gate: a planned-rescale restore records
    handoff spans and NO ckpt.restore span — and since the checkpoint
    dir is empty, the successful restore itself proves the path read
    zero bytes of checkpoint storage."""
    chunky, raw, _server = served
    start_seq = trace.buffer_seq()
    chunky.parts, raw.value = None, None
    assert checkpoint.load_state(chunky)
    assert checkpoint.load_state(raw)
    spans = [
        rec
        for rec in trace.snapshot_spans()
        if rec.get("seq", 0) > start_seq
    ]
    names = {rec["name"] for rec in spans}
    assert "handoff.fetch" in names and "handoff.restore" in names
    assert "ckpt.restore" not in names, (
        "planned-rescale path touched checkpoint storage"
    )
    from adaptdl_tpu import metrics

    stats = metrics.restart_stats()
    assert stats["handoffS"] >= 0 and stats["handoffBytes"] > 0
    metrics._reset_state()


def test_handoff_sha_mismatch_falls_back_to_storage(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    state = Chunky("hand-c", {"w": 1})
    checkpoint.save_all_states()  # durable fallback holds w=1
    server = handoff.serve_states()
    try:
        # Corrupt a served chunk AFTER the sha table was computed.
        entry = server._payload["hand-c"]
        cid = entry["order"][0]
        entry["chunks"][cid] = b"garbage"
        handoff.set_source(server.url)
        state.parts = None
        assert checkpoint.load_state(state)
        assert state.parts == {"w": 1}, "durable checkpoint served"
    finally:
        server.stop()


def test_handoff_fetch_fault_falls_back(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    state = Chunky("hand-c", {"w": 5})
    checkpoint.save_all_states()
    server = handoff.serve_states()
    try:
        handoff.set_source(server.url)
        faults.configure("handoff.fetch=fail@1+", seed=SEED)
        state.parts = None
        assert checkpoint.load_state(state)
        assert state.parts == {"w": 5}
    finally:
        faults.configure(None)
        server.stop()


def test_handoff_dead_peer_falls_back(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    state = Chunky("hand-c", {"w": 9})
    checkpoint.save_all_states()
    server = handoff.serve_states()
    url = server.url
    server.stop()  # peer died before the successor arrived
    handoff.set_source(url)
    state.parts = None
    assert checkpoint.load_state(state)
    assert state.parts == {"w": 9}


def test_handoff_unavailability_is_sticky(tmp_path, monkeypatch):
    """One failed probe must not be re-paid for every state."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    a, b = Chunky("hand-a", {"x": 1}), Chunky("hand-b", {"y": 2})
    checkpoint.save_all_states()
    server = handoff.serve_states()
    url = server.url
    server.stop()
    handoff.set_source(url)
    assert checkpoint.load_state(a)
    start = time.monotonic()
    assert checkpoint.load_state(b)
    assert time.monotonic() - start < 1.0, (
        "second state re-probed the dead peer"
    )


def test_descriptor_discovery_validates_group(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_HANDOFF", "on")
    descriptor = tmp_path / handoff.DESCRIPTOR_NAME
    descriptor.write_text(
        json.dumps({"url": "http://127.0.0.1:1/x", "group": 0})
    )
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "1")
    assert handoff.discover_url() == "http://127.0.0.1:1/x"
    # Same (or newer) group = not our predecessor: never trusted.
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "0")
    assert handoff.discover_url() is None
    # An OLDER-than-predecessor leftover (some earlier epoch's
    # server that outlived a crash) may hold state that predates
    # newer durable checkpoints: also never trusted.
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "3")
    assert handoff.discover_url() is None


def test_handoff_url_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_HANDOFF", "on")
    monkeypatch.setenv("ADAPTDL_HANDOFF_URL", "http://127.0.0.1:2/y")
    assert handoff.discover_url() == "http://127.0.0.1:2/y"


def test_handoff_disabled_by_default(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.delenv("ADAPTDL_HANDOFF", raising=False)
    descriptor = tmp_path / handoff.DESCRIPTOR_NAME
    descriptor.write_text(
        json.dumps({"url": "http://127.0.0.1:1/x", "group": 0})
    )
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "1")
    assert not env.handoff_enabled()
    assert handoff.discover_url() is None


def test_supervisor_handoff_advertise_and_discover(monkeypatch):
    state = ClusterState()
    state.create_job("ns/job", spec={"max_replicas": 4})
    supervisor = Supervisor(state)
    url = supervisor.start()
    try:
        client = rpc.default_client()
        # No advertisement yet: empty body.
        response = client.get(f"{url}/handoff/ns/job")
        assert response.status_code == 200 and response.json() == {}
        response = client.put(
            f"{url}/handoff/ns/job",
            json={"url": "http://10.0.0.1:7777", "group": 2},
        )
        assert response.status_code == 200
        body = client.get(f"{url}/handoff/ns/job").json()
        assert body == {"url": "http://10.0.0.1:7777", "group": 2}
        # A stale (older-group) retry must not roll the pointer back.
        response = client.put(
            f"{url}/handoff/ns/job",
            json={"url": "http://10.0.0.9:1111", "group": 1},
        )
        assert response.status_code == 404
        body = client.get(f"{url}/handoff/ns/job").json()
        assert body["url"] == "http://10.0.0.1:7777"
        # Unknown job: 404 both ways.
        assert (
            client.get(f"{url}/handoff/ns/ghost").status_code == 404
        )
        # Successor-side discovery goes through the supervisor.
        monkeypatch.setenv("ADAPTDL_HANDOFF", "on")
        monkeypatch.setenv("ADAPTDL_SUPERVISOR_URL", url)
        monkeypatch.setenv("ADAPTDL_JOB_ID", "ns/job")
        monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "3")
        assert handoff.discover_url() == "http://10.0.0.1:7777"
        monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "2")
        assert handoff.discover_url() is None, "stale group rejected"
    finally:
        supervisor.stop()


def test_spawned_child_server_serves_and_expires(
    tmp_path, monkeypatch
):
    """The detached child shard server: spawned with the pickled
    payload on stdin, advertises via the descriptor file, serves a
    successor, and exits after /done."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_HANDOFF", "on")
    state = Chunky("hand-c", {"w": 42})
    proc = handoff.spawn_server()
    assert proc is not None
    descriptor = tmp_path / handoff.DESCRIPTOR_NAME
    deadline = time.monotonic() + 30
    while not descriptor.exists():
        assert time.monotonic() < deadline, "descriptor never appeared"
        assert proc.poll() is None, "child died before serving"
        time.sleep(0.1)
    # The successor (restart group bumped) discovers and restores.
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "1")
    state.parts = None
    assert checkpoint.load_state(state)
    assert state.parts == {"w": 42}
    # /done was posted (all manifest states fetched): child exits
    # and withdraws its descriptor.
    deadline = time.monotonic() + 30
    while proc.poll() is None:
        assert time.monotonic() < deadline, "child never exited"
        time.sleep(0.1)
    assert proc.returncode == 0
    assert not descriptor.exists()


def test_poisoned_dir_heals_peer_sourced_states(tmp_path, monkeypatch):
    """Version consistency across SOURCES: when a storage dir proves
    corrupt after some states already restored from the peer, the
    peer-sourced states are re-loaded through the same storage
    fallback (peer marked unavailable first), so every state lands on
    one surviving version."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    a = Chunky("heal-a", {"v": 1})
    b = Chunky("heal-b", {"v": 1})
    checkpoint.save_all_states()  # version 1 on disk
    a.parts["v"] = 2
    b.parts["v"] = 2
    # Keep version 1 alive: fake the post_rename window so the v2
    # save does not prune it.
    real_fsync = checkpoint._fsync_dir
    calls = {"n": 0}

    def die_after_rename(path):
        real_fsync(path)
        if path == str(tmp_path):
            calls["n"] += 1
            if calls["n"] == 1:
                raise KeyboardInterrupt("pre-prune kill")

    monkeypatch.setattr(checkpoint, "_fsync_dir", die_after_rename)
    with pytest.raises(KeyboardInterrupt):
        checkpoint.save_all_states()  # version 2 on disk, v1 kept
    monkeypatch.setattr(checkpoint, "_fsync_dir", real_fsync)
    # The peer serves ONLY state a, at version 2 (matching the
    # newest dir, as a real drain server would).
    server = handoff.serve_states(states=[a])
    try:
        # Corrupt the newest dir's b payload: b's storage scan will
        # poison it and fall back to version 1.
        newest = sorted(_dirs(tmp_path))[-1]
        path = tmp_path / newest / "heal-b"
        blob = bytearray(path.read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        path.write_bytes(bytes(blob))
        handoff.set_source(server.url)
        a.parts = None
        b.parts = None
        assert checkpoint.load_state(a)
        assert a.parts == {"v": 2}, "a came from the peer"
        assert checkpoint.load_state(b)  # poisons newest, heals a
        assert b.parts == {"v": 1}
        assert a.parts == {"v": 1}, (
            "peer-sourced a must fall back alongside b"
        )
    finally:
        server.stop()


def test_spawn_server_is_rank0_only(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_HANDOFF", "on")
    monkeypatch.setenv("ADAPTDL_REPLICA_RANK", "1")
    Chunky("rank-c", {"w": 1})
    assert handoff.spawn_server() is None
