"""Zero-downtime rescale: speculative successor warm-up + differential
shard pulls.

The planned-rescale pipeline is overlapped until commit is a cutover,
not a restart: the allocator publishes its CANDIDATE next allocation
ahead of commit (journaled ``candidate`` op + ``GET /candidate/{job}``),
the runner pre-warms a successor process against it, and the commit
epoch only swaps traffic. Covered here:

- candidate lifecycle on ClusterState (publish/get/journal replay,
  survives its own prediction coming true, cleared by superseding
  decisions and epoch rollbacks),
- the supervisor readback endpoint (+ ``sup.candidate.pre`` fault),
- the warmup protocol units (``candidate_matches``, the ready/cutover
  file channel, ``maybe_hold`` go/abort in a real child process),
- differential chunk pulls through the warm-prefetch cache (strictly
  fewer bytes than a full pull, bit-identical result, knob off =
  full pull),
- the GSPMD-derived default handoff shard plan pinned against the
  explicit ``fraction_plan``,
- per-shard content hashing on the orbax-backed sharded checkpoint,
- the LocalElasticRunner end-to-end warm cutover (``steps_lost == 0``,
  zero ``ckpt.restore`` storage spans) and every chaos fallback:
  successor killed mid-warm-up, spawn fault, candidate mispredicted,
  incumbent dead before cutover — each loss-equal to the cold path.
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from adaptdl_tpu import checkpoint, faults, handoff, metrics, rpc
from adaptdl_tpu.parallel import create_mesh
from adaptdl_tpu.sched import warmup
from adaptdl_tpu.sched.local_runner import LocalElasticRunner
from adaptdl_tpu.sched.state import ClusterState
from adaptdl_tpu.sched.supervisor import Supervisor
from adaptdl_tpu.sharded_checkpoint import (
    ShardedTrainerCheckpoint,
    diff_shard_tables,
    shard_hash_table,
)
from adaptdl_tpu.trainer import ElasticTrainer, TrainerCheckpoint

SEED = 1234
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# One deadline for every wait on another process: long enough for a
# loaded machine, and never what a passing run waits for.
WAIT_S = 180.0


@pytest.fixture(autouse=True)
def _clean_fault_and_client_state():
    faults.reset()
    rpc.reset_default_client()
    handoff.set_source(None)
    handoff._reset_client_state()
    yield
    faults.reset()
    rpc.reset_default_client()
    handoff.set_source(None)
    handoff._reset_client_state()
    metrics._reset_state()


def _cstate(tmp_path, **kwargs):
    kwargs.setdefault("alloc_commit_timeout", 0.3)
    kwargs.setdefault("slot_strike_limit", 2)
    kwargs.setdefault("slot_quarantine_s", 60.0)
    kwargs.setdefault("reconcile_window", 0.5)
    return ClusterState(state_dir=str(tmp_path / "sched"), **kwargs)


# ---- candidate lifecycle on the state machine ------------------------


def test_candidate_publish_get_roundtrip_and_journal_replay(tmp_path):
    state = _cstate(tmp_path)
    state.create_job("ns/a")
    state.update("ns/a", allocation=["s0"], status="Running")
    state.renew_lease("ns/a", 0, 30.0, group=0)  # commit
    assert state.publish_candidate(
        "ns/a",
        ["s0", "s1"],
        topology={"modelShards": 2},
        batch_config={"atomicBsz": 16, "accumSteps": 1},
    )
    cand = state.get_candidate("ns/a")
    assert cand["allocation"] == ["s0", "s1"]
    assert cand["topology"]["modelShards"] == 2
    assert cand["batchConfig"] == {"atomicBsz": 16, "accumSteps": 1}
    assert cand["epoch"] >= 0
    # Unknown jobs: no publish, no candidate.
    assert not state.publish_candidate("ns/zzz", ["s0"])
    assert state.get_candidate("ns/zzz") is None
    # The op is journaled: a supervisor recovered mid-warm-up still
    # knows what the runner may be warming against.
    recovered = _cstate(tmp_path)
    assert recovered.get_candidate("ns/a") == cand


def test_candidate_survives_its_own_update_superseded_clears(tmp_path):
    state = _cstate(tmp_path)
    state.create_job("ns/a")
    state.update("ns/a", allocation=["s0"], status="Running")
    state.renew_lease("ns/a", 0, 30.0, group=0)
    state.publish_candidate("ns/a", ["s0", "s1"])
    # The prediction coming true must NOT clear the candidate: the
    # runner reads it back when it sees the drift, after the update.
    state.update("ns/a", allocation=["s0", "s1"])
    cand = state.get_candidate("ns/a")
    assert cand is not None and cand["allocation"] == ["s0", "s1"]
    # A superseding decision (different config) discards it: the warm
    # successor would be built for a config that will never launch.
    state.update("ns/a", allocation=["s0"])
    assert state.get_candidate("ns/a") is None


def test_rollback_clears_candidate(tmp_path):
    """A candidate published against an epoch the commit-timeout
    machinery rolls back is stale — a runner must never warm (or cut
    over to) a successor for a revoked config."""
    state = _cstate(tmp_path)
    state.create_job("ns/a")
    state.update("ns/a", allocation=["good"], status="Running")
    state.renew_lease("ns/a", 0, 30.0, group=0)  # commit baseline
    state.update("ns/a", allocation=["bad", "bad"])  # pending epoch
    state.publish_candidate("ns/a", ["bad", "bad"])
    assert state.get_candidate("ns/a")["allocation"] == ["bad", "bad"]
    state.expire_overdue_allocations(now=time.monotonic() + 1.0)
    assert state.get_candidate("ns/a") is None
    assert not warmup.candidate_matches(
        state.get_candidate("ns/a"), ["bad", "bad"], None
    )


# ---- GET /candidate/{job} --------------------------------------------


def _http_get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read().decode() or "{}")


def test_candidate_endpoint_readback_404s_and_fault(tmp_path):
    state = _cstate(tmp_path)
    state.create_job("ns/a")
    state.update("ns/a", allocation=["s0"], status="Running")
    sup = Supervisor(state)
    sup.start()
    try:
        url = sup.url
        # No candidate published yet: an explicit 404, not {}.
        code, body = _http_get(f"{url}/candidate/ns/a")
        assert code == 404 and body["error"] == "no candidate"
        code, _body = _http_get(f"{url}/candidate/ns/missing")
        assert code == 404
        state.publish_candidate(
            "ns/a", ["s0", "s1"], topology={"seqShards": 2}
        )
        code, body = _http_get(f"{url}/candidate/ns/a")
        assert code == 200
        assert body["allocation"] == ["s0", "s1"]
        assert body["topology"]["seqShards"] == 2
        assert set(body) == {
            "allocation", "topology", "batchConfig", "epoch",
        }
        # An injected fault surfaces as the transient 500 the rpc
        # client retries through; the next hit serves normally.
        faults.configure("sup.candidate.pre=fail@1", seed=SEED)
        code, _body = _http_get(f"{url}/candidate/ns/a")
        assert code == 500
        code, body = _http_get(f"{url}/candidate/ns/a")
        assert code == 200 and body["allocation"] == ["s0", "s1"]
    finally:
        sup.stop()


# ---- warmup protocol units -------------------------------------------


def test_candidate_matches_semantics():
    assert not warmup.candidate_matches(None, ["a"], None)
    cand = {"allocation": ["a", "b"], "topology": None}
    assert warmup.candidate_matches(cand, ["a", "b"], None)
    assert not warmup.candidate_matches(cand, ["a"], None)
    # Topology comparison is normalized: an explicit pure-DP topology
    # equals None.
    trivial = {
        "allocation": ["a"],
        "topology": {"modelShards": 1, "seqShards": 1},
    }
    assert warmup.candidate_matches(trivial, ["a"], None)
    sharded = {"allocation": ["a"], "topology": {"modelShards": 2}}
    assert not warmup.candidate_matches(sharded, ["a"], None)
    assert warmup.candidate_matches(
        sharded, ["a"], {"modelShards": 2}
    )


def test_await_cutover_verdicts(tmp_path):
    # No channel configured (direct test use): proceed.
    assert warmup._await_cutover(None) == warmup.GO
    path = str(tmp_path / "cutover")
    warmup._write_atomic(path, "go")
    assert warmup._await_cutover(path) == warmup.GO
    warmup._write_atomic(path, "abort")
    assert warmup._await_cutover(path) == warmup.ABORT


HOLD_SCRIPT = textwrap.dedent(
    """
    import sys
    from adaptdl_tpu.sched import warmup

    held = warmup.maybe_hold()
    print("RELEASED", held, flush=True)
    sys.exit(0)
    """
)


def _hold_env():
    env2 = dict(os.environ)
    env2["PYTHONPATH"] = (
        REPO + os.pathsep + env2.get("PYTHONPATH", "")
    )
    env2["ADAPTDL_HANDOFF"] = "off"
    return env2


def test_warm_successor_lifecycle_ready_then_cutover(tmp_path):
    script = tmp_path / "hold.py"
    script.write_text(HOLD_SCRIPT)
    warm = warmup.WarmSuccessor(
        [sys.executable, str(script)],
        _hold_env(),
        ["local", "local"],
        None,
        restarts=1,
    )
    warm.spawn()
    try:
        assert warm.wait_ready(WAIT_S), "successor never marked ready"
        assert warm.alive(), "successor must hold after ready"
        assert warm.matches(["local", "local"], None)
        assert warm.matches(
            ["local", "local"], {"modelShards": 1}
        ), "normalized topology comparison"
        assert not warm.matches(["local"], None)
        assert warm.restarts == 1
        proc = warm.cutover()
        assert proc.wait(WAIT_S) == 0, "released successor runs to completion"
    finally:
        warm.discard()


def test_warm_successor_discard_kills_and_cleans(tmp_path):
    script = tmp_path / "hold.py"
    script.write_text(HOLD_SCRIPT)
    warm = warmup.WarmSuccessor(
        [sys.executable, str(script)],
        _hold_env(),
        ["local"],
        None,
        restarts=2,
    )
    warm.spawn()
    assert warm.wait_ready(WAIT_S)
    proc = warm.proc
    warm.discard("test discard")
    assert proc.poll() is not None, "discard reaps the successor"
    assert proc.returncode != 0, "a discarded speculation never 'succeeds'"
    assert not os.path.exists(warm.workdir), "channel dir removed"


def test_maybe_hold_abort_exits_with_graceful_code(tmp_path):
    script = tmp_path / "hold.py"
    script.write_text(HOLD_SCRIPT)
    ready = str(tmp_path / "ready")
    cut = str(tmp_path / "cutover")
    env2 = _hold_env()
    env2["ADAPTDL_WARMUP"] = "1"
    env2["ADAPTDL_WARMUP_READY_FILE"] = ready
    env2["ADAPTDL_WARMUP_CUTOVER_FILE"] = cut
    proc = subprocess.Popen(
        [sys.executable, str(script)],
        env=env2,
        stdout=subprocess.PIPE,
    )
    try:
        deadline = time.monotonic() + WAIT_S
        while time.monotonic() < deadline and not os.path.exists(ready):
            assert proc.poll() is None, "died before marking ready"
            time.sleep(0.05)
        assert os.path.exists(ready)
        warmup._write_atomic(cut, warmup.ABORT)
        out, _ = proc.communicate(timeout=WAIT_S)
        assert proc.returncode == 143, (
            "an aborted speculation exits with the graceful rescale "
            "code so nothing counts it as a failure"
        )
        assert b"RELEASED" not in out, "aborted successor never proceeds"
    finally:
        if proc.poll() is None:
            proc.kill()


# ---- differential chunk pulls ----------------------------------------


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


def _big_parts():
    rng = np.random.default_rng(0)
    return {
        "a": rng.integers(0, 255, size=200_000, dtype=np.uint8),
        "b": rng.integers(0, 255, size=100_000, dtype=np.uint8),
        "step": 1,
    }


def _parts_equal(got, want):
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key])


def test_differential_pull_moves_strictly_fewer_bytes(
    tmp_path, monkeypatch
):
    """The tentpole's byte economics: a warm successor that prefetched
    the incumbent's chunks re-pulls only what changed before the final
    drain — strictly fewer bytes than the full pull — and the restored
    state is bit-identical to the full pull's."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    state = Chunky("diff", _big_parts())

    # Warm-up window: prefetch v1 while the incumbent keeps going.
    server1 = handoff.serve_states()
    try:
        assert handoff.warm_prefetch(url=server1.url) > 0
    finally:
        server1.stop()

    # The incumbent takes more steps: only "b" and "step" change.
    state.parts["step"] = 2
    state.parts["b"] = state.parts["b"][::-1].copy()
    expected = dict(state.parts)

    # Drain snapshot served; successor restores differentially.
    server2 = handoff.serve_states()
    try:
        handoff.set_source(server2.url)
        base = dict(handoff._fetch_stats)
        state.parts = None
        assert checkpoint.load_state(state)
        _parts_equal(state.parts, expected)
        diff_bytes = handoff._fetch_stats["bytes"] - base["bytes"]
        reused = handoff._fetch_stats["reused"] - base["reused"]
        assert reused > 0, "unchanged chunk 'a' reused from the warm cache"
        assert diff_bytes > 0, "changed chunks re-fetched"
    finally:
        server2.stop()

    # Reference: the same snapshot pulled cold (no warm cache).
    handoff.set_source(None)
    handoff._reset_client_state()
    state.parts = dict(expected)
    server3 = handoff.serve_states()
    try:
        handoff.set_source(server3.url)
        state.parts = None
        assert checkpoint.load_state(state)
        _parts_equal(state.parts, expected)
        full_bytes = handoff._fetch_stats["bytes"]
        assert full_bytes > 0
        assert diff_bytes < full_bytes, (
            f"differential pull ({diff_bytes}B) must move strictly "
            f"fewer bytes than the full pull ({full_bytes}B)"
        )
    finally:
        server3.stop()


def test_diff_knob_off_reuses_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_HANDOFF_DIFF", "off")
    state = Chunky("nodiff", _big_parts())
    server1 = handoff.serve_states()
    try:
        assert handoff.warm_prefetch(url=server1.url) > 0
    finally:
        server1.stop()
    expected = dict(state.parts)
    server2 = handoff.serve_states()
    try:
        handoff.set_source(server2.url)
        state.parts = None
        assert checkpoint.load_state(state)
        _parts_equal(state.parts, expected)
        assert handoff._fetch_stats["reused"] == 0, (
            "knob off pins the full-pull behavior"
        )
        assert handoff._fetch_stats["bytes"] > 0
    finally:
        server2.stop()


def test_stale_warm_cache_degrades_to_full_pull_bit_identically(
    tmp_path, monkeypatch
):
    """Every prefetched chunk changed before the drain: zero reuse,
    and the restore is exactly the full pull."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    state = Chunky("stale", _big_parts())
    server1 = handoff.serve_states()
    try:
        assert handoff.warm_prefetch(url=server1.url) > 0
    finally:
        server1.stop()
    state.parts = {
        "a": state.parts["a"][::-1].copy(),
        "b": state.parts["b"][::-1].copy(),
        "step": 3,
    }
    expected = dict(state.parts)
    server2 = handoff.serve_states()
    try:
        handoff.set_source(server2.url)
        state.parts = None
        assert checkpoint.load_state(state)
        _parts_equal(state.parts, expected)
        assert handoff._fetch_stats["reused"] == 0
    finally:
        server2.stop()


# ---- GSPMD-derived default shard plan --------------------------------


def _model_sharded_trainer():
    mesh = create_mesh(
        {"data": 2, "model": 2}, devices=jax.devices()[:4]
    )
    return ElasticTrainer(
        lambda p, b, r: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2),
        {"w": jnp.zeros((64, 8))},
        optax.sgd(0.1),
        16,
        mesh=mesh,
        param_sharding_fn=lambda path, leaf: P("model"),
    )


def test_default_plan_matches_fraction_plan_on_sharded_leaves():
    """Satellite 1: with no explicit ``shard_plan_fn``, the handoff
    shard plan is derived from GSPMD's own device->index map — and on
    model-sharded leaves it equals exactly what a launcher would have
    had to pass as ``fraction_plan(rows, shard, num_shards)``."""
    trainer = _model_sharded_trainer()
    holder = {"state": trainer.init_state()}
    ck = trainer.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
    )
    state = holder["state"]
    leaves, treedef = jax.tree_util.tree_flatten(state)
    specs = treedef.flatten_up_to(trainer.state_spec_tree(state))
    chunk_rows = {
        f"leaf/{i:05d}": int(np.shape(leaf)[0])
        for i, leaf in enumerate(leaves)
        if np.ndim(leaf) >= 1 and np.shape(leaf)[0] > 0
    }
    sharded = {
        f"leaf/{i:05d}"
        for i, spec in enumerate(specs)
        if isinstance(spec, P) and len(spec) > 0 and spec[0] == "model"
    }
    assert sharded & set(chunk_rows), "model-sharded leaves exist"
    # A successor process owning model-shard 0 of 2 (both data rows).
    col0 = list(np.asarray(trainer.mesh.devices)[:, 0].flat)
    derived = ck._default_shard_plan(chunk_rows, devices=col0)
    expected = handoff.fraction_plan(chunk_rows, 0, 2)
    for cid in sorted(sharded & set(chunk_rows)):
        assert derived[cid] == expected[cid], cid
    # ...and shard 1 pins the other half.
    col1 = list(np.asarray(trainer.mesh.devices)[:, 1].flat)
    derived1 = ck._default_shard_plan(chunk_rows, devices=col1)
    expected1 = handoff.fraction_plan(chunk_rows, 1, 2)
    for cid in sorted(sharded & set(chunk_rows)):
        assert derived1[cid] == expected1[cid], cid
    # Replicated leaves derive the full span — which the handoff
    # layer's plan normalization treats as a full pull: over-coverage
    # is safe, under-coverage never happens.
    for cid in set(chunk_rows) - sharded:
        if derived is not None and cid in derived:
            assert derived[cid] == (0, chunk_rows[cid]), cid
    # The default plan is wired in: handoff_shard_plan without an
    # explicit fn routes through the GSPMD derivation.
    assert ck._shard_plan_fn is None
    assert ck.handoff_shard_plan(chunk_rows) is not None


def test_default_plan_excluded_for_transform_hooks():
    """The zero family and transform hooks store a canonical layout
    whose leaves don't map onto the run spec tree: the conservative
    full pull stays."""
    trainer = _model_sharded_trainer()
    holder = {"state": trainer.init_state()}
    ck = TrainerCheckpoint(
        "plan-guard",
        trainer,
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
        transform_save=lambda s: s,
    )
    assert ck._default_shard_plan({"leaf/00000": 64}) is None
    ck.unregister()


# ---- sharded checkpoint: per-shard content hashing -------------------


def _small_trainer(ndev):
    return ElasticTrainer(
        lambda p, b, r: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2),
        {"w": jnp.zeros(4)},
        optax.adam(1e-2),
        16,
        mesh=create_mesh(devices=jax.devices()[:ndev]),
    )


def test_shard_hash_table_is_deterministic_and_tracks_changes():
    trainer = _small_trainer(2)
    state = trainer.init_state()
    tab1 = shard_hash_table(state)
    assert tab1, "addressable shards hashed"
    for entry in tab1.values():
        assert set(entry) == {"sha", "bytes"}
        assert entry["bytes"] > 0
    assert shard_hash_table(state) == tab1, "hashing is deterministic"
    changed, nbytes = diff_shard_tables(None, tab1)
    assert sorted(changed) == sorted(tab1), "no baseline: all changed"
    assert nbytes == sum(e["bytes"] for e in tab1.values())
    assert diff_shard_tables(tab1, tab1) == ([], 0)
    # A train step moves params/moments/step: some shards change.
    rng = np.random.default_rng(0)
    batch = trainer.shard_batch(
        {
            "x": rng.normal(size=(16, 4)).astype(np.float32),
            "y": rng.normal(size=16).astype(np.float32),
        }
    )
    step = trainer.train_step(8, 0)
    state2, _ = step(state, batch)
    changed2, nbytes2 = diff_shard_tables(
        tab1, shard_hash_table(state2)
    )
    assert 0 < len(changed2) <= len(tab1)
    assert nbytes2 > 0


def test_sharded_save_records_shard_delta_and_sidecar(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    trainer = _small_trainer(2)
    holder = {"state": trainer.init_state()}
    ck = ShardedTrainerCheckpoint(
        "st",
        trainer,
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
    )
    checkpoint.save_all_states()
    latest = checkpoint.latest_checkpoint_dir()
    with open(os.path.join(latest, "st"), "rb") as f:
        meta = pickle.load(f)
    delta = meta["shard_delta"]
    assert delta["shards_total"] > 0
    assert delta["shards_changed"] == delta["shards_total"], (
        "first save: everything is new"
    )
    assert delta["changed_bytes"] > 0
    assert os.path.isfile(ck._last_payload_dir + ".hashes.json"), (
        "hash sidecar written beside the payload dir"
    )
    # An identical second save encodes an empty delta.
    checkpoint.save_all_states()
    with open(
        os.path.join(checkpoint.latest_checkpoint_dir(), "st"), "rb"
    ) as f:
        meta2 = pickle.load(f)
    assert meta2["shard_delta"]["shards_changed"] == 0
    assert meta2["shard_delta"]["changed_bytes"] == 0
    ck.unregister()


def test_shard_delta_baseline_survives_restart(tmp_path, monkeypatch):
    """A restored incarnation diffs its first save against what it
    actually restored (the sidecar), not against nothing."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    trainer = _small_trainer(2)
    holder = {"state": trainer.init_state()}
    ck = ShardedTrainerCheckpoint(
        "st",
        trainer,
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
    )
    checkpoint.save_all_states()
    ck.unregister()

    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "1")
    trainer2 = _small_trainer(2)
    holder2 = {"state": trainer2.init_state()}
    ck2 = ShardedTrainerCheckpoint(
        "st",
        trainer2,
        lambda: holder2["state"],
        lambda s: holder2.__setitem__("state", s),
    )
    assert checkpoint.load_state(ck2)
    checkpoint.save_all_states()
    with open(
        os.path.join(checkpoint.latest_checkpoint_dir(), "st"), "rb"
    ) as f:
        meta = pickle.load(f)
    assert meta["shard_delta"]["shards_changed"] == 0, (
        "nothing changed since the restore: the sidecar seeded the "
        "diff baseline across the restart"
    )
    ck2.unregister()


def test_sharded_hash_knob_off_skips_delta(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_SHARDED_HASHES", "off")
    trainer = _small_trainer(2)
    holder = {"state": trainer.init_state()}
    ck = ShardedTrainerCheckpoint(
        "st",
        trainer,
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
    )
    checkpoint.save_all_states()
    with open(
        os.path.join(checkpoint.latest_checkpoint_dir(), "st"), "rb"
    ) as f:
        meta = pickle.load(f)
    assert "shard_delta" not in meta
    assert not os.path.exists(ck._last_payload_dir + ".hashes.json")
    ck.unregister()


# ---- runner end-to-end: warm cutover + chaos fallbacks ---------------

# A jax-free elastic job: deterministic EMA toward TRUE_W (the weight
# trajectory is a pure function of the step count, so ANY correct
# restart discipline — warm, cold, crash-recovery — ends bit-identical;
# loss-equality is weight-equality). Conforming drain: on SIGTERM save
# durably, leave a shard server behind (planned path), exit 143.
SIM_SCRIPT = textwrap.dedent(
    """
    import os
    import pickle
    import sys
    import time

    import numpy as np

    from adaptdl_tpu import _signal, checkpoint, env, handoff, trace
    from adaptdl_tpu.sched import warmup

    _signal.install_handlers()

    LOG = os.environ["SIM_LOG"]

    def emit(line):
        with open(LOG, "a") as f:
            f.write(line + chr(10))
            f.flush()

    if os.environ.get("SIM_WARM_SUICIDE") and os.environ.get(
        "ADAPTDL_WARMUP"
    ):
        # Chaos: the speculative successor dies mid-warm-up, before it
        # ever reaches ready.
        os._exit(9)

    # Explicit early hold point (warmup.maybe_hold is idempotent; the
    # call inside load_state below becomes a no-op).
    went = warmup.maybe_hold()
    if went and env.handoff_enabled():
        # Adopted at cutover: the incumbent's drain server may still
        # be advertising; wait for discovery so the restore below
        # measures the pure peer-pull path.
        desc = os.path.join(
            os.environ["ADAPTDL_CHECKPOINT_PATH"], ".handoff.json"
        )
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline and not os.path.exists(desc):
            time.sleep(0.02)

    class Sim(checkpoint.State):
        def __init__(self):
            super().__init__("sim")
            self.w = np.zeros(4)
            self.step = 0

        def save(self, f):
            pickle.dump({"w": self.w, "step": self.step}, f)

        def load(self, f):
            d = pickle.load(f)
            self.w, self.step = d["w"], d["step"]

        def snapshot_chunks(self, snapshot):
            d = pickle.loads(snapshot)
            return [
                ("w", pickle.dumps(d["w"])),
                ("step", pickle.dumps(d["step"])),
            ]

        def load_chunks(self, chunks):
            d = {k: pickle.loads(v) for k, v in chunks}
            self.w, self.step = d["w"], d["step"]

    state = Sim()
    restarts = env.num_restarts()
    mode = "warm" if os.environ.get("ADAPTDL_WARMUP") else "cold"
    start_seq = trace.buffer_seq()
    checkpoint.load_state(state)
    spans = sorted({
        rec["name"]
        for rec in trace.snapshot_spans()
        if rec.get("seq", 0) > start_seq
    })
    emit("start %d %s %d %s" % (
        restarts, mode, state.step, "|".join(spans) or "-",
    ))

    TRUE_W = np.array([1.0, -2.0, 3.0, 0.5])
    total = int(os.environ.get("SIM_TOTAL_STEPS", "80"))
    pause = float(os.environ.get("SIM_STEP_SLEEP", "0.04"))
    # The first incarnation goes no further than this step until it
    # is signalled: it is mid-training when the rescale comes, however
    # long the successor's warm-up takes on this machine.
    hold_at = 0 if restarts else int(os.environ.get("SIM_HOLD_AT", "0"))
    holding = False
    while state.step < total:
        if _signal.get_exit_flag():
            if os.environ.get("SIM_CRASH_ON_TERM"):
                emit("crash %d %d" % (restarts, state.step))
                os._exit(7)
            serve = env.handoff_enabled()
            handle = checkpoint.save_all_states(
                retain_snapshots=serve
            )
            if serve:
                handoff.spawn_server(snapshots=handle.snapshots)
            emit("drain %d %d" % (restarts, state.step))
            sys.exit(143)
        if hold_at and state.step == hold_at:
            if not holding:
                holding = True
                emit("hold %d %d" % (restarts, state.step))
            time.sleep(pause)
            continue
        state.w = state.w + 0.1 * (TRUE_W - state.w)
        state.step += 1
        if state.step % 25 == 0:
            checkpoint.save_all_states()
        time.sleep(pause)
    checkpoint.save_all_states()
    emit("done %d %d %s" % (
        restarts,
        state.step,
        ",".join("%.17g" % v for v in state.w),
    ))
    sys.exit(0)
    """
)

TRUE_W = np.array([1.0, -2.0, 3.0, 0.5])


def _expected_w(steps):
    w = np.zeros(4)
    for _ in range(steps):
        w = w + 0.1 * (TRUE_W - w)
    return w


def _log_lines(log):
    with open(log, encoding="utf-8") as f:
        return [ln.split() for ln in f.read().splitlines() if ln]


def _done_weights(line):
    return np.array([float(v) for v in line[3].split(",")])


def _drive_rescale(runner, log, errors, alloc):
    """Test-side allocator: once the incumbent has taken its stretch
    of steps (and holds for the signal), publish the candidate (as the
    real allocator does, just ahead of the decision) and then the
    decision itself."""
    try:
        deadline = time.monotonic() + WAIT_S
        while time.monotonic() < deadline:
            if os.path.exists(log) and any(
                ln[0] == "hold" for ln in _log_lines(log)
            ):
                break
            time.sleep(0.05)
        else:
            errors.append("incumbent never reached its hold")
            return
        runner.state.publish_candidate(runner.job_name, alloc, None)
        runner.state.update(runner.job_name, allocation=alloc)
    except Exception as exc:  # noqa: BLE001 - surfaced via errors
        errors.append(repr(exc))


# Steps of the simulated job, and the step at which its first
# incarnation waits to be signalled: the incumbent is still training
# when the speculative successor — a fresh python whose imports alone
# take seconds, many of them beside five other workers — reports ready
# (or dies, or is discarded). Past a periodic save (every 25 steps),
# so that a crash has steps to lose.
SIM_TOTAL = 80
SIM_HOLD_AT = 40


def _run_elastic(
    tmp_path,
    monkeypatch,
    *,
    warm_enabled=True,
    sim_env=None,
    fault_spec=None,
    total=SIM_TOTAL,
):
    monkeypatch.setenv(
        "ADAPTDL_WARMUP_ENABLED", "on" if warm_enabled else ""
    )
    # The runner gives a successor this long to report ready before it
    # rescales cold; the successor's death ends the wait at once.
    monkeypatch.setattr(warmup, "READY_DEADLINE_S", WAIT_S)
    if fault_spec:
        faults.configure(fault_spec, seed=SEED)
    script = tmp_path / "sim.py"
    script.write_text(SIM_SCRIPT)
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    log = str(tmp_path / "sim.log")
    extra = {
        "PYTHONPATH": REPO
        + os.pathsep
        + os.environ.get("PYTHONPATH", ""),
        "SIM_LOG": log,
        "SIM_TOTAL_STEPS": str(total),
        "SIM_HOLD_AT": str(SIM_HOLD_AT),
        "SIM_STEP_SLEEP": "0.04",
    }
    extra.update(sim_env or {})
    runner = LocalElasticRunner(
        str(script),
        num_chips=2,
        checkpoint_dir=str(ckpt),
        job_name="test/warm",
        allocator_interval=9999.0,
        extra_env=extra,
        handoff=True,
    )
    # All allocation decisions come from the test driver; the real
    # allocator stays out of the way for determinism.
    runner.allocator.optimize_once = lambda: None
    errors = []
    driver = threading.Thread(
        target=_drive_rescale,
        args=(runner, log, errors, ["local", "local"]),
        daemon=True,
    )
    driver.start()
    code = runner.run()
    driver.join(WAIT_S)
    assert not driver.is_alive() and not errors, errors
    return code, log, runner


def test_warm_rescale_cutover_loses_zero_steps(tmp_path, monkeypatch):
    """THE warmgate scenario: a planned rescale with warm-up on. The
    successor was fully up before the incumbent was signalled, the
    cutover adopts it, its restore is pure peer-pull (zero
    ``ckpt.restore`` storage spans), and it resumes at exactly the
    step the incumbent drained at — ``steps_lost == 0``."""
    code, log, runner = _run_elastic(tmp_path, monkeypatch)
    assert code == 0
    assert runner.restarts == 1, "exactly one (planned) rescale"
    lines = _log_lines(log)
    starts = [ln for ln in lines if ln[0] == "start"]
    drains = [ln for ln in lines if ln[0] == "drain"]
    dones = [ln for ln in lines if ln[0] == "done"]
    assert [ln[1:3] for ln in starts] == [
        ["0", "cold"],
        ["1", "warm"],
    ], f"one cold launch, one warm cutover: {starts}"
    assert len(drains) == 1
    drain_step = int(drains[0][2])
    assert drain_step > 0, "incumbent was mid-training at the drift"
    warm = starts[1]
    assert int(warm[3]) == drain_step, (
        f"steps lost at cutover: drained at {drain_step}, resumed at "
        f"{warm[3]}"
    )
    spans = warm[4].split("|")
    assert "handoff.fetch" in spans and "handoff.restore" in spans
    assert "ckpt.restore" not in spans, (
        "warm cutover touched checkpoint storage"
    )
    assert len(dones) == 1 and int(dones[0][2]) == SIM_TOTAL
    assert np.array_equal(
        _done_weights(dones[0]), _expected_w(SIM_TOTAL)
    ), (
        "warm cutover is loss-equal to uninterrupted training"
    )
    assert runner.state.get_job("test/warm").status == "Succeeded"


def test_warm_spawn_fault_falls_back_cold_loss_equal(
    tmp_path, monkeypatch
):
    code, log, runner = _run_elastic(
        tmp_path, monkeypatch, fault_spec="warmup.spawn=fail@1"
    )
    assert code == 0
    lines = _log_lines(log)
    starts = [ln for ln in lines if ln[0] == "start"]
    assert [ln[1:3] for ln in starts] == [
        ["0", "cold"],
        ["1", "cold"],
    ], f"spawn fault falls back to the cold planned path: {starts}"
    dones = [ln for ln in lines if ln[0] == "done"]
    assert np.array_equal(_done_weights(dones[0]), _expected_w(SIM_TOTAL))


def test_warm_successor_killed_midwarm_falls_back_cold(
    tmp_path, monkeypatch
):
    code, log, _runner = _run_elastic(
        tmp_path, monkeypatch, sim_env={"SIM_WARM_SUICIDE": "1"}
    )
    assert code == 0
    lines = _log_lines(log)
    starts = [ln for ln in lines if ln[0] == "start"]
    assert [ln[1:3] for ln in starts] == [
        ["0", "cold"],
        ["1", "cold"],
    ], f"dead speculation is discarded, rescale goes cold: {starts}"
    dones = [ln for ln in lines if ln[0] == "done"]
    assert np.array_equal(_done_weights(dones[0]), _expected_w(SIM_TOTAL))


def test_incumbent_crash_before_cutover_discards_warm(
    tmp_path, monkeypatch
):
    """The incumbent dies (exit 7) instead of draining: the warm
    successor was built against state the crash never drained — it is
    discarded, and the relaunch restores cold from the durable
    checkpoint, loss-equal."""
    code, log, _runner = _run_elastic(
        tmp_path, monkeypatch, sim_env={"SIM_CRASH_ON_TERM": "1"}
    )
    assert code == 0
    lines = _log_lines(log)
    assert [ln[0] for ln in lines].count("crash") == 1
    starts = [ln for ln in lines if ln[0] == "start"]
    assert all(ln[2] == "cold" for ln in starts), (
        f"a warm successor must never survive an incumbent crash: "
        f"{starts}"
    )
    dones = [ln for ln in lines if ln[0] == "done"]
    assert len(dones) == 1 and int(dones[0][2]) == SIM_TOTAL
    assert np.array_equal(_done_weights(dones[0]), _expected_w(SIM_TOTAL))


def test_mispredicted_candidate_discards_warm_successor(
    tmp_path, monkeypatch
):
    """Mispredict fallback at the adoption gate: the launch config
    moved again between warm-up and cutover, so the ready successor is
    discarded — never adopted — and the caller launches cold."""
    script = tmp_path / "sim.py"
    script.write_text(SIM_SCRIPT)
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    log = str(tmp_path / "sim.log")
    runner = LocalElasticRunner(
        str(script),
        num_chips=2,
        checkpoint_dir=str(ckpt),
        job_name="test/warm-mis",
        allocator_interval=9999.0,
        extra_env={
            "PYTHONPATH": REPO
            + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
            "SIM_LOG": log,
        },
        handoff=False,
    )
    runner.supervisor.start()
    try:
        alloc = ["local", "local"]
        # No candidate published: the runner never speculates.
        runner._spawn_warm(alloc, None)
        assert runner._warm is None

        runner.state.publish_candidate(runner.job_name, alloc, None)
        runner._spawn_warm(alloc, None)
        assert runner._warm is not None and runner._warm.alive()
        warm_proc = runner._warm.proc
        workdir = runner._warm.workdir
        # What the graceful-exit path does before re-entering the loop.
        runner.restarts += 1
        assert runner._adopt_warm(["local"], None) is None, (
            "mispredicted speculation must never be adopted"
        )
        assert runner._warm is None
        warm_proc.wait(WAIT_S)
        assert warm_proc.returncode != 0
        assert not os.path.exists(workdir)
    finally:
        runner.supervisor.stop()
        runner.state.update(runner.job_name, status="Failed")


def test_stale_restart_counter_discards_warm_successor(
    tmp_path, monkeypatch
):
    """A successor warmed for restart N must not be adopted as
    restart N+1 (its checkpoint version indexing would clash): the
    restart-counter gate discards it even when the config matches."""
    script = tmp_path / "sim.py"
    script.write_text(SIM_SCRIPT)
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    runner = LocalElasticRunner(
        str(script),
        num_chips=2,
        checkpoint_dir=str(ckpt),
        job_name="test/warm-stale",
        allocator_interval=9999.0,
        extra_env={
            "PYTHONPATH": REPO
            + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
            "SIM_LOG": str(tmp_path / "sim.log"),
        },
        handoff=False,
    )
    runner.supervisor.start()
    try:
        alloc = ["local", "local"]
        runner.state.publish_candidate(runner.job_name, alloc, None)
        runner._spawn_warm(alloc, None)
        assert runner._warm is not None
        warm_proc = runner._warm.proc
        # The incumbent crashed AND a cold retry already burned the
        # restart index this successor was spawned with.
        runner.restarts += 2
        assert runner._adopt_warm(alloc, None) is None
        warm_proc.wait(WAIT_S)
        assert warm_proc.returncode != 0
    finally:
        runner.supervisor.stop()
        runner.state.update(runner.job_name, status="Failed")
