"""Mesh-shape elasticity: reshard-aware fast rescale.

The acceptance surface of the (dp, tp, pp) scheduling work that is
NOT the policy itself: the shard-map-keyed range pull (a resharding
successor's handoff bytes ~ its shard fraction of the state), the
mesh-shape keying of the AOT compile cache and the delta chain (a
stale dp-shaped executable or delta base must never serve a (dp, tp)
successor), and the bounded divisor-factorized shape grid.
"""

from __future__ import annotations

import json
import os
import pickle

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from adaptdl_tpu import aot_cache, checkpoint, handoff
from adaptdl_tpu.goodput import mesh_shape_grid
from adaptdl_tpu.parallel import create_mesh
from adaptdl_tpu.sched_hints import validate_hints
from adaptdl_tpu.trainer import ElasticTrainer


class LeafState(checkpoint.State):
    """Chunk-capable state with big ndarray leaves (range-addressable
    on the handoff path) and a pluggable shard plan."""

    def __init__(self, name, arrays, plan_fn=None):
        super().__init__(name)
        self.arrays = [np.asarray(a) for a in arrays]
        self.plan_fn = plan_fn
        self.partial_seen = None

    def snapshot(self):
        return [a.copy() for a in self.arrays]

    def write_snapshot(self, snap, fileobj):
        pickle.dump(snap, fileobj)

    def load(self, fileobj):
        self.arrays = pickle.load(fileobj)

    def snapshot_chunks(self, snap):
        return [("treedef", pickle.dumps(len(snap)))] + [
            (f"leaf/{i:05d}", pickle.dumps(a))
            for i, a in enumerate(snap)
        ]

    def load_chunks(self, chunks):
        mapping = dict(chunks)
        n = pickle.loads(mapping["treedef"])
        self.arrays = [
            pickle.loads(mapping[f"leaf/{i:05d}"]) for i in range(n)
        ]

    def handoff_shard_plan(self, chunk_rows):
        if self.plan_fn is None:
            return None
        return self.plan_fn(chunk_rows)

    def load_chunk_rows(self, chunks, partial):
        self.partial_seen = partial
        mapping = dict(chunks)
        n = pickle.loads(mapping["treedef"])
        spans = {
            cid: (lo, hi, rows, arr)
            for cid, lo, hi, rows, arr in partial
        }
        out = []
        for i in range(n):
            cid = f"leaf/{i:05d}"
            if cid in mapping:
                out.append(pickle.loads(mapping[cid]))
                continue
            lo, hi, rows, arr = spans[cid]
            full = np.zeros((rows, *arr.shape[1:]), arr.dtype)
            full[lo:hi] = arr
            out.append(full)
        self.arrays = out


def _arrays(seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.normal(size=(64, 32)).astype(np.float32),
        rng.normal(size=(128, 8)).astype(np.float32),
    ]


@pytest.fixture
def small_parts(monkeypatch):
    # The test leaves are a few KB; drop the production floor so they
    # partition into range-addressable parts.
    monkeypatch.setattr(handoff, "RANGE_PART_MIN_BYTES", 64)
    monkeypatch.setattr(handoff, "RANGE_PARTS", 4)
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", "0")


# ---- shard-map-keyed range pull --------------------------------------


def test_range_pull_bytes_match_shard_fraction(small_parts):
    """Acceptance: a resharding successor pulls ~ its shard fraction
    of the state via the range endpoint — not full leaves — and the
    rows it pulled are bit-identical to the predecessor's."""
    arrays = _arrays()
    src = LeafState("mesh-frac", arrays)
    server = handoff.serve_states(group=-1)
    src.unregister()
    try:
        # Full-pull reference.
        full = LeafState("mesh-frac", [np.zeros_like(a) for a in arrays])
        handoff.set_source(server.url)
        assert handoff.try_restore(full)
        full_bytes = handoff._fetch_stats["bytes"]
        for got, want in zip(full.arrays, arrays):
            np.testing.assert_array_equal(got, want)
        full.unregister()
        handoff._reset_client_state()

        # Quarter-shard successor: bytes ~ 1/4 (part-aligned, so
        # bounded by fraction + one part's slack per leaf).
        frac = LeafState(
            "mesh-frac",
            [np.zeros_like(a) for a in arrays],
            plan_fn=lambda rows: handoff.fraction_plan(rows, 1, 4),
        )
        handoff.set_source(server.url)
        assert handoff.try_restore(frac)
        frac_bytes = handoff._fetch_stats["bytes"]
        assert frac.partial_seen, "range path must have been taken"
        for cid, lo, hi, rows, arr in frac.partial_seen:
            i = int(cid.split("/")[1])
            np.testing.assert_array_equal(arr, arrays[i][lo:hi])
            # The covering range is the planned quarter, part-aligned.
            assert hi - lo <= rows // 4 + rows // 4
        # Strictly less than half of the full pull for a 1/4 plan.
        assert frac_bytes < 0.5 * full_bytes, (frac_bytes, full_bytes)
        frac.unregister()
    finally:
        server.stop()
        handoff._reset_client_state()


def test_range_pull_part_sha_mismatch_falls_back(small_parts):
    """A corrupted part fails its sha256 and the restore falls back
    to storage (returns False here, with no peer-sourced state)."""
    arrays = _arrays()
    src = LeafState("mesh-sha", arrays)
    payload = handoff.collect_chunks([src])
    src.unregister()
    # Server construction computes the part sha table; corrupting the
    # whole-leaf bytes AFTER it means every re-sliced part mismatches
    # the advertised shas (and the whole-leaf sha mismatches too, so
    # the full-pull retry fails the same way).
    server = handoff.HandoffServer(payload, group=-1)
    entry = payload["mesh-sha"]
    bad = _arrays(seed=9)[0]
    entry["chunks"]["leaf/00000"] = pickle.dumps(bad)
    server.start()
    try:
        dst = LeafState(
            "mesh-sha",
            [np.zeros_like(a) for a in arrays],
            plan_fn=lambda rows: handoff.fraction_plan(rows, 0, 2),
        )
        handoff.set_source(server.url)
        assert not handoff.try_restore(dst)
        dst.unregister()
    finally:
        server.stop()
        handoff._reset_client_state()


def test_broken_range_plan_downgrades_to_full_pull(small_parts):
    """The range pull is an optimization: a client-side plan bug (a
    state whose plan outruns its load_chunk_rows) retries as a
    full-leaf pull from the SAME peer instead of marking it
    unavailable and costing the whole process its fast restart."""
    arrays = _arrays()
    src = LeafState("mesh-downgrade", arrays)
    server = handoff.serve_states(group=-1)
    src.unregister()
    try:
        class Broken(LeafState):
            def load_chunk_rows(self, chunks, partial):
                raise RuntimeError("plan bug")

        dst = Broken(
            "mesh-downgrade",
            [np.zeros_like(a) for a in arrays],
            plan_fn=lambda rows: handoff.fraction_plan(rows, 0, 4),
        )
        handoff.set_source(server.url)
        assert handoff.try_restore(dst)
        for got, want in zip(dst.arrays, arrays):
            np.testing.assert_array_equal(got, want)
        # The peer stayed available for later states.
        assert not handoff._unavailable
        dst.unregister()
    finally:
        server.stop()
        handoff._reset_client_state()


def test_full_span_plan_takes_whole_chunk_path(small_parts):
    """A plan covering every row of every leaf is a full pull — the
    normalizer strips it and the bulk path serves (no zero-filling,
    no per-part requests)."""
    arrays = _arrays()
    src = LeafState("mesh-fullspan", arrays)
    server = handoff.serve_states(group=-1)
    src.unregister()
    try:
        dst = LeafState(
            "mesh-fullspan",
            [np.zeros_like(a) for a in arrays],
            plan_fn=lambda rows: {
                cid: (0, n) for cid, n in rows.items()
            },
        )
        handoff.set_source(server.url)
        assert handoff.try_restore(dst)
        assert dst.partial_seen is None  # load_chunks path, not rows
        for got, want in zip(dst.arrays, arrays):
            np.testing.assert_array_equal(got, want)
        dst.unregister()
    finally:
        server.stop()
        handoff._reset_client_state()


def test_manifest_advertises_parts_and_topology(small_parts):
    src = LeafState("mesh-manifest", _arrays())
    try:
        # Partitioning runs at SERVER construction (off the doomed
        # incarnation's drain-critical collect path), not in
        # collect_chunks itself.
        payload = handoff.collect_chunks([src])
        assert all("parts" not in e for e in payload.values())
        handoff.attach_parts(payload)
        entry = payload["mesh-manifest"]
        assert "parts" in entry
        meta = entry["parts"]["leaf/00000"]
        assert meta["rows"] == 64
        assert meta["bounds"][0] == 0 and meta["bounds"][-1] == 64
        assert len(meta["sha"]) == len(meta["bounds"]) - 1
        # treedef is tiny -> never partitioned.
        assert "treedef" not in entry["parts"]
    finally:
        src.unregister()


def test_peer_topology_visible_to_successor(small_parts, monkeypatch):
    monkeypatch.setenv("ADAPTDL_MODEL_SHARDS", "2")
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "8")
    from adaptdl_tpu import metrics

    monkeypatch.setattr(metrics, "_active_topology", None)
    src = LeafState("mesh-topo", _arrays())
    server = handoff.serve_states(group=-1)
    src.unregister()
    try:
        dst = LeafState("mesh-topo", _arrays(seed=1))
        handoff.set_source(server.url)
        assert handoff.try_restore(dst)
        assert handoff.peer_topology() == [4, 1, 2, 1, 1]
        dst.unregister()
    finally:
        server.stop()
        handoff._reset_client_state()


# ---- trainer-level shard plan ----------------------------------------


def test_trainer_checkpoint_shard_plan_restores_planned_rows(
    small_parts, tmp_path, monkeypatch
):
    """A TrainerCheckpoint built with a shard_plan_fn range-pulls and
    re-materializes exactly the planned rows of each big leaf (the
    rest zero-fill — rows a resharded process's devices never read)."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    rng = np.random.default_rng(3)
    dim = 64
    params = {
        "w": jnp.asarray(rng.normal(size=(dim, dim)).astype(np.float32))
    }

    def loss_fn(p, batch, _rng):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    def make_trainer():
        return ElasticTrainer(
            loss_fn, params, optax.sgd(0.1), 8,
            mesh=create_mesh(devices=jax.devices()[:2]),
        )

    t1 = make_trainer()
    holder = {"state": t1.init_state()}
    ck = t1.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
        name="mesh-trainer",
    )
    data = {
        "x": rng.normal(size=(8, dim)).astype(np.float32),
        "y": rng.normal(size=(8, dim)).astype(np.float32),
    }
    step = t1.train_step(4, 0)
    holder["state"], m = step(holder["state"], t1.shard_batch(data))
    jax.block_until_ready(m["loss"])
    w_before = np.asarray(holder["state"].params["w"])

    server = handoff.serve_states(group=-1)
    ck.unregister()
    try:
        t2 = make_trainer()
        holder2 = {"state": t2.init_state()}
        ck2 = t2.make_checkpoint_state(
            lambda: holder2["state"],
            lambda s: holder2.__setitem__("state", s),
            name="mesh-trainer",
            shard_plan_fn=lambda rows: handoff.fraction_plan(
                rows, 0, 2
            ),
        )
        handoff.set_source(server.url)
        assert checkpoint.load_state(ck2)
        w_after = np.asarray(holder2["state"].params["w"])
        np.testing.assert_array_equal(
            w_after[: dim // 2], w_before[: dim // 2]
        )
        # Rows outside this shard's plan were never pulled.
        assert not np.array_equal(
            w_after[dim // 2:], w_before[dim // 2:]
        )
        ck2.unregister()
    finally:
        server.stop()
        handoff._reset_client_state()


# ---- mesh-shape keying of the delta chain ----------------------------


class Chunky(checkpoint.State):
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
        self.parts = {key: pickle.loads(data) for key, data in chunks}


def test_topology_change_forces_full_save(tmp_path, monkeypatch):
    """The delta chain is keyed on the writer's mesh shape: a shape
    change mid-process degrades the next save to a FULL checkpoint
    instead of chaining a (dp, tp) delta onto a dp-shaped base."""
    from adaptdl_tpu import metrics

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "4")
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "8")
    monkeypatch.setattr(metrics, "_active_topology", None)
    state = Chunky("shape-key", {"a": 1, "b": 2})
    try:
        checkpoint.save_all_states()  # full (first of the cadence)
        state.parts["a"] = 10
        checkpoint.save_all_states()  # delta, same shape
        latest = checkpoint.latest_checkpoint_dir()
        manifest = checkpoint.read_manifest(latest)
        assert manifest["kind"] == "delta"
        assert manifest["topology"] == [8, 1, 1, 1, 1]

        # The scheduler reshapes the job: tp=2 on the same chips.
        monkeypatch.setenv("ADAPTDL_MODEL_SHARDS", "2")
        state.parts["a"] = 20
        checkpoint.save_all_states()
        latest = checkpoint.latest_checkpoint_dir()
        manifest = checkpoint.read_manifest(latest)
        assert manifest["kind"] == "full", (
            "a delta must never chain across a mesh-shape change"
        )
        assert manifest["topology"] == [4, 1, 2, 1, 1]
    finally:
        state.unregister()


def test_cross_shape_delta_chain_refused_on_load(
    tmp_path, monkeypatch
):
    """A delta container whose recorded shape differs from its base's
    is refused at load (ValueError inside the chain assembly) and the
    restore falls back version-consistently to the base."""
    from adaptdl_tpu import metrics

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_CKPT_FULL_EVERY", "4")
    monkeypatch.setattr(metrics, "_active_topology", None)
    state = Chunky("shape-load", {"a": 1})
    try:
        checkpoint.save_all_states()  # full base
        state.parts["a"] = 2
        checkpoint.save_all_states()  # delta
        delta_dir = checkpoint.latest_checkpoint_dir()
        path = os.path.join(delta_dir, "shape-load")
        with open(path, "rb") as f:
            container = pickle.load(f)
        assert container["format"] == "chunked-delta"
        container["topology"] = [2, 1, 4, 1, 1]  # forged shape
        blob = pickle.dumps(container)
        with open(path, "wb") as f:
            f.write(blob)
        # Keep the dir's integrity manifest consistent so the ONLY
        # failing check is the mesh-shape key.
        manifest_path = os.path.join(delta_dir, "manifest.json")
        with open(manifest_path, encoding="utf-8") as f:
            manifest = json.load(f)
        manifest["states"]["shape-load"]["sha256"] = (
            checkpoint._chunk_sha(blob)
        )
        manifest["states"]["shape-load"]["bytes"] = len(blob)
        with open(manifest_path, "w", encoding="utf-8") as f:
            json.dump(manifest, f, sort_keys=True)

        with pytest.raises(ValueError, match="cross-shape"):
            checkpoint._load_payload(
                str(tmp_path), delta_dir, state
            )
        # End to end: load_state falls back to the intact full base.
        assert checkpoint.load_state(state)
        assert state.parts == {"a": 1}
    finally:
        state.unregister()


# ---- AOT cache mesh-shape fingerprint --------------------------------


def test_aot_fingerprint_keys_on_mesh_shape(tmp_path, monkeypatch):
    """Acceptance: the compile cache can never serve an executable
    compiled for a different mesh shape — same devices, same program,
    different (dp, tp) factorization => different fingerprint, and a
    cache entry stored under the dp shape misses for the tp trainer."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))

    def loss_fn(p, batch, _rng):
        return jnp.mean((batch["x"] @ p["w"]) ** 2)

    params = {"w": jnp.zeros((8, 8))}

    def trainer_for(mesh):
        return ElasticTrainer(
            loss_fn, params, optax.sgd(0.1), 8, mesh=mesh
        )

    devices = jax.devices()[:4]
    t_dp = trainer_for(create_mesh({"data": 4}, devices=devices))
    t_tp = trainer_for(
        create_mesh({"data": 2, "model": 2}, devices=devices)
    )
    args = ({"w": np.zeros((8, 8), np.float32)},)
    fp_dp = aot_cache.fingerprint(t_dp, ("step", 4, 0), args)
    fp_tp = aot_cache.fingerprint(t_tp, ("step", 4, 0), args)
    assert fp_dp != fp_tp
    # A dp-shaped entry on disk never loads for the tp fingerprint.
    cache_dir = aot_cache.cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    with open(os.path.join(cache_dir, fp_dp), "wb") as f:
        f.write(b"stale dp executable")
    assert aot_cache.load(fp_tp) is None
    # Same factorization, same axes, different axis ORDER is a
    # different program too.
    t_pt = trainer_for(
        create_mesh({"model": 2, "data": 2}, devices=devices)
    )
    assert aot_cache.fingerprint(
        t_pt, ("step", 4, 0), args
    ) != fp_tp


# ---- shape grid ------------------------------------------------------


def test_mesh_shape_grid_dp_only_is_singleton():
    assert mesh_shape_grid() == ((1, 1, 1, 1),)
    assert mesh_shape_grid(num_chips=12) == ((1, 1, 1, 1),)


def test_mesh_shape_grid_divisor_factorized_and_bounded():
    grid = mesh_shape_grid(
        max_model_shards=6, max_stage_shards=2, num_chips=12
    )
    assert grid[0] == (1, 1, 1, 1)
    # Non-pow2 divisor shapes of the chip count are searchable.
    assert (1, 3, 1, 1) in grid
    assert (1, 6, 2, 1) in grid
    # Every shape's group divides the chip count and respects limits.
    for sp, tp, ss, ep in grid:
        assert 12 % (sp * tp * ss * ep) == 0
        assert tp <= 6 and ss <= 2 and sp == 1 and ep == 1
    # Bounded candidate set, pure DP never truncated away.
    capped = mesh_shape_grid(
        max_seq_shards=64, max_model_shards=64, max_stage_shards=64,
        max_expert_shards=64, max_candidates=16,
    )
    assert len(capped) == 16
    assert capped[0] == (1, 1, 1, 1)


def test_mesh_shape_grid_hint_validation():
    hints = {"meshShapeGrid": [[1, 1, 1, 1], [1, 2, 1, 1]]}
    validate_hints(hints)
    with pytest.raises(ValueError, match="meshShapeGrid"):
        validate_hints({"meshShapeGrid": [[1, 2]]})
    with pytest.raises(ValueError, match="meshShapeGrid"):
        validate_hints({"meshShapeGrid": [[0, 1, 1, 1]]})
    with pytest.raises(ValueError, match="meshShapeGrid"):
        validate_hints({"meshShapeGrid": "2x2"})
