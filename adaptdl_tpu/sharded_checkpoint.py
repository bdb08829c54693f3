"""Sharded (orbax-backed) TrainState checkpointing with re-sharding.

The pickle-based :class:`~adaptdl_tpu.trainer.TrainerCheckpoint` is
right for data-parallel state (replicated leaves, one writer). Once
state is *sharded* — model-parallel params, ZeRO-split optimizer
moments, or simply too-big-for-one-host models — checkpointing must
write each process's shards and restore onto whatever mesh the next
incarnation builds. That re-shard-on-restore is the capability the
reference never needed (it reloads rank-0 full state,
reference: adaptdl/adaptdl/checkpoint.py:151-156) but a TPU slice
rescale demands.

Design: the named-State registry keeps its small rank-0 byte-stream
(it stores only a pointer + pytree metadata); the tensor payload goes
through orbax into a sibling directory during :meth:`State.sync` —
which the registry already invokes on *every* process before the
rank-0 write, giving sharded saves their all-hosts participation for
free. On restore, orbax materializes each leaf directly into the
sharding the new incarnation requests — device-to-device re-shard
without staging the full state on any single host.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import shutil
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from adaptdl_tpu import checkpoint, env, faults, storage


def _sharded_root() -> str:
    root = env.checkpoint_path()
    assert root is not None, "ADAPTDL_CHECKPOINT_PATH is not set"
    return os.path.join(os.path.abspath(root), "sharded")


def _payload_pattern(name: str) -> re.Pattern:
    # A bare "{name}-g{restart}" (no ".{seq}") is the pre-versioning
    # naming; accept it (as seq 0) so commit() prunes dirs left by
    # older incarnations instead of leaking them forever.
    return re.compile(rf"^{re.escape(name)}-g(\d+)(?:\.(\d+))?$")


def _list_payload_dirs(name: str) -> list[tuple[int, int, str]]:
    """(restart, seq, path) for this state's payload dirs, ascending
    (same versioned-dir contract as the registry — one shared scanner,
    checkpoint.scan_versioned_dirs)."""
    return checkpoint.scan_versioned_dirs(
        _sharded_root(), _payload_pattern(name)
    )


def _next_payload_dir(name: str) -> str:
    """A fresh, versioned payload dir for the save about to happen.

    Every save within an incarnation gets its own ``{name}-g{restart}.
    {seq}`` directory: the payload referenced by the last COMPLETE
    registry checkpoint is never overwritten in place, so a crash at
    any point during the orbax write (or between it and the registry
    rename) leaves the previous checkpoint's payload untouched.
    Deterministic across processes: all processes scan the same shared
    directory in lockstep (sync() runs collectively before the rank-0
    registry write).
    """
    existing = _list_payload_dirs(name)
    seq = checkpoint.next_save_seq(existing, env.num_restarts())
    return os.path.join(
        _sharded_root(), f"{name}-g{env.num_restarts()}.{seq}"
    )


def _hashable_ndarray(data) -> np.ndarray:
    """Materialize a (shard of a) leaf for hashing. Extended dtypes
    (typed PRNG keys) refuse ``np.asarray``; hash their underlying
    integer representation instead."""
    dtype = getattr(data, "dtype", None)
    if dtype is not None and jax.dtypes.issubdtype(
        dtype, jax.dtypes.extended
    ):
        data = jax.random.key_data(data)
    return np.asarray(data)


def shard_hash_table(state) -> dict[str, dict]:
    """Per-shard content hashes of this process's addressable shards:
    ``{"<leaf-path>@<shard-index>": {"sha": ..., "bytes": n}}``. The
    differential-encoding unit for the orbax payload — two payloads'
    tables diffed shard-by-shard tell a successor (and the metrics
    layer) exactly which shards a save actually changed, at per-shard
    rather than per-payload granularity. Keys are process-local by
    construction (each process hashes only the shards it owns), which
    matches orbax's per-process shard files."""
    table: dict[str, dict] = {}
    leaves, _ = jax.tree_util.tree_flatten_with_path(state)
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            for shard in shards:
                data = _hashable_ndarray(shard.data)
                table[f"{key}@{shard.index}"] = {
                    "sha": hashlib.sha256(data.tobytes()).hexdigest(),
                    "bytes": int(data.nbytes),
                }
        else:
            data = _hashable_ndarray(leaf)
            table[f"{key}@full"] = {
                "sha": hashlib.sha256(data.tobytes()).hexdigest(),
                "bytes": int(data.nbytes),
            }
    return table


def diff_shard_tables(
    prev: dict | None, cur: dict
) -> tuple[list[str], int]:
    """Shard keys in ``cur`` whose content differs from (or is absent
    in) ``prev``, plus their total byte volume — the bytes a
    shard-granular transfer would actually have to move. ``prev``
    None (no baseline) marks everything changed."""
    prev = prev or {}
    changed = [
        key
        for key, meta in cur.items()
        if prev.get(key, {}).get("sha") != meta["sha"]
    ]
    return changed, sum(int(cur[key]["bytes"]) for key in changed)


def hash_table_path(payload_dir: str) -> str:
    """The sidecar hash-table file for one payload dir. A sibling
    (not a file inside the dir): orbax owns the dir's contents and
    finalizes it by rename, so the sidecar is written independently
    and pruned alongside the dir in commit()."""
    return f"{payload_dir}.hashes.json"


def load_hash_table(payload_dir: str) -> dict | None:
    """The payload's per-shard hash table, or None when it predates
    shard hashing (or the sidecar is unreadable — hashing is an
    accounting layer, never a restore dependency)."""
    try:
        with open(hash_table_path(payload_dir), encoding="utf-8") as f:
            table = json.load(f)
        return table if isinstance(table, dict) else None
    except (OSError, ValueError):
        return None


class ShardedTrainerCheckpoint(checkpoint.State):
    """Orbax-backed State for (possibly sharded) TrainStates.

    Args:
      name: registry key.
      trainer: the ElasticTrainer whose mesh defines restore placement.
      get_state/set_state: state accessors (same contract as
        TrainerCheckpoint).
      sharding_fn: optional ``leaf_path -> PartitionSpec`` for restore
        placement; default restores everything replicated over the
        trainer's mesh (pure data parallelism).

    Delta/handoff interplay: the registry payload here is a tiny
    pointer, so it rides the delta cadence and the peer-to-peer
    handoff as one opaque chunk — what moves between incarnations is
    the *pointer*, and the tensor payload flows through orbax's own
    per-process shard files with re-shard-on-restore (each process
    writes/reads only its shards, which is already the "pull exactly
    the chunks your new sharding needs" semantics at the storage
    layer). Differential encoding rides alongside orbax's format
    rather than inside it: every save hashes this process's
    addressable shards (``shard_hash_table``) into a sidecar next to
    the payload dir, and the diff against the previous save's table
    (seeded from the restored payload's sidecar after a restart) is
    recorded in the pointer as ``shard_delta`` — so the metrics layer
    and a warm successor can see exactly which shards a save changed
    and how many bytes a shard-granular pull would move, next to the
    full measured payload size (``payload_nbytes``, device bytes
    summed at sync time).
    """

    def __init__(
        self,
        name: str,
        trainer,
        get_state: Callable[[], Any],
        set_state: Callable[[Any], None],
        sharding_fn: Callable[[tuple], P] | None = None,
    ):
        super().__init__(name)
        self._trainer = trainer
        self._get_state = get_state
        self._set_state = set_state
        self._sharding_fn = sharding_fn
        self._last_payload_dir: str | None = None
        self._last_payload_nbytes: int = 0
        # Previous save's per-shard hash table (differential-encoding
        # baseline). Kept on the instance because commit() prunes old
        # payload dirs; re-seeded from the restored payload's sidecar
        # in load() so the first save after a restart diffs against
        # the state it actually restored.
        self._prev_hash_table: dict | None = None
        self._last_shard_delta: dict = {}
        # Orbax checkpointer with its array write still in flight
        # (StandardCheckpointer is an AsyncCheckpointer: save()
        # returns once the on-device data is snapshotted and the
        # write continues in the background).
        self._pending_checkpointer = None

    # -- State protocol ----------------------------------------------

    def _saved_carry_shapes(self, checkpointer, path):
        """Leaf shapes of the payload's gns.prev_grad, from orbax
        metadata, or None when the metadata cannot be read (the
        restore then tries the current canonical layout first and
        falls back to the older ones the storage layout still
        reads)."""
        try:
            tree = checkpointer.metadata(path).item_metadata.tree
            return [
                tuple(leaf.shape)
                for leaf in jax.tree.leaves(
                    tree["gns"]["prev_grad"],
                    is_leaf=lambda x: hasattr(x, "shape"),
                )
            ]
        except Exception:  # noqa: BLE001 - metadata is best-effort
            return None

    def _finish_pending(self) -> None:
        """Join this state's in-flight orbax write, if any. Saves are
        serialized per state so the payload-dir scan (seq allocation)
        always sees every finalized predecessor."""
        pending, self._pending_checkpointer = (
            self._pending_checkpointer, None,
        )
        if pending is not None:
            pending.wait_until_finished()

    def unregister(self) -> None:
        self._finish_pending()
        super().unregister()

    def sync(self) -> None:
        """All processes write their shards via orbax — into a fresh
        versioned directory, never over a payload an existing complete
        checkpoint still references."""
        import orbax.checkpoint as ocp

        self._finish_pending()
        state = self._get_state()
        # RNG keys are opaque; store raw key data alongside.
        state = state._replace(rng=jax.random.key_data(state.rng))
        # Canonical (dp-independent) layout, produced by device
        # collectives and REPLICATED: n is rarely divisible by dp, and
        # a transient params-sized vector stays within the job's
        # existing memory envelope.
        layout = self._trainer.storage
        state = layout.to_canonical(state, storage.on_mesh(layout.mesh))
        path = _next_payload_dir(self.name)
        # Measured payload volume for the metrics layer: logical
        # device bytes summed over leaves (cheap — shape metadata, no
        # host transfer), recorded in the pointer so restartStats can
        # report sharded save bytes alongside the registry's.
        self._last_payload_nbytes = int(
            sum(
                getattr(leaf, "nbytes", 0) or 0
                for leaf in jax.tree.leaves(state)
            )
        )
        # A fault here (kill/latency mid-payload-write) leaves only a
        # fresh versioned dir no registry checkpoint references — the
        # previous complete (pointer, payload) pair stays restorable,
        # and the chaos suite proves it.
        if env.sharded_hash_enabled():
            # Differential encoding: hash this process's addressable
            # shards (one host transfer per save;
            # ADAPTDL_SHARDED_HASHES=off where that dominates) and diff
            # against the previous save, so the pointer records which
            # shards actually changed.
            table = shard_hash_table(state)
            changed, changed_bytes = diff_shard_tables(
                self._prev_hash_table, table
            )
            self._last_shard_delta = {
                "shards_total": len(table),
                "shards_changed": len(changed),
                "changed_bytes": int(changed_bytes),
            }
            self._prev_hash_table = table
        else:
            self._last_shard_delta = {}
        faults.maybe_fail("ckpt.sharded.payload")
        checkpointer = ocp.StandardCheckpointer()
        checkpointer.save(path, state)
        if env.sharded_hash_enabled() and jax.process_index() == 0:
            # Sidecar, not a file inside the payload dir: orbax owns
            # that dir and finalizes it by rename. Best-effort — the
            # table is accounting, never a restore dependency.
            try:
                os.makedirs(_sharded_root(), exist_ok=True)
                with open(
                    hash_table_path(path), "w", encoding="utf-8"
                ) as f:
                    json.dump(self._prev_hash_table, f)
            except OSError:
                pass
        if env.num_processes() > 1:
            # Multi-host: every process must finish its shards before
            # rank 0's registry rename can reference the payload — the
            # non-rank-0 processes have no later pipeline point to
            # wait at, so the overlap is single-host only.
            checkpointer.wait_until_finished()
        else:
            # Single-host: defer the wait to the write phase
            # (write_snapshot below), overlapping the orbax array
            # write with training's next steps. The registry pointer
            # is only written after the payload is fully durable, so
            # the newest complete registry checkpoint always
            # references a complete payload.
            self._pending_checkpointer = checkpointer
        self._last_payload_dir = path

    def snapshot(self):
        snap = {
            "payload_dir": self._last_payload_dir,
            "payload_nbytes": self._last_payload_nbytes,
        }
        if self._last_shard_delta:
            snap["shard_delta"] = dict(self._last_shard_delta)
        return snap

    def write_snapshot(self, snapshot, fileobj) -> None:
        self._finish_pending()
        pickle.dump(snapshot, fileobj)

    def save(self, fileobj) -> None:
        self.write_snapshot(self.snapshot(), fileobj)

    def commit(self) -> None:
        """Registry rename succeeded: every payload dir other than the
        one just written is now unreferenced (the registry pruned all
        older checkpoint dirs in the same step) — drop them, including
        orphans from crashed incarnations."""
        keep = self._last_payload_dir
        for _, _, path in _list_payload_dirs(self.name):
            if path != keep:
                shutil.rmtree(path, ignore_errors=True)
                try:
                    os.remove(hash_table_path(path))
                except OSError:
                    pass

    def load(self, fileobj) -> None:
        import orbax.checkpoint as ocp

        meta = pickle.load(fileobj)
        path = meta["payload_dir"]
        # Seed the differential baseline from the restored payload's
        # sidecar: the first save of this incarnation then reports
        # only what training actually changed since the restore.
        self._prev_hash_table = load_hash_table(path)
        template = self._get_state()
        template = template._replace(
            rng=jax.random.key_data(template.rng)
        )
        layout = self._trainer.storage
        mesh = layout.mesh
        repl = NamedSharding(mesh, P())

        def abstract(path_, leaf):
            spec = P() if self._sharding_fn is None else (
                self._sharding_fn(path_)
            )
            return jax.ShapeDtypeStruct(
                np.shape(leaf), leaf.dtype,
                sharding=NamedSharding(mesh, spec),
            )

        # The payload holds the canonical layout sync() wrote: its
        # abstract form is the transform's own, traced.
        target = jax.tree_util.tree_map_with_path(
            abstract,
            jax.eval_shape(
                lambda s: layout.to_canonical(s, storage.inline), template
            ),
        )
        checkpointer = ocp.StandardCheckpointer()
        # Align the prev_grad target with the SAVED layout, read from
        # the payload's metadata: the canonical one, or one from before
        # it that the layout still reads.
        carries = [target.gns.prev_grad] + [
            jax.tree.map(
                lambda leaf: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=repl
                ),
                carry,
            )
            for carry in layout.legacy_carries()
        ]
        saved = (
            self._saved_carry_shapes(checkpointer, path)
            if len(carries) > 1
            else None
        )
        if saved is not None:
            carries = [
                c for c in carries
                if [leaf.shape for leaf in jax.tree.leaves(c)] == saved
            ] or carries[:1]
        # Metadata unreadable (likely an older payload): try each
        # layout, current first — re-raising the ORIGINAL error if
        # none fits.
        first_err = None
        for carry in carries:
            try:
                restored = checkpointer.restore(
                    path,
                    target._replace(
                        gns=target.gns._replace(prev_grad=carry)
                    ),
                )
                break
            except Exception as err:  # noqa: BLE001 - re-raised below
                first_err = first_err or err
        else:
            raise first_err
        restored = layout.from_canonical(restored, storage.on_mesh(mesh))
        restored = restored._replace(
            rng=jax.random.wrap_key_data(restored.rng)
        )
        self._set_state(restored)
