"""Persistent AOT-executable cache: restarts skip retracing.

The XLA persistent compilation cache (bootstrap._enable_compilation_
cache) only caches the *backend compile*; a restarted incarnation
still pays Python tracing + jaxpr lowering for every train-step
configuration before its first step — the dominant term of a set-up
(``trace_lower_s``, PERF.md section 3). This module caches the
step at the level above: the fully compiled executable, serialized
with ``jax.experimental.serialize_executable``, keyed by a
fingerprint of everything that determines the program. A restarted
incarnation with the same topology deserializes and runs — no trace,
no lower, no compile.

Scope and safety:

- The cache lives under the job's shared checkpoint directory
  (``{ADAPTDL_CHECKPOINT_PATH}/.jax_aot_cache``; ``ADAPTDL_AOT_CACHE``
  overrides the location, ``off`` disables), so entries are private to
  one job — the same script across that job's restarts.
- The fingerprint pins the jax version, backend + device kinds, mesh
  axes, trainer configuration, the loss function's bytecode, and the
  full aval/sharding signature of (state, batch, aux). A rescale that
  changes the device count misses (different mesh) and falls back to
  a normal compile; only same-topology restarts — failure recovery,
  preemption-return, and the save->restore->first-step path — hit.
- Entries are written atomically (tmp + rename); serialization runs
  on the caller's thread (the runtime client is not safe to touch
  concurrently with compilation), only the file write is backgrounded,
  and the directory is pruned to a bounded entry count.
- Cached programs are compiled WITHOUT input donation: a deserialized
  executable's input-aliasing metadata is not reliably reconstructed
  across processes, and executing one with donated buffers corrupts
  memory. The cost is one extra state-sized buffer per step on the
  cached path (1/dp-sized under the ZeRO modes).
- Single-controller only: multi-process jobs never use the cache
  (per-process deserialization of one SPMD executable is not worth
  the coordination risk).

A cache hit or a corrupt entry can never break training: any failure
deserializing or executing falls back to the ordinary jitted path
(``ElasticTrainer._aot_wrap`` awaits a deserialized executable's first
run inside its handler, because dispatch is asynchronous), logs a
WARNING with the traceback, and drops the entry.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import tempfile
import threading
import time
from typing import Any

LOG = logging.getLogger(__name__)

# Bounded disk footprint: entries beyond this are pruned oldest-first.
_MAX_ENTRIES = 32


def cache_dir() -> str | None:
    """Resolved cache directory, or None when disabled/unconfigured."""
    from adaptdl_tpu import env

    knob = env.aot_cache_knob()
    if knob.lower() in ("off", "0", "false", "none"):
        return None
    if knob:
        base = knob
    else:
        base = env.checkpoint_path()
        if base is None:
            return None
    return os.path.join(os.path.abspath(base), ".jax_aot_cache")


def enabled() -> bool:
    from adaptdl_tpu import env

    if env.num_processes() > 1:
        return False
    return cache_dir() is not None


def _code_hash(fn: Any) -> str:
    """Best-effort hash of a callable's bytecode (plus nested code
    objects): catches the common loss-function edit between runs that
    reuse a checkpoint dir. Closure *values* (e.g. model configs) are
    not captured — those change the aval signature instead."""
    try:
        stack = [fn.__code__]
        digest = hashlib.sha256()
        while stack:
            code = stack.pop()
            digest.update(code.co_code)
            for const in code.co_consts:
                if hasattr(const, "co_code"):
                    stack.append(const)
                else:
                    digest.update(repr(const).encode())
        return digest.hexdigest()
    except Exception:  # noqa: BLE001 - builtins, partials, callables
        return "nocode"


def _describe_tree(tree: Any) -> str:
    import jax
    import numpy as np

    def describe(leaf):
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", sharding)
        return (
            str(np.shape(leaf)),
            str(getattr(leaf, "dtype", type(leaf).__name__)),
            str(spec),
        )

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return repr((str(treedef), [describe(leaf) for leaf in leaves]))


def fingerprint(trainer: Any, key: tuple, args: tuple) -> str:
    """Cache key: everything that determines the compiled program."""
    import jax

    mesh = trainer.mesh
    parts = [
        jax.__version__,
        jax.default_backend(),
        repr(
            sorted(
                {
                    (d.platform, d.device_kind)
                    for d in mesh.devices.flat
                }
            )
        ),
        # Together these two parts pin the NAMED mesh shape —
        # mesh.shape is exactly zip(axis_names, devices.shape) — so an
        # executable compiled for one (dp, sp, tp, ss, ep)
        # factorization can never serve a successor that rescaled to a
        # different shape over the same device count (the collectives
        # baked into the program are shape-specific). The mesh-shape
        # fingerprint test in tests/test_mesh_reshard.py enforces the
        # invariant.
        repr(mesh.devices.shape),
        repr(tuple(mesh.axis_names)),
        repr(key),
        repr(
            (
                trainer.init_batch_size,
                type(trainer.scaling_rule).__name__,
                trainer.precondition,
                trainer.smoothing,
                trainer.has_aux,
                trainer.storage.name,
                trainer.num_param_groups,
                trainer.pipeline_micro,
                trainer._group_ids,
            )
        ),
        _code_hash(trainer.loss_fn),
        _describe_tree(args),
    ]
    return hashlib.sha256("\x1f".join(parts).encode()).hexdigest()


def load(fp: str) -> Any | None:
    """Deserialize a cached executable; None on miss or any failure."""
    directory = cache_dir()
    if directory is None:
        return None
    path = os.path.join(directory, fp)
    if not os.path.isfile(path):
        return None
    try:
        from jax.experimental.serialize_executable import (
            deserialize_and_load,
        )

        start = time.monotonic()
        with open(path, "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        compiled = deserialize_and_load(payload, in_tree, out_tree)
        LOG.info(
            "AOT cache hit %s (%.3fs) — first step skips retracing",
            fp[:12],
            time.monotonic() - start,
        )
        return compiled
    except Exception:  # noqa: BLE001 - a stale/corrupt entry
        LOG.warning("unreadable AOT cache entry %s", fp[:12], exc_info=True)
        discard(fp)
        return None


def discard(fp: str) -> None:
    """Drop an entry that could not be read or run, so the next
    incarnation recompiles instead of tripping over it again."""
    directory = cache_dir()
    if directory is None:
        return
    try:
        os.remove(os.path.join(directory, fp))
    except OSError:
        pass


# In-flight background writers, so tests and the bench can wait for
# entries to land deterministically (a real restarted process never
# needs this — its entries were written by the previous incarnation).
# Mutated by every save_async caller AND drained by wait_for_writes
# from tests/atexit; graftcheck enforces the lock (GC101).
_writers: list[threading.Thread] = []  # guarded-by: _writers_lock
_writers_lock = threading.Lock()  # lock-order: 41
_atexit_registered = False


def _ensure_atexit_join() -> None:
    """Join in-flight writers at interpreter exit: a daemon thread
    killed mid-``serialize_executable`` call aborts the process with a
    C++ error — which would turn a graceful exit-143 rescale into a
    crash the controller counts against the failure budget."""
    global _atexit_registered
    if _atexit_registered:
        return
    _atexit_registered = True
    import atexit

    atexit.register(wait_for_writes, 60.0)


def wait_for_writes(timeout: float | None = None) -> None:
    with _writers_lock:
        pending = list(_writers)
        _writers.clear()
    for thread in pending:
        thread.join(timeout)


def save_async(fp: str, compiled: Any) -> threading.Thread | None:
    """Persist an executable: serialize NOW on the caller's thread
    (``serialize_executable`` reaches into the runtime client, which
    is not safe to run concurrently with compilation on another
    thread), then pickle + write — pure Python I/O — in the
    background with an atomic rename. Failures only cost the cache
    entry."""
    directory = cache_dir()
    if directory is None:
        return None
    try:
        from jax.experimental.serialize_executable import serialize

        entry = serialize(compiled)
    except Exception:  # noqa: BLE001 - cache is an optimization
        LOG.warning("AOT executable serialization failed", exc_info=True)
        return None

    def _write() -> None:
        try:
            os.makedirs(directory, exist_ok=True)
            fd, tmp = tempfile.mkstemp(
                prefix="_tmp-aot-", dir=directory
            )
            with os.fdopen(fd, "wb") as f:
                pickle.dump(entry, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, os.path.join(directory, fp))
            _prune(directory)
        except Exception:  # noqa: BLE001 - cache is an optimization
            LOG.warning("AOT cache write failed", exc_info=True)

    thread = threading.Thread(
        target=_write, name="adaptdl-aot-writer", daemon=True
    )
    with _writers_lock:
        _writers[:] = [t for t in _writers if t.is_alive()]
        _writers.append(thread)
    _ensure_atexit_join()
    thread.start()
    return thread


def _prune(directory: str) -> None:
    """Keep the newest _MAX_ENTRIES entries (and drop stale tmps)."""
    try:
        entries = []
        for name in os.listdir(directory):
            path = os.path.join(directory, name)
            if name.startswith("_tmp-aot-"):
                # graftcheck: disable=GC701 (file mtimes are wall-clock
                # values; comparing them against monotonic time would
                # be wrong, and no span measures this housekeeping)
                if time.time() - os.path.getmtime(path) > 3600:
                    os.remove(path)
                continue
            entries.append((os.path.getmtime(path), path))
        entries.sort(reverse=True)
        for _, path in entries[_MAX_ENTRIES:]:
            os.remove(path)
    except OSError:  # pragma: no cover - concurrent prune
        pass


def load_or_compile(trainer: Any, key: tuple, jitted: Any, args: tuple):
    """The train step's first-call path: return a cached executable if
    the fingerprint hits, else AOT-compile through ``jitted`` and
    persist the result in the background. Returns ``(compiled,
    hit_fingerprint)``; the fingerprint is None on a miss, and on a
    hit names the entry the caller should :func:`discard` if the
    deserialized executable turns out not to run."""
    from adaptdl_tpu import trace

    fp = fingerprint(trainer, key, args)
    with trace.span("aot.lookup", fingerprint=fp[:12]) as attrs:
        compiled = load(fp)
        attrs["hit"] = compiled is not None
    if compiled is not None:
        trace.event("aot.hit")
        return compiled, fp
    trace.event("aot.miss")
    # Whether jax's persistent compile cache served this compile: the
    # bridge counts its hits (tests and tools come here without
    # initialize_job having installed it).
    trace.install_jax_bridge()
    pc_hits = trace.jax_cache_hits()
    with trace.span("aot.compile", fingerprint=fp[:12]) as attrs:
        compiled = jitted.lower(*args).compile()
        from_pc = trace.jax_cache_hits() != pc_hits
        attrs["persistent_cache_hit"] = from_pc
    import jax

    if from_pc and jax.default_backend() == "cpu":
        # XLA:CPU (jaxlib 0.9.0) cannot serialize an executable that
        # the persistent cache deserialized: the entry it writes loads
        # and then fails at run time with "Function ... not found". On
        # the CPU only an executable this process compiled is cached;
        # the persistent cache serves the next incarnation anyway. (A
        # TPU v5e round-trips such an executable correctly.)
        LOG.info(
            "step %s came from the persistent compile cache; not "
            "re-serializing it into the AOT cache", key,
        )
    else:
        save_async(fp, compiled)
    return compiled, None
