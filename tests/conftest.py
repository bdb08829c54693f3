"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip sharding is validated without TPU hardware by forcing the
host platform to present 8 devices, mirroring the reference's strategy
of testing distributed behavior on one machine (reference:
adaptdl/adaptdl/conftest.py). These env vars must be set before the
first ``import jax`` anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

# Under pytest-xdist each worker gets a temp dir of its own (children
# inherit it through TMPDIR): the leak canary below looks for the
# package's stray directories in the temp dir, and in a shared one it
# takes another worker's live ``adaptdl-warmup-*`` for this test's
# leak (23 teardown errors in the driver's run of PR 25, ROADMAP D0).
if os.environ.get("PYTEST_XDIST_WORKER"):
    _worker_tmp = tempfile.mkdtemp(
        prefix=f"pytest-{os.environ['PYTEST_XDIST_WORKER']}-"
    )
    os.environ["TMPDIR"] = _worker_tmp
    tempfile.tempdir = None  # re-read TMPDIR at the next use
    atexit.register(shutil.rmtree, _worker_tmp, ignore_errors=True)

from adaptdl_tpu import checkpoint, trace  # noqa: E402

# Re-exported fixture: forked multi-replica elastic test harness.
from tests.elastic_harness import elastic_multiprocessing  # noqa: E402, F401


@pytest.fixture(autouse=True)
def _clean_state_registry():
    """Isolate the global State registry (and the graftscope trace
    buffer/registry/context) between tests."""
    checkpoint._reset_registry()
    trace._reset_state()
    yield
    checkpoint._reset_registry()
    trace._reset_state()


@pytest.fixture(autouse=True)
def _compile_cache_config_restored():
    """A test that switches the process-wide persistent compile cache
    on (``initialize_job`` -> ``bootstrap._enable_compilation_cache``)
    does so for every test the worker runs after it, and which files
    those are is up to xdist's scheduling: their programs then come
    deserialized from ``.jax_compile_cache`` with ``jit.cache_*``
    events beside them (eight cases of tests/test_trainer.py in the
    driver's run of PR 43's tree, ROADMAP D0). Put jax's config back
    after every test, so each compiles as it would alone."""
    import jax

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs",
    )
    prev = {name: getattr(jax.config, name) for name in names}
    yield
    if prev != {name: getattr(jax.config, name) for name in names}:
        from jax.experimental.compilation_cache import compilation_cache

        for name, value in prev.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


# ---- the chip's compiler without a chip -------------------------------


@pytest.fixture(scope="module")
def v5e():
    """A DESCRIBED (not attached) v5e:2x2 for the TPU's own compiler
    (``tests/test_chip_compile*.py``, ``tests/test_lowered_step_diff.py``)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")


@pytest.fixture
def chip_compile(monkeypatch):
    """Steer the code the way the chip would (the program itself asks
    ``jax.default_backend()``, which is the CPU here), and keep the
    persistent compile cache out of it: a compile for a described chip
    is written to the cache but cannot be read back without a chip, so
    the next one would warn and compile again."""
    import importlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # ``import adaptdl_tpu.ops.flash_attention as m`` yields the FUNCTION
    # (the package re-exports it under the module's name).
    flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")
    monkeypatch.setattr(flash_mod, "_use_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


# ---- per-test resource-leak canary ----------------------------------
#
# The GC14xx lifecycle passes prove every spawn in adaptdl_tpu/ has a
# custodian *statically*; this fixture is the dynamic counterpart. A
# test that leaves a non-daemon thread running, a live child process,
# or a stray adaptdl temp dir behind fails HERE — at the leaking test
# — instead of hanging the pytest process at exit or poisoning an
# unrelated test later in the session. E2e tests that deliberately
# detach (sanctioned via ``# detached:`` in the code under test) opt
# out with ``@pytest.mark.leaks_ok``.

_LEAK_GRACE_S = 2.0
# Temp-dir prefixes owned by the package (checkpoint staging dirs are
# created inside the checkpoint root, not the global tmpdir, so only
# the warmup workdir prefix matters here — keep the tuple extensible).
_ADAPTDL_TMP_PREFIXES = ("adaptdl-warmup-", "adaptdl-tpu-")


def _live_child_pids() -> set:
    """Direct live (non-zombie) children of this process, minus the
    multiprocessing bookkeeping daemons that legitimately persist for
    the whole session (resource_tracker, forkserver)."""
    pids = set()
    task_dir = "/proc/self/task"
    if not os.path.isdir(task_dir):  # non-Linux: canary skips pids
        return pids
    for tid in os.listdir(task_dir):
        try:
            with open(os.path.join(task_dir, tid, "children")) as f:
                pids.update(int(p) for p in f.read().split())
        except (OSError, ValueError):
            continue
    live = set()
    for pid in pids:
        try:
            with open(f"/proc/{pid}/stat") as f:
                state = f.read().rpartition(")")[2].split()[0]
            if state == "Z":  # finished, awaiting reap: not a leak
                continue
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read().replace(b"\0", b" ")
            if (b"resource_tracker" in cmdline
                    or b"forkserver" in cmdline):
                continue
        except OSError:
            continue  # raced with exit
        live.add(pid)
    return live


def _stray_tmp_entries() -> set:
    tmp = tempfile.gettempdir()
    try:
        entries = os.listdir(tmp)
    except OSError:
        return set()
    return {
        e for e in entries if e.startswith(_ADAPTDL_TMP_PREFIXES)
    }


def _leaked_threads(before: set) -> list:
    return [
        t for t in threading.enumerate()
        if t.is_alive()
        and not t.daemon
        and t is not threading.main_thread()
        and t.ident not in before
        # The asyncio default executor's workers belong to the event
        # loop; aiohttp test harnesses tear the loop (and them) down
        # after this fixture runs.
        and not t.name.startswith("asyncio_")
    ]


@pytest.fixture(autouse=True)
def _resource_leak_canary(request):
    if request.node.get_closest_marker("leaks_ok"):
        yield
        return
    before_threads = {t.ident for t in threading.enumerate()}
    before_children = _live_child_pids()
    before_tmp = _stray_tmp_entries()
    yield
    deadline = time.monotonic() + _LEAK_GRACE_S
    while _leaked_threads(before_threads) and (
        time.monotonic() < deadline
    ):
        time.sleep(0.05)
    leaked = _leaked_threads(before_threads)
    assert not leaked, (
        f"test leaked non-daemon thread(s): "
        f"{[t.name for t in leaked]} — join them in teardown or mark "
        f"the test @pytest.mark.leaks_ok"
    )
    while (_live_child_pids() - before_children) and (
        time.monotonic() < deadline
    ):
        time.sleep(0.05)
    children = _live_child_pids() - before_children
    assert not children, (
        f"test leaked live child process(es): {sorted(children)} — "
        f"wait()/terminate them or mark the test "
        f"@pytest.mark.leaks_ok"
    )
    tmp_dirs = _stray_tmp_entries() - before_tmp
    assert not tmp_dirs, (
        f"test leaked temp dir(s) under {tempfile.gettempdir()}: "
        f"{sorted(tmp_dirs)} — clean them up in teardown"
    )
