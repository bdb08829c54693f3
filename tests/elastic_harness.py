"""Forked multi-replica harness for elastic tests.

One test exercises a full save -> kill -> restart-with-different-replica
-count -> load -> resume cycle on one machine: the harness forks
``num_replicas`` processes with a complete fake ``ADAPTDL_*``
environment sharing one checkpoint directory; whatever integer rank 0's
invocation returns becomes the replica count for the next simulated
restart (falsy return ends the test). This mirrors the reference's
central test fixture (reference: adaptdl/adaptdl/conftest.py:25-100)
with a new fork+pipe implementation.

Children must not touch the JAX device backend unless the parent hasn't
initialised it; control-plane tests (checkpoint/collective/data/epoch)
are pure host Python so fork is safe and fast.
"""

from __future__ import annotations

import os
import pickle
import sys
import traceback

from adaptdl_tpu._compat import pick_unused_port
import pytest


def _run_replica(fn, rank, num_replicas, num_restarts, ckpt_dir, port, write_fd):
    os.environ.update(
        {
            "ADAPTDL_CHECKPOINT_PATH": str(ckpt_dir),
            "ADAPTDL_JOB_ID": "test/elastic",
            "ADAPTDL_MASTER_ADDR": "127.0.0.1",
            "ADAPTDL_MASTER_PORT": str(port),
            "ADAPTDL_REPLICA_RANK": str(rank),
            "ADAPTDL_NUM_REPLICAS": str(num_replicas),
            "ADAPTDL_NUM_PROCESSES": str(num_replicas),
            "ADAPTDL_NUM_NODES": "1",
            "ADAPTDL_NUM_RESTARTS": str(num_restarts),
        }
    )
    # A reducer an earlier test of this pytest process left behind
    # (``collective._require`` makes a single-replica one on demand,
    # ``run_step`` and ``initialize_job`` among its callers) came
    # through the fork: with it ``collective.initialize()`` is a no-op
    # and every replica reduces alone. Whether such a test ran before
    # on this xdist worker is scheduling. Drop it, do not close it:
    # its sockets and threads are the parent's.
    from adaptdl_tpu import collective

    collective._reducer = None
    status = 0
    try:
        result = fn()
        payload = pickle.dumps(("ok", result))
    except BaseException:
        payload = pickle.dumps(("err", traceback.format_exc()))
        status = 1
    with os.fdopen(write_fd, "wb") as f:
        f.write(payload)
    # Skip interpreter teardown: the fork inherited pytest's state.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(status)


def _fork_round(fn, num_replicas, num_restarts, ckpt_dir):
    port = pick_unused_port()
    pipes, pids = [], []
    for rank in range(num_replicas):
        read_fd, write_fd = os.pipe()
        pid = os.fork()
        if pid == 0:
            os.close(read_fd)
            _run_replica(
                fn, rank, num_replicas, num_restarts, ckpt_dir, port, write_fd
            )
        os.close(write_fd)
        pipes.append(read_fd)
        pids.append(pid)
    results = []
    failures = []
    for rank, (pid, read_fd) in enumerate(zip(pids, pipes)):
        with os.fdopen(read_fd, "rb") as f:
            raw = f.read()
        os.waitpid(pid, 0)
        if not raw:
            failures.append(f"replica {rank}: died without reporting")
            continue
        kind, value = pickle.loads(raw)
        if kind == "err":
            failures.append(f"replica {rank}:\n{value}")
        else:
            results.append(value)
    if failures:
        pytest.fail("\n".join(failures))
    return results


@pytest.fixture
def elastic_multiprocessing(tmp_path):
    """Returns run(fn, num_replicas=1): simulate elastic restarts of fn."""

    def run(fn, num_replicas: int = 1, max_restarts: int = 10):
        ckpt_dir = tmp_path / "checkpoint"
        ckpt_dir.mkdir(exist_ok=True)
        history = []
        for num_restarts in range(max_restarts + 1):
            results = _fork_round(fn, num_replicas, num_restarts, ckpt_dir)
            history.append(results)
            requested = results[0]
            if not requested:
                return history
            num_replicas = int(requested)
        raise RuntimeError(f"exceeded {max_restarts} restarts")

    return run
