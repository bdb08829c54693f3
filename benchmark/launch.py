"""Parent-side machinery: start a worker, relay its events, stop it.

The parent never imports jax: a process that has touched JAX holds the
chip, and the worker that needs it then fails or hangs. Workers are
children (``python benchmark/worker.py <spec.json>``) started under
the job environment a launcher would export (``chip_smoke.py``'s
``_job_env``, PR 21, without its ``ADAPTDL_FIT_INTERVAL``: every other
knob stays at the default users get). Each reports through a pipe of
its own; its stdout goes to our stderr so that the result line is the
only thing on stdout.
"""

from __future__ import annotations

import json
import os
import select
import signal
import subprocess
import sys
import time

from benchmark import manifest


class WorkerFailure(Exception):
    pass


def job_env(root: str, ckpt_dir: str, restarts: int, chips: int) -> dict:
    env = dict(os.environ)
    env.update(
        ADAPTDL_CHECKPOINT_PATH=ckpt_dir,
        ADAPTDL_NUM_RESTARTS=str(restarts),
        ADAPTDL_NUM_REPLICAS=str(chips),
        PYTHONPATH=os.pathsep.join(
            p for p in (root, env.get("PYTHONPATH")) if p
        ),
    )
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        # The checkpoint directory is thrown away and the cache's path
        # is part of its key: one fixed directory in the checkout
        # (<root>/.jax_compile_cache), named through the program's own
        # knob.
        env["ADAPTDL_COMPILE_CACHE"] = root
    return env


class Worker:
    """One child process and the events it has sent so far."""

    def __init__(self, root: str, spec: dict, env: dict):
        spec = dict(spec, spawned_at=time.time())
        spec_path = os.path.join(
            spec["work_dir"], f"spec-{spec['role']}.json"
        )
        with open(spec_path, "w", encoding="utf-8") as f:
            json.dump(spec, f)
        read_fd, write_fd = os.pipe()
        self.proc = subprocess.Popen(
            [
                sys.executable,
                "-u",
                manifest.bench_path(root, "worker.py"),
                spec_path,
                str(write_fd),
            ],
            env=env,
            cwd=root,
            stdout=sys.stderr,
            stderr=sys.stderr,
            pass_fds=(write_fd,),
        )
        os.close(write_fd)
        self._fd = read_fd
        self._buffer = b""
        self.events: dict[str, dict] = {}
        self.seen_at: dict[str, float] = {}  # parent's monotonic clock

    def _drain(self, timeout: float) -> bool:
        """Read what the pipe holds; False once the child closed it."""
        ready, _, _ = select.select([self._fd], [], [], timeout)
        if not ready:
            return True
        chunk = os.read(self._fd, 1 << 16)
        now = time.monotonic()
        if not chunk:
            return False
        self._buffer += chunk
        *lines, self._buffer = self._buffer.split(b"\n")
        for line in lines:
            msg = json.loads(line)
            self.events.setdefault(msg["event"], msg)
            self.seen_at.setdefault(msg["event"], now)
        return True

    def wait_for(self, event: str, deadline: float) -> dict:
        """Block until the worker has sent ``event``; its failure, its
        exit or the deadline raise."""
        open_ = True
        while event not in self.events:
            if "error" in self.events:
                raise WorkerFailure(self.events["error"]["error"])
            if not open_:
                raise WorkerFailure(
                    f"worker exited {self.proc.wait()} before {event!r}"
                )
            if time.monotonic() > deadline:
                raise WorkerFailure(f"no {event!r} before the deadline")
            open_ = self._drain(1.0)
        return self.events[event]

    def wait_exit(self, deadline: float) -> int:
        while self._drain(1.0):
            if time.monotonic() > deadline:
                raise WorkerFailure("worker did not exit by the deadline")
        return self.proc.wait(timeout=max(deadline - time.monotonic(), 1))

    def sigterm(self) -> float:
        os.kill(self.proc.pid, signal.SIGTERM)
        return time.monotonic()

    def stop(self) -> None:
        """Never leave a worker behind."""
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None
