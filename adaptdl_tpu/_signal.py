"""Graceful-preemption signal handling.

The scheduler preempts a job by deleting its pods, which delivers
SIGTERM. Rather than dying mid-step, we record the signal in a flag that
the training loop polls once per step; when every replica has observed
it (agreement via an async control-plane allreduce, see
:meth:`adaptdl_tpu.data.AdaptiveDataLoaderHelper.profile`), the job
checkpoints and exits with code 143 so the controller treats it as a
graceful rescale rather than a failure.

(reference: adaptdl/adaptdl/_signal.py:29-42; exit-143 convention at
sched/adaptdl_sched/controller.py:276-283.)
"""

from __future__ import annotations

import signal
import time

GRACEFUL_EXIT_CODE = 143

# A bare boolean: loads/stores are atomic in CPython and the handler runs
# on the main thread between bytecodes, so taking a lock here could
# deadlock against main-thread readers instead of protecting them.
_exit_flag = False
# Wall clock of the FIRST signal (or programmatic set): where the
# rescale's trace starts. A bare float for the same reason as the flag;
# the handler does these two stores and nothing else, and
# ``data._check_exit`` turns the time into the ``exit.agree`` span.
_signal_time: float | None = None
_installed = False


def _handler(signum, frame):  # noqa: ARG001 - signal handler signature
    global _exit_flag, _signal_time
    if _signal_time is None:
        _signal_time = time.time()
    _exit_flag = True


def install_handlers() -> None:
    """Install SIGTERM/SIGINT handlers (idempotent, main thread only)."""
    global _installed
    if _installed:
        return
    signal.signal(signal.SIGTERM, _handler)
    signal.signal(signal.SIGINT, _handler)
    _installed = True


def get_exit_flag() -> bool:
    """True once a termination signal has been received."""
    return _exit_flag


def signal_time() -> float | None:
    """Wall clock (``time.time()``) at which the exit flag was first
    raised, or None while it is down."""
    return _signal_time


def set_exit_flag(value: bool = True) -> None:
    """Set the flag programmatically (tests, in-process rescale, the
    preemption-notice listener); raising it stamps the signal time as
    the handler does, lowering it clears the time."""
    global _exit_flag, _signal_time
    if not value:
        _signal_time = None
    elif _signal_time is None:
        _signal_time = time.time()
    _exit_flag = value
