"""What the trainer tells a program it is TRACING about the device's
free bytes, for code that can trade memory for recomputation
(``models.transformer.block_remat``).

The trainer sets it around the first call of every step program and
of the calibration program (``ElasticTrainer._aot_wrap``,
``calibrate_accum_time``; the numbers are ``_activations``'), which is
when jax traces them. Outside a trainer, and where the device does
not say what its allocator may hand out (the CPU), there is none, and
a model is traced as it would be without this module.

The two numbers are a pure function of what every program of a job
and the job's successor share — the device's ``bytes_limit``, the
train state's shapes and storage layout, whether the job runs the
non-donating twin — never of what happens to be allocated when a
program is traced (``bytes_in_use``): a successor that traces a model
itself has to make the choice its predecessor made.
"""

from __future__ import annotations

import contextlib
from contextvars import ContextVar
from typing import NamedTuple


class Activations(NamedTuple):
    # ``bytes_limit`` less the copies of train state and gradient a
    # step program holds and the trainer's reserve: what is left for
    # everything a model keeps alive itself. May be negative.
    free_bytes: int
    bytes_limit: int


_ACTIVATIONS: ContextVar[Activations | None] = ContextVar(
    "adaptdl_tpu_activations", default=None
)


def activations() -> Activations | None:
    """The budget of the program being traced; ``None`` where nobody
    set one."""
    return _ACTIVATIONS.get()


def enter(budget: Activations | None):
    """Set the budget; hand the result to ``leave``."""
    return _ACTIVATIONS.set(budget)


def leave(token) -> None:
    _ACTIVATIONS.reset(token)


@contextlib.contextmanager
def tracing_with(budget: Activations | None):
    token = enter(budget)
    try:
        yield
    finally:
        leave(token)
