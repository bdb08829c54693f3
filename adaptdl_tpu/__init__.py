"""adaptdl_tpu — a TPU-native elastic deep-learning training framework.

A ground-up JAX/XLA re-design with the capabilities of petuum/adaptdl
(the OSDI'21 "Pollux" system): adaptive batch sizing driven by a goodput
model (throughput x statistical efficiency), gradient-noise-scale-aware
learning-rate scaling, checkpoint-restart elasticity across TPU slice
sizes, and a Pollux-style cluster scheduler.

Where the reference instruments PyTorch with backward hooks and wraps
DistributedDataParallel (reference: adaptdl/adaptdl/torch/parallel.py),
this framework folds everything into a single jitted train step over a
``jax.sharding.Mesh``: gradients are averaged with ``lax.pmean`` over the
"data" mesh axis (ICI/DCN instead of NCCL), and the gradient-noise-scale
statistics are computed inside the same step as two extra scalar
reductions instead of 330 lines of hook machinery.

Public subpackage map (mirrors the reference component inventory,
SURVEY.md section 2):

- :mod:`adaptdl_tpu.env` — ADAPTDL_* environment configuration.
- :mod:`adaptdl_tpu.checkpoint` — named-State registry, atomic
  restart-indexed checkpoint dirs, replay on restart.
- :mod:`adaptdl_tpu.collective` / :mod:`adaptdl_tpu.reducer` — control
  plane object allreduce/broadcast (host side, tiny payloads).
- :mod:`adaptdl_tpu.goodput` — the goodput model and perf-param fitting.
- :mod:`adaptdl_tpu.trainer` — ElasticTrainer: the jitted elastic
  data-parallel train step (the AdaptiveDataParallel equivalent).
- :mod:`adaptdl_tpu.data` — ElasticSampler + AdaptiveDataLoader.
- :mod:`adaptdl_tpu.epoch`, :mod:`adaptdl_tpu.accumulator` — replay-safe
  epoch loop and metric accumulation.
- :mod:`adaptdl_tpu.sched` — Pollux policy + cluster components.
"""

import sys as _sys
import time as _time

# What importing this package costs a (re)starting worker, for the
# ``boot.import`` span that ``initialize_job`` records: the clocks and
# the size of ``sys.modules`` here and on the last line, and whether
# the script had imported jax before us.
_boot = {
    "start": _time.time(),
    "mono": _time.monotonic(),
    "modules": len(_sys.modules),
    "jax_preloaded": "jax" in _sys.modules,
}

__version__ = "0.1.0"

from adaptdl_tpu import env  # noqa: E402,F401
from adaptdl_tpu.bootstrap import initialize_job  # noqa: E402,F401

_boot["seconds"] = _time.monotonic() - _boot["mono"]
_boot["modules"] = len(_sys.modules) - _boot["modules"]
