"""Builder's tool: record the SMALL trace that ``test_xplane.py`` checks
the reduction against, on whatever chips the machine has.

    chiprun --chips 4 -- python benchmark/tests/record_trace.py

A few steps of a tiny data-parallel program (a matmul, a gradient
``pmean`` over the chips, an update) with the benchmark's own
annotations around a sleeping "loader" and the dispatch, written to
``chiprun_out/recorded_v5e.xplane.pb`` with the numbers the reduction
reads from it (``recorded_v5e.expected.json``). Copy both into
``benchmark/tests/data/``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import xplane

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))

    def per_replica(w, x):
        grad = jax.grad(lambda w: jnp.tanh(x @ w).sum())(w)
        grad = jax.lax.pmean(grad, "data")
        return w - 1e-3 * grad

    step = jax.jit(
        jax.shard_map(
            per_replica, mesh=mesh,
            in_specs=(P(), P("data")), out_specs=P(),
        )
    )
    w = jax.device_put(
        jnp.ones((2048, 2048), jnp.float32), NamedSharding(mesh, P())
    )
    x = jax.device_put(
        jnp.ones((len(devices) * 512, 2048), jnp.float32),
        NamedSharding(mesh, P("data")),
    )
    jax.block_until_ready(step(w, x))
    out = tempfile.mkdtemp(prefix="record-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.slice"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench.data_next"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.run_step"):
                w = step(w, x)
        jax.block_until_ready(w)
    jax.profiler.stop_trace()
    (found,) = glob.glob(
        os.path.join(out, "plugins", "profile", "*", "*.xplane.pb")
    )
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    target = os.path.join(dest, "recorded_v5e.xplane.pb")
    shutil.copy(found, target)
    trace = xplane.load(target)
    name, runs, mean_s = trace.step_program()
    seconds, exposed, events = trace.matching_s(re.compile("all-reduce"))
    expected = {
        "device_kind": devices[0].device_kind,
        "chips": len(trace.devices),
        "window_s": trace.window_s(),
        "busy_s": trace.busy_s(),
        "step_program": name,
        "step_runs": runs,
        "step_mean_s": mean_s,
        "top_op": trace.top_ops(3)[0][0],
        "allreduce_s": seconds,
        "allreduce_exposed_s": exposed,
        "allreduce_events": events,
        "idle_gaps": trace.idle_gaps(),
        "lines": trace.lines_seen,
    }
    with open(os.path.join(dest, "recorded_v5e.expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected, indent=1))
    print("size", os.path.getsize(target))
    names = sorted(
        {xplane.op_kind(e.name) for d in trace.devices
         for e in d.ops + d.async_ops}
    )
    print("op kinds:", names)
    shutil.rmtree(out, ignore_errors=True)


if __name__ == "__main__":
    main()
