"""Rehearsal without the chip, with the chip's memory limit: a
benchmark cell's train step as THE TRAINER would run it on a 16 GB
v5e, compiled by the TPU's own compiler for a described v5e.

``benchmark/tests/compile_v5e.py`` / ``compile_cell_v5e.py`` rebuild
the step from ``step._jitted.__wrapped__``, and a described device
reports no ``bytes_limit``, so what the trainer decides from the
limit — whether the step donates its state, and how far a remat'd
block climbs its ladder (``models.transformer.block_remat``) — is not
in the program they compile. Here the limit is injected (15.75 GiB,
what a v5e's allocator reports), the AOT cache is on as under any job
with a checkpoint path, and the program is the one of the pair in
``ElasticTrainer._finalize_step`` that the donation rule's first
reading picks, traced as ``_aot_wrap`` traces it. Nothing runs; a
compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python tools/compile_step_v5e.py gpt2-124m-steady \
        [--chips 4] [--atomic 16 --accum 1] [--text FILE] [--lower-only]
"""

from __future__ import annotations

import argparse
import importlib
import os
import re
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BYTES_LIMIT = int(15.75 * 2**30)
# How the TPU compiler writes an all-reduce that runs beside the op
# scheduled next to it: three fusions, ``%async-collective-start`` (it
# holds the all-reduce), the op it runs under (``calls=
# %async_collective_fusion``) and ``%async-collective-done``.
ASYNC_START = r"^\s*%async-collective-start[.\d]* = "


def entry_computation(text: str) -> str:
    """The instructions of a compiled module's entry computation, in
    the order they are scheduled."""
    return re.search(
        r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.M | re.S
    ).group(1)


def inject_limit(bytes_limit, set_attribute=setattr):
    """The described device's answer about its memory (it has none);
    a test passes its ``monkeypatch.setattr``."""
    from adaptdl_tpu import trainer as trainer_mod

    set_attribute(
        trainer_mod, "_memory_stats",
        lambda device: {} if bytes_limit is None else {
            "bytes_limit": bytes_limit
        },
    )


def step_program(cell_name, chips=None, atomic=None, accum=None,
                 bytes_limit=BYTES_LIMIT, topo=None):
    """-> (lower, facts): ``lower()`` lowers the cell's step as the
    trainer would trace it with ``bytes_limit`` a device (None: a
    device that does not say), on abstract arguments on the described
    chips. The caller keeps ``inject_limit`` in place until then: the
    trainer asks the device when a program is TRACED."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from adaptdl_tpu import device_budget
    from adaptdl_tpu.parallel import mesh as mesh_mod
    from benchmark import manifest

    cell = manifest.load_cell(cell_name)
    config = manifest.load_module(cell.config_py)
    chips = chips or cell.chips
    geometry = dict(cell.workload["geometry"])
    if atomic is not None:
        geometry.update(atomic_bsz=atomic, accum_steps=accum)
    geometry["global_batch"] = (
        chips * geometry["atomic_bsz"] * (geometry["accum_steps"] + 1)
    )
    topo = topo or topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    os.environ["ADAPTDL_NUM_REPLICAS"] = str(chips)
    # Abstract weights (nothing can be placed on a described device:
    # build()'s jax.jit(init) becomes eval_shape), the described mesh
    # as the default one.
    real_jit, real_mesh = jax.jit, mesh_mod.create_mesh_from_topology
    jax.jit = lambda f, **kw: (
        lambda *a: jax.eval_shape(f, *a)
    ) if getattr(f, "__name__", "") == "<lambda>" else real_jit(f, **kw)
    mesh_mod.create_mesh_from_topology = lambda **kw: mesh
    try:
        trainer = config.build(cell.sizes, geometry, 0)["trainer"]
    finally:
        jax.jit, mesh_mod.create_mesh_from_topology = real_jit, real_mesh
    state = trainer._abstract_state()
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)
        ),
        state, trainer.state_spec_tree(state),
    )
    batch = {
        k: jax.ShapeDtypeStruct(
            (geometry["global_batch"], config.units_per_sample(cell.sizes)),
            jnp.int32, sharding=NamedSharding(mesh, P("data")),
        )
        for k in ("inputs", "targets")
    }
    # What ``_aot_wrap`` does at the step's first call under the AOT
    # cache: the donation rule's first reading (``_second_state_fits``),
    # then the twin traced under the bytes the device has free, or the
    # donating step under no budget.
    step = trainer.train_step(geometry["atomic_bsz"], geometry["accum_steps"])
    sharded = step._jitted.__wrapped__
    donated = (
        bytes_limit is not None and 2 * trainer._held_bytes > bytes_limit
    )
    # (With the compiler options ``_finalize_step`` gives both of the
    # pair: what a job of several replicas compiles its reduce under.)
    options = trainer._reduce_overlap_options()
    jitted = jax.jit(
        sharded, donate_argnums=0 if donated else (),
        compiler_options=options,
    )

    def lower():
        with device_budget.tracing_with(
            None if donated else trainer._activations()
        ):
            return jitted.lower(state, batch, ())

    facts = {
        "chips": chips, "geometry": geometry, "donated": donated,
        "held_bytes": trainer._held_bytes,
    }
    return lower, facts


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("cell")
    parser.add_argument("--chips", type=int)
    parser.add_argument("--atomic", type=int)
    parser.add_argument("--accum", type=int, default=1)
    parser.add_argument("--text")
    parser.add_argument("--no-limit", action="store_true")
    parser.add_argument("--lower-only", action="store_true")
    args = parser.parse_args()

    import jax

    from adaptdl_tpu import trace

    jax.config.update("jax_enable_compilation_cache", False)
    for name in ("flash_attention", "grouped_matmul"):  # as the chip would
        importlib.import_module(
            f"adaptdl_tpu.ops.{name}"
        )._use_interpret = lambda: False
    gib = 2**30
    with tempfile.TemporaryDirectory() as ckpt:
        os.environ["ADAPTDL_CHECKPOINT_PATH"] = ckpt  # the AOT cache on
        limit = None if args.no_limit else BYTES_LIMIT
        inject_limit(limit)
        lower, facts = step_program(
            args.cell, args.chips, args.atomic, args.accum, limit
        )
        t0 = time.monotonic()
        lowered = lower()
        lower_s = time.monotonic() - t0
    policy, overlap = (
        [
            r["attrs"] for r in trace.snapshot_spans() if r["name"] == name
        ][-1]
        for name in ("remat.policy", "step.reduce_overlap")
    )
    print(
        f"{args.cell} chips={facts['chips']} step "
        f"({facts['geometry']['atomic_bsz']}, "
        f"{facts['geometry']['accum_steps']}): state + gradient "
        f"{facts['held_bytes'] / gib:.2f} GiB a device, "
        f"{'DONATING' if facts['donated'] else 'non-donating twin'}; "
        f"trace + lower {lower_s:.1f}s; remat.policy rungs="
        f"{policy['rungs']!r} rung_bytes={policy['rung_bytes'] / gib:.3f} "
        f"GiB budget_bytes={policy['budget_bytes'] / gib:.3f} GiB "
        f"bytes_limit={policy['bytes_limit']}; step.reduce_overlap "
        + " ".join(f"{k}={v}" for k, v in overlap.items()),
        flush=True,
    )
    if args.lower_only:
        text = lowered.as_text()
    else:
        t0 = time.monotonic()
        compiled = lowered.compile()
        mem, text = compiled.memory_analysis(), compiled.as_text()
        total = (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
        )
        entry = entry_computation(text)
        qkv = re.findall(
            r"^\s*%[\w.\-]+ = bf16\[3,[\d,]+\]\S* fusion\(", text, re.M
        )
        print(
            f"compile {time.monotonic() - t0:.1f}s: args "
            f"{mem.argument_size_in_bytes / gib:.2f} GiB, out "
            f"{mem.output_size_in_bytes / gib:.2f}, temp "
            f"{mem.temp_size_in_bytes / gib:.2f}, alias "
            f"{mem.alias_size_in_bytes / gib:.2f}, total {total / gib:.2f} "
            f"GiB a device; fusions with a bf16[3, ...] result (the "
            f"fused QKV projection) x{len(qkv)} "
            f"%attention x{text.count(' %attention')} "
            f"%flash_bwd x{text.count(' %flash_bwd')} "
            f"%moe_gmm x{text.count(' %moe_gmm')} "
            f"all-reduce: synchronous x{entry.count(' all-reduce(')}, "
            f"under another op (async collective fusions) x"
            f"{len(re.findall(ASYNC_START, entry, re.M))}",
            flush=True,
        )
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
