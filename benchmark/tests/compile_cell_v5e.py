"""Rehearsal without the chip, for any cell: its train step compiled
by the TPU's own compiler for a DESCRIBED v5e at real size, with its
``memory_analysis()``. Nothing runs; a compile that passes is not a
chip run. ``compile_v5e.py`` does this for the gpt2-124m cells (and
compares 1 and 4 devices); this one takes the cell's name and its own
geometry, and compiles the step the trainer's donation rule would
pick on a 16 GB chip: the non-donating twin where two states fit, the
donating step where they do not.

    JAX_PLATFORMS=cpu python benchmark/tests/compile_cell_v5e.py \
        lfm2-8b-a1b-steady [atomic accum] [--text FILE]
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)


def main(cell_name: str, atomic: int | None, accum: int | None,
         text_file: str | None) -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import manifest

    jax.config.update("jax_enable_compilation_cache", False)
    # Steer the kernels as the chip would.
    for name in ("flash_attention", "grouped_matmul"):
        try:
            mod = importlib.import_module(f"adaptdl_tpu.ops.{name}")
        except ImportError:  # a parent commit without the module
            continue
        mod._use_interpret = lambda: False
    cell = manifest.load_cell(cell_name)
    config = manifest.load_module(cell.config_py)
    sizes, chips = cell.sizes, cell.chips
    geometry = dict(cell.workload["geometry"])
    if atomic is not None:
        geometry.update(
            atomic_bsz=atomic, accum_steps=accum,
            global_batch=chips * atomic * (accum + 1),
        )
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    os.environ["ADAPTDL_NUM_REPLICAS"] = str(chips)
    # Abstract weights: nothing can be placed on a described device.
    # build() makes them with jax.jit(init); here the same shapes come
    # from eval_shape through a patched jit, and the default mesh is
    # the described one.
    real_jit = jax.jit
    jax.jit = lambda f, **kw: (
        lambda *a: jax.eval_shape(f, *a)
    ) if getattr(f, "__name__", "") == "<lambda>" else real_jit(f, **kw)
    from adaptdl_tpu.parallel import mesh as mesh_mod

    original = mesh_mod.create_mesh_from_topology
    mesh_mod.create_mesh_from_topology = lambda **kw: mesh
    try:
        built = config.build(sizes, geometry, 0)
    finally:
        jax.jit = real_jit
        mesh_mod.create_mesh_from_topology = original
    trainer = built["trainer"]
    state = trainer._abstract_state()
    specs = trainer.state_spec_tree(state)
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)
        ),
        state, specs,
    )
    seq = config.units_per_sample(sizes)
    batch = {
        k: jax.ShapeDtypeStruct(
            (geometry["global_batch"], seq), jnp.int32,
            sharding=NamedSharding(mesh, P("data")),
        )
        for k in ("inputs", "targets")
    }
    gib = 2**30
    state_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(state)
        if not jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)
    )  # replicated layout: every chip holds the whole
    params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(state.params)
    )
    limit = 15.75 * gib
    donate = 2 * state_bytes + 4 * params > limit
    print(
        f"{cell_name}: {params / 1e6:.1f} M parameters, state "
        f"{state_bytes / gib:.2f} GiB; a second state "
        f"{'does not fit' if donate else 'fits'}: compiling the "
        f"{'donating' if donate else 'non-donating'} step "
        f"({geometry['atomic_bsz']}, {geometry['accum_steps']})",
        flush=True,
    )
    step = trainer.train_step(geometry["atomic_bsz"], geometry["accum_steps"])
    sharded = step._jitted.__wrapped__
    jitted = jax.jit(sharded, donate_argnums=0) if donate else jax.jit(sharded)
    t0 = time.monotonic()
    compiled = jitted.lower(state, batch, ()).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    if text_file:
        with open(text_file, "w") as f:
            f.write(text)
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    print(
        f"compile {time.monotonic() - t0:.1f}s: args "
        f"{mem.argument_size_in_bytes / gib:.2f} GiB, out "
        f"{mem.output_size_in_bytes / gib:.2f}, temp "
        f"{mem.temp_size_in_bytes / gib:.2f}, alias "
        f"{mem.alias_size_in_bytes / gib:.2f}, total "
        f"{total / gib:.2f} GiB per device; "
        f"tpu_custom_call x{text.count('tpu_custom_call')}, "
        f"moe_gmm x{text.count('%moe_gmm')}, "
        f"moe_tgmm x{text.count('%moe_tgmm')}, "
        f"flash_bwd x{text.count('%flash_bwd')}",
        flush=True,
    )


if __name__ == "__main__":
    argv = sys.argv[1:]
    text_file = None
    if "--text" in argv:
        at = argv.index("--text")
        text_file = argv[at + 1]
        del argv[at:at + 2]
    main(
        argv[0],
        int(argv[1]) if len(argv) > 1 else None,
        int(argv[2]) if len(argv) > 2 else None,
        text_file,
    )
