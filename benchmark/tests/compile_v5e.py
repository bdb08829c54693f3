"""Rehearsal without the chip: the gpt2-124m train step compiled by the
TPU's own compiler for a DESCRIBED v5e:2x2 at real size, on 1 and 4
devices, with its ``memory_analysis()`` for the candidate batch
geometries. Nothing runs; a compile that passes is not a chip run.
Compiled is the NON-donating twin the AOT executable cache runs
(input and output state live together), which is what every elastic
job with a checkpoint path executes.

    JAX_PLATFORMS=cpu python benchmark/tests/compile_v5e.py [atomic ...]
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


def main(atomics: list[int]) -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import manifest

    jax.config.update("jax_enable_compilation_cache", False)
    flash = importlib.import_module("adaptdl_tpu.ops.flash_attention")
    flash._use_interpret = lambda: False  # steer as the chip would
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    cell = manifest.load_cell("gpt2-124m-steady")
    config = manifest.load_module(cell.config_py)
    sizes = cell.sizes
    for chips in (1, 4):
        mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
        for atomic in atomics:
            geometry = {
                "atomic_bsz": atomic, "accum_steps": 1,
                "global_batch": chips * atomic * 2,
            }
            os.environ["ADAPTDL_NUM_REPLICAS"] = str(chips)
            # Abstract weights: nothing can be placed on a described
            # device. build() makes them with jax.jit(init); here the
            # same shapes come from eval_shape through a patched jit.
            real_jit = jax.jit
            jax.jit = lambda f, **kw: (
                lambda *a: jax.eval_shape(f, *a)
            ) if getattr(f, "__name__", "") == "<lambda>" else real_jit(
                f, **kw
            )
            # ... and the default mesh is the described one.
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
            seq = sizes["n_positions"]
            batch = {
                k: jax.ShapeDtypeStruct(
                    (geometry["global_batch"], seq), jnp.int32,
                    sharding=NamedSharding(mesh, P("data")),
                )
                for k in ("inputs", "targets")
            }
            step = trainer.train_step(atomic, 1)
            sharded = step._jitted.__wrapped__
            t0 = time.monotonic()
            compiled = jax.jit(sharded).lower(state, batch, ()).compile()
            mem = compiled.memory_analysis()
            text = compiled.as_text()
            gib = 2**30
            print(
                f"chips={chips} atomic={atomic} accum=1 compile "
                f"{time.monotonic() - t0:.1f}s: args "
                f"{mem.argument_size_in_bytes / gib:.2f} GiB, out "
                f"{mem.output_size_in_bytes / gib:.2f} GiB, temp "
                f"{mem.temp_size_in_bytes / gib:.2f} GiB, alias "
                f"{mem.alias_size_in_bytes / gib:.2f} GiB, total "
                f"{(mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / gib:.2f}"
                f" GiB per device; mosaic={flash.MOSAIC_CALL in text} "
                f"all-reduce={'all-reduce' in text}",
                flush=True,
            )


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:]] or [8, 16])
