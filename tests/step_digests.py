"""Digests of the benchmark's nine configurations at their tiny sizes
(``tests/configurations.py``): the parameter tree (paths, shapes,
dtypes) and the lowered text of the gradient of each configuration's
own loss, on the CPU, in float32 and in bfloat16. A PR that edits the
shared model code runs this on its parent and keeps the output
(``tests/data/step_digests.json``); ``tests/test_step_digests.py`` holds
the tree to it, one case a configuration and dtype, so a configuration
whose program moved — and with it its AOT-cache key and
``trace_lower_s`` — fails a test that names it and not a chip check. A
PR that means to change a configuration's program regenerates the file
and says so:

    JAX_PLATFORMS=cpu python tests/step_digests.py > tests/data/step_digests.json

(At the REAL sizes, for the chip's compiler: ``tools/lowered_step_diff.py``.)
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import configurations  # noqa: E402 (beside this file)

DTYPES = ("float32", "bfloat16")


def digest(name: str, compute_dtype: str = "float32") -> dict:
    """{"tree": sha256 of the parameter paths / shapes / dtypes,
    "lowered": sha256 of the lowered gradient program's text} of one
    configuration at its tiny size."""
    import jax

    os.environ.setdefault("ADAPTDL_NUM_REPLICAS", "1")
    sizes = configurations.sizes(name, compute_dtype=compute_dtype)
    config = configurations.module(name)
    with configurations.rows_of_several_chunks(name):
        built = config.build(sizes, dict(configurations.GEOMETRY), 3)
        trainer = built["trainer"]
        params = trainer.params_tree(trainer.init_state())
        tree = sorted(
            (jax.tree_util.keystr(path), tuple(leaf.shape), str(leaf.dtype))
            for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        )
        data = config.make_dataset(sizes, 5, 4)
        batch = {k: v[:2] for k, v in data.items()}
        loss_fn = built["loss_fn"]

        def loss(params, batch, rng):
            out = loss_fn(params, batch, rng)
            return out[0] if isinstance(out, tuple) else out

        lowered = jax.jit(jax.grad(loss)).lower(
            params, batch, jax.random.key(0)
        )

    def sha(text):
        return hashlib.sha256(text.encode()).hexdigest()

    return {"tree": sha(repr(tree)), "lowered": sha(lowered.as_text())}


def digests() -> dict:
    return {
        f"{name}/{dtype}": digest(name, dtype)
        for name in configurations.NAMES
        for dtype in DTYPES
    }


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1))
