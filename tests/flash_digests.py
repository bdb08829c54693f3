"""Digests of the flash kernels' programs with EQUAL head counts, at
tiny sizes on the CPU (interpret mode: the kernel's body is in the
lowered text): forward and the one backward kernel of the resident,
the K-blocked and the band schedule, float32 and bfloat16. A PR that
gives the kernels a new shape to adapt to (PR 55: fewer kv heads than
query heads) runs this on its PARENT and keeps the output
(``tests/data/flash_equal_heads_digests.json``);
``tests/test_flash_attention.py`` holds the tree to it, so equal head
counts are shown to lower to the parent's program. A PR that means to
change that program regenerates the file and says so:

    JAX_PLATFORMS=cpu python tests/flash_digests.py > tests/data/flash_equal_heads_digests.json
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# name -> (module constants, window): 64 keys of 16 in tiles of 16.
# ``k_blocked``: 32 bfloat16 keys' K and V, double-buffered, so two
# chunks of two tiles in bfloat16 (four of one in float32).
CASES = {
    "resident": ({"_TILE_ROWS": 16}, None),
    "k_blocked": (
        {"_TILE_ROWS": 16, "_KV_VMEM_BUDGET": 2 * 2 * 32 * 16 * 2}, None
    ),
    "band": (
        {"_WINDOW_TILE": 16, "_WINDOW_PIECE": 16, "_WINDOW_PIECE_BWD": 16},
        24,
    ),
}


def digest(case: str, dtype: str, set_attribute=setattr) -> str:
    """sha256 of the lowered text of the gradient of one call, (2, 2,
    64, 16), under ``case``'s constants (``set_attribute``: a test's
    ``monkeypatch.setattr``)."""
    import jax
    import jax.numpy as jnp

    flash = importlib.import_module("adaptdl_tpu.ops.flash_attention")
    constants, window = CASES[case]
    for name, value in constants.items():
        set_attribute(flash, name, value)
    arg = jax.ShapeDtypeStruct((2, 2, 64, 16), jnp.dtype(dtype))

    def loss(q, k, v):
        out = flash.flash_attention(q, k, v, True, None, 16, 16, window)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(arg, arg, arg).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def digests() -> dict:
    return {
        f"{case}/{dtype}": digest(case, dtype)
        for case in CASES
        for dtype in ("float32", "bfloat16")
    }


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1))
