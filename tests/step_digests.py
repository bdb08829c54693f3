"""Digests of the benchmark's configurations at tiny sizes: the
parameter tree (paths, shapes, dtypes) and the lowered text of the
gradient of each configuration's own loss, on the CPU. A PR that adds
options to the shared model code runs this on its parent and keeps the
output (``tests/data/step_digests.json``); ``tests/test_kimi_linear.py``
holds the tree to it, so a configuration whose program moved — and
with it its AOT-cache key and ``trace_lower_s`` — fails a test and
not a chip check. A PR that means to change a configuration's program
regenerates the file and says so:

    JAX_PLATFORMS=cpu python tests/step_digests.py > tests/data/step_digests.json
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {
    "gpt2-124m": {
        "n_layer": 2, "n_embd": 32, "n_head": 2, "vocab_size": 211,
        "n_positions": 32, "compute_dtype": "float32",
    },
    "lfm2-8b-a1b": {
        "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 24, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_experts": 8, "experts_held": 2,
        "num_experts_per_tok": 2, "vocab_size": 97, "sequence_length": 32,
        "compute_dtype": "float32",
    },
    "keye-vl-2.0-30b-a3b": {
        "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 24, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "router_width": 8,
        "experts_held": 8, "num_experts": 8, "num_local_experts": 8,
        "num_experts_per_tok": 2, "vocab_size": 97, "sequence_length": 32,
        "num_hidden_layers": 1, "compute_dtype": "float32",
        "sa_config": {
            "indexer_head_dim": 16, "indexer_num_heads": 3,
            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
            "q_chunk_size": 512, "topk": 8,
        },
    },
    "ouro-2.6b": {
        "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 48, "vocab_size": 97,
        "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
        "sequence_length": 32, "head_chunk_columns": 32,
        "compute_dtype": "float32",
    },
}
GEOMETRY = {"global_batch": 4, "atomic_bsz": 2, "accum_steps": 1}


def digest(name: str, compute_dtype: str = "float32") -> dict:
    """{"tree": sha256 of the parameter paths / shapes / dtypes,
    "lowered": sha256 of the lowered gradient program's text} of one
    configuration at its tiny size."""
    import jax

    from benchmark import manifest

    os.environ.setdefault("ADAPTDL_NUM_REPLICAS", "1")
    base = os.path.join(ROOT, "benchmark", "configs", name)
    with open(base + ".json") as f:
        sizes = json.load(f)
    sizes.update(TINY[name], compute_dtype=compute_dtype)
    config = manifest.load_module(base + ".py")
    built = config.build(sizes, GEOMETRY, 3)
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

    lowered = jax.jit(jax.grad(loss)).lower(params, batch, jax.random.key(0))

    def sha(text):
        return hashlib.sha256(text.encode()).hexdigest()

    return {"tree": sha(repr(tree)), "lowered": sha(lowered.as_text())}


def digests() -> dict:
    return {
        f"{name}/{dtype}": digest(name, dtype)
        for name in TINY
        for dtype in ("float32", "bfloat16")
    }


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1))
