"""The whole laguna-xs.2 model at a small size against its plain
reference (PR 54): loss, final hidden states, the gradient of every
kind of leaf and the cell's own ``reference_check``; and one dense and
one grouped-query configuration of before, which this PR's new
config fields must leave the programs they were. (The mixers, YaRN,
the gate and the share: ``tests/test_laguna.py``.)"""


import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adaptdl_tpu.models.transformer import (
    AttentionKind,
    GroupedQueryAttention,
    TransformerConfig,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "laguna-xs.2"
TINY = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "sliding_window": 24,
    "router_width": 16, "experts_held": 4, "num_experts": 4,
    "num_experts_per_tok": 3, "vocab_size": 97, "sequence_length": 64,
    "head_chunk_rows": 32, "compute_dtype": "float32",
}


@functools.cache
def _config_module():
    from benchmark import manifest

    return manifest.load_module(
        os.path.join(ROOT, "benchmark", "configs", NAME + ".py")
    )


def _sizes(**changes):
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        sizes = json.load(f)
    sizes.update(TINY)
    sizes.update(changes)
    return sizes


def _built(monkeypatch, sizes, seed=3):
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    geometry = {"global_batch": 4, "atomic_bsz": 2, "accum_steps": 1}
    return _config_module().build(sizes, geometry, seed)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ---- the whole model -------------------------------------------------------


def test_loss_hidden_states_and_gradients_equal_the_reference(monkeypatch):
    """Five layers of the cell's pattern (full + dense FFN, sliding x 3,
    full; four routed with a shared expert), remat on, both kinds of
    kernel, a share of 4 of 16 experts, the untied head: the loss, the
    final hidden states and the gradient of every leaf."""
    config, sizes = _config_module(), _sizes()
    built = _built(monkeypatch, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    data = config.make_dataset(sizes, 5, 4)
    batch = {k: jnp.asarray(v[:2]) for k, v in data.items()}

    def system(params):
        return built["loss_fn"](params, batch, jax.random.key(0))[0]

    def reference(params):
        return config.reference_loss(
            config.reference_weights(params, sizes),
            batch["inputs"], batch["targets"], sizes,
        )[0]

    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    want, want_grads = jax.jit(jax.value_and_grad(reference))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    kinds = set()
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            continue  # a buffer: no gradient reaches it on either side
        kinds.add(re.sub(r"layer_\d+", "layer", name))
        scale = max(float(jnp.abs(ref).max()), 1e-6)
        assert float(jnp.abs(got - ref).max()) / scale < 5e-4, name
    # Every kind of leaf: q, kv, gate, out, both norms, the dense FFN's
    # three, the router, the experts' three, the shared expert's three,
    # the two tables and the final norm (the bias buffer apart).
    assert len(kinds) == 19, sorted(kinds)
    hidden, _, _ = jax.jit(built["head_io"])(
        params, batch, jax.random.key(0)
    )
    with jax.default_matmul_precision("highest"):
        want_hidden, _ = config.reference_hidden(
            config.reference_weights(params, sizes), batch["inputs"], sizes
        )
    assert _rel(hidden, want_hidden) < 5e-5
    report = config.reference_check(built, params, data, sizes)
    assert report["ok"], report


# ---- the configurations of before --------------------------------------------


with open(os.path.join(ROOT, "tests", "data", "step_digests.json")) as _f:
    _DIGESTS = json.load(_f)


@pytest.mark.parametrize(
    "name, dtype", [("gpt2-124m", "float32"), ("lfm2-8b-a1b", "bfloat16")]
)
def test_a_config_without_the_new_fields_is_the_program_of_before(
    name, dtype
):
    """One dense and one grouped-query configuration at their tiny
    sizes: parameter tree and lowered gradient program against what the
    commit before the window gave (``tests/step_digests.py``; all four
    in ``tests/test_kimi_linear.py``)."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import step_digests

    assert step_digests.digest(name, dtype) == _DIGESTS[f"{name}/{dtype}"]


def test_a_kind_that_restates_the_config_is_the_same_mixer():
    """``attention_kinds`` is neutral where it says what the config
    says: the same parameter tree and the same numbers."""
    base = dict(
        num_layers=1, num_heads=4, num_kv_heads=2, d_model=32, head_dim=8,
        dtype=jnp.float32, rope_theta=1e5, rotary_dims=4,
    )
    plain = TransformerConfig(**base)
    by_kind = TransformerConfig(**base, attention_kinds=(
        ("full_attention", AttentionKind(num_heads=4, rope_theta=1e5)),
    ))
    x = jax.random.normal(jax.random.key(1), (2, 24, 32))
    positions = jnp.arange(24)
    params = GroupedQueryAttention(plain).init(
        jax.random.key(0), x, positions
    )["params"]
    got = GroupedQueryAttention(by_kind).apply({"params": params}, x, positions)
    want = GroupedQueryAttention(plain).apply({"params": params}, x, positions)
    np.testing.assert_array_equal(got, want)
