"""The whole laguna-xs.2 model at a small size against its plain
reference (PR 54): loss, final hidden states, the gradient of every
kind of leaf and the cell's own ``reference_check``. (The mixers, YaRN,
the gate and the share: ``tests/test_laguna.py``; that the
configurations of before are the programs they were:
``tests/test_step_digests.py``.)"""

import re

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from configurations import rel

from adaptdl_tpu.models.transformer import (
    AttentionKind,
    GroupedQueryAttention,
    TransformerConfig,
)

NAME = "laguna-xs.2"


# ---- the whole model -------------------------------------------------------


def test_loss_hidden_states_and_gradients_equal_the_reference(monkeypatch):
    """Five layers of the cell's pattern (full + dense FFN, sliding x 3,
    full; four routed with a shared expert), remat on, both kinds of
    kernel, a share of 4 of 16 experts, the untied head: the loss, the
    final hidden states and the gradient of every leaf."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
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
    assert rel(hidden, want_hidden) < 5e-5
    report = config.reference_check(built, params, data, sizes)
    assert report["ok"], report


# ---- a kind that restates the config -----------------------------------


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
