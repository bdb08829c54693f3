"""The whole smallthinker-21b-a3b model at a small size against its plain
reference (PR 60): loss, final hidden states, the gradient of every
kind of leaf and the cell's own ``reference_check``; and each of the
model's three distinctive readings — ``relu`` for ``silu``, the router
on the block's input, no rotary on the full layer — taken the other
way FAILS that comparison. (The routed layer, the mixers and the
share: ``tests/test_smallthinker.py``.)"""

import dataclasses
import functools
import re

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from configurations import rel

from adaptdl_tpu.models.transformer import TransformerLM, routed_lm_loss_fn
from adaptdl_tpu.ops.flash_attention import flash_attention

NAME = "smallthinker-21b-a3b"
FLASH = functools.partial(flash_attention, block_q=16, block_k=16)


def _system(monkeypatch):
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    data = config.make_dataset(sizes, 5, 4)
    return config, sizes, built, params, data


def _reference_loss(config, sizes, batch, variant=""):
    def loss(params):
        return config.reference_loss(
            config.reference_weights(params, sizes),
            batch["inputs"], batch["targets"], sizes, variant=variant,
        )[0]

    return loss


# ---- the whole model -------------------------------------------------------


def test_loss_hidden_states_and_gradients_equal_the_reference(monkeypatch):
    """Four layers of the cell's pattern (full without positions,
    sliding x 3, every one routed on its own input), remat on, both
    kinds of kernel at groups of seven, a share of 4 of 16 ReGLU
    experts, the untied head: the loss, the final hidden states, the
    gradient of every leaf, and the cell's own ``reference_check``."""
    config, sizes, built, params, data = _system(monkeypatch)
    batch = {k: jnp.asarray(v[:2]) for k, v in data.items()}

    def system(params):
        return built["loss_fn"](params, batch, jax.random.key(0))[0]

    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    want, want_grads = jax.jit(
        jax.value_and_grad(_reference_loss(config, sizes, batch))
    )(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    kinds = set()
    for (path, got), ref in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)
    ):
        name = jax.tree_util.keystr(path)
        kinds.add(re.sub(r"layer_\d+", "layer", name))
        scale = max(float(jnp.abs(ref).max()), 1e-6)
        assert float(jnp.abs(got - ref).max()) / scale < 5e-4, name
    # Every kind of leaf: q, kv, out, both norms, the router, the
    # experts' three, the two tables and the final norm.
    assert len(kinds) == 12, sorted(kinds)
    hidden, _, load = jax.jit(built["head_io"])(
        params, batch, jax.random.key(0)
    )
    with jax.default_matmul_precision("highest"):
        want_hidden, _ = config.reference_hidden(
            config.reference_weights(params, sizes), batch["inputs"], sizes
        )
    assert rel(hidden, want_hidden) < 5e-5
    # What a router read is its block's input: the embedding on layer
    # 0, the block before's result after.
    np.testing.assert_array_equal(
        load["routed_on"][0],
        params["embed"]["embedding"][batch["inputs"]].reshape(-1, 32),
    )
    report = config.reference_check(built, params, data, sizes)
    assert report["ok"], report
    assert 0.2 < report["hidden_zero_share"] < 0.8


@pytest.mark.parametrize(
    "wrong",
    [
        {"experts_activation": "silu"},
        {"experts_routed_on": "ffn_input"},
        {"attention_kinds": "rotary on the full layer"},
    ],
    ids=["silu_gate", "router_on_the_ffn_input", "rotary_on_the_full_layer"],
)
def test_each_reading_taken_the_other_way_fails_the_comparison(
    monkeypatch, wrong
):
    """A ``silu`` gate, a router on the FFN's input and rotary on the
    full layer are each another model on the same parameter tree: the
    loss and the gradients leave the reference's by orders more than
    the right program's 1e-5 / 5e-4."""
    config, sizes, built, params, data = _system(monkeypatch)
    batch = {k: jnp.asarray(v[:2]) for k, v in data.items()}
    cfg = config.model_config(sizes, FLASH)
    if "attention_kinds" in wrong:
        wrong = {"attention_kinds": tuple(
            (kind, dataclasses.replace(own, rope=True))
            for kind, own in cfg.attention_kinds
        )}
    loss_fn = routed_lm_loss_fn(
        TransformerLM(dataclasses.replace(cfg, **wrong)),
        sizes["head_chunk_rows"],
    )
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, jax.random.key(0))[0]
    ))(params)
    want, want_grads = jax.jit(
        jax.value_and_grad(_reference_loss(config, sizes, batch))
    )(params)
    assert abs(float(loss) - float(want)) / float(want) > 1e-4
    worst = max(
        rel(got, ref)
        for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads))
    )
    assert worst > 0.05
    # ... and the reference's own variant of the same fault differs
    # from the reference: the controls of the chip's limits read it.
    variant = {
        "experts_activation": "silu", "experts_routed_on": "router_on_x",
        "attention_kinds": "rotary_swapped",
    }[next(iter(wrong))]
    faulty = jax.jit(_reference_loss(config, sizes, batch, variant))(params)
    assert abs(float(faulty) - float(want)) / float(want) > 1e-4
