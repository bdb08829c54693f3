"""What the keye-vl-2.0-30b-a3b configuration forced (PR 34), at small
sizes in float32 against the configuration's own plain reference
(``benchmark/configs/keye-vl-2.0-30b-a3b.py``, which imports nothing
from ``adaptdl_tpu``): the whole model's two losses (the indexer's own
among them), the softmax top-k router and the share, heads wider than
``d_model / heads`` and the untied output table. (The indexer's
selection, sparse attention and its backward:
``tests/test_sparse_attention.py``.)"""

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adaptdl_tpu.models import moe
from adaptdl_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    routed_lm_loss_fn,
    sparse_select_counters,
)
from adaptdl_tpu.ops import grouped_matmul as gmm
from adaptdl_tpu.ops import sparse_attention as sparse

NAME = "keye-vl-2.0-30b-a3b"


def _model(sizes, seed=3):
    cfg = configurations.module(NAME).model_config(sizes)
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.key(seed),
        jnp.zeros((1, sizes["sequence_length"]), jnp.int32), train=False,
    )["params"]
    return model, params


def _row(sizes, seed=5):
    data = configurations.module(NAME).make_dataset(sizes, seed, 4)
    return {k: jnp.asarray(v[:1]) for k, v in data.items()}


@pytest.fixture(params=["one_kernel", "two_kernels"])
def backward(request, monkeypatch):
    """Both schedules of the backward on the same small shapes: the
    row-wide accumulators of a test's row always fit, so the two
    kernels are reached by taking the budget away."""
    if request.param == "two_kernels":
        monkeypatch.setattr(sparse, "_ROW_BUDGET", 0)
    assert sparse.backward_schedule(2, 32, 32, 16)[0] == request.param


# ---- the whole model against the plain reference --------------------


def test_losses_and_every_gradient_equal_the_reference(backward):
    """L_LM, L_I and the gradient of their sum to every parameter, the
    system (kernels interpreted, remat on) against ``jax.grad`` of the
    plain reference on the same weights and row."""
    config = configurations.module(NAME)
    sizes = configurations.sizes(NAME)
    model, params = _model(sizes)
    batch = _row(sizes)
    loss_fn = routed_lm_loss_fn(model)
    (loss, counters), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, jax.random.key(0)
    )

    def reference(weights):
        lm, index, _ = config.reference_loss(
            weights, batch["inputs"][0], batch["targets"][0], sizes
        )
        return lm + index, (lm, index)

    weights = config.reference_weights(params, sizes)
    (total, (lm, index)), want = jax.value_and_grad(
        reference, has_aux=True
    )(weights)
    np.testing.assert_allclose(loss, total, rtol=2e-6)
    np.testing.assert_allclose(
        counters["indexer.loss"]["loss"].mean(), index, rtol=2e-5
    )
    assert float(index) > 0 and float(lm) > float(index)
    got = config.reference_weights(grads, sizes)
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == 3 + 15
    for (path, a), b in zip(flat_got, flat_want):
        assert float(jnp.abs(b).max()) > 0, path
        np.testing.assert_allclose(
            a, b, atol=3e-5 * float(jnp.abs(b).max()), err_msg=str(path)
        )
    select = counters["sparse.select"]
    row = config.selected_pairs_per_row(32, 8)
    np.testing.assert_array_equal(select["keys_selected"], [row])
    np.testing.assert_array_equal(select["queries"], [32])
    np.testing.assert_array_equal(
        select["keys_visited"], [sparse.keys_visited(32)]
    )


def test_no_gradient_crosses_between_the_two_losses(backward):
    """L_LM reaches everything but the indexer's parameters, L_I
    reaches them and nothing else: exact zeros on the other side."""
    sizes = configurations.sizes(NAME)
    model, params = _model(sizes)
    batch = _row(sizes)
    cfg = model.config

    def losses(params):
        logits, sown = model.apply(
            {"params": params}, batch["inputs"], train=True,
            mutable=["moe_load", "indexer_loss", "sparse_select"],
        )
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits), batch["targets"][..., None], -1
        )
        return -picked.mean(), sparse_select_counters(cfg, sown)[
            "indexer.loss"
        ]["loss"].mean()

    of_lm = jax.grad(lambda p: losses(p)[0])(params)
    of_index = jax.grad(lambda p: losses(p)[1])(params)
    for path, leaf in jax.tree_util.tree_flatten_with_path(of_lm)[0]:
        inside = any(getattr(k, "key", "") == "indexer" for k in path)
        other = of_index
        for k in path:
            other = other[k.key]
        if inside:
            assert not np.any(np.asarray(leaf)), path
            assert np.any(np.asarray(other)), path
        else:
            assert not np.any(np.asarray(other)), path
            assert np.any(np.asarray(leaf)), path


# ---- the softmax router and the share ---------------------------------


def _layer(seed=7, tokens=64, d=32, f=24, experts=8):
    keys = jax.random.split(jax.random.key(seed), 5)
    return {
        "x": jax.random.normal(keys[0], (tokens, d)),
        "router": jax.random.normal(keys[1], (d, experts)) * 0.5,
        "w1": jax.random.normal(keys[2], (experts, d, f)) * d**-0.5,
        "w3": jax.random.normal(keys[3], (experts, d, f)) * d**-0.5,
        "w2": jax.random.normal(keys[4], (experts, f, d)) * f**-0.5,
    }


def test_softmax_router_equals_a_plain_one():
    layer = _layer()
    sizes = configurations.sizes(NAME)
    experts, weights = moe.softmax_top_k(
        layer["x"], layer["router"], 2, 1e-20, 1.0
    )
    config = configurations.module(NAME)
    want = config.reference_router(layer, layer["x"], sizes)
    got = config.in_expert_order(experts, weights)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    # Softmax is not the sigmoid router: the weights differ.
    _, sigmoid = moe.sigmoid_top_k(
        layer["x"], layer["router"], jnp.zeros(8), 2, 1e-20, 1.0
    )
    assert float(jnp.abs(jnp.sort(sigmoid) - jnp.sort(weights)).max()) > 1e-3
    with pytest.raises(ValueError, match="router_kind"):
        moe.routed_experts(
            layer["x"], layer["router"], None, layer["w1"], layer["w3"],
            layer["w2"], experts_total=8, first_expert=0, top_k=2,
            router_kind="argmax",
        )


@pytest.mark.parametrize("shares", [1, 4])
def test_the_shares_of_a_softmax_layer_add_up_to_the_uncut_layer(shares):
    """``shares`` chips, each told which ``8 / shares`` experts it
    holds: their partial results, summed, are the whole layer's as the
    uncut reference gives it."""
    layer = _layer()
    sizes = configurations.sizes(NAME)
    held = 8 // shares
    with jax.default_matmul_precision("highest"):
        whole, counts = configurations.module(NAME).reference_routed_ffn(
            layer, layer["x"], sizes, first_expert=0
        )
    total, rows = jnp.zeros_like(whole), []
    for chip in range(shares):
        at = slice(chip * held, (chip + 1) * held)
        y, load = moe.routed_experts(
            layer["x"], layer["router"], None, layer["w1"][at],
            layer["w3"][at], layer["w2"][at], experts_total=8,
            first_expert=chip * held, top_k=2, router_kind="softmax",
        )
        assert int(load["dropped"]) == 0
        assert int(load["held_rows"].sum() + load["left_out"]) == 64 * 2
        rows.append(np.asarray(load["held_rows"]))
        total = total + y
    np.testing.assert_allclose(total, whole, atol=5e-5)
    np.testing.assert_array_equal(np.concatenate(rows), np.asarray(counts))


def _softmax_value_and_gradients(layer, first, held, top_k):
    """The softmax layer's output, load and the gradients of a fixed
    functional of it by input, router and the three expert leaves."""
    cot = jax.random.normal(jax.random.key(23), layer["x"].shape)
    at = slice(first, first + held)

    def of(x, router, w1, w3, w2):
        y, load = moe.routed_experts(
            x, router, None, w1, w3, w2,
            experts_total=layer["router"].shape[1], first_expert=first,
            top_k=top_k, router_kind="softmax",
        )
        return (y * cot).sum(), (y, load)

    (_, (y, load)), grads = jax.value_and_grad(of, range(5), has_aux=True)(
        layer["x"], layer["router"], layer["w1"][at], layer["w3"][at],
        layer["w2"][at],
    )
    return y, grads, load


@pytest.mark.parametrize(
    "tokens, experts, first, held, top_k",
    [(64, 16, 4, 2, 2), (128, 32, 0, 4, 8)],
)
def test_the_bounded_softmax_layer_equals_the_worst_case_bit_for_bit(
    tokens, experts, first, held, top_k, monkeypatch
):
    """A softmax router's share small enough to bound (the cell's own,
    16 of 128, is not: ``rows_bound``): the glue walks ``rows_bound``
    rows, and output and all five gradients are those of the layer
    that walks ``rows_capacity``."""
    layer = _layer(seed=tokens, tokens=tokens, experts=experts)
    tile = gmm.tile_rows(tokens * min(top_k, held))
    bound = moe.rows_bound(tokens, top_k, held, experts, tile)
    capacity = moe.rows_capacity(tokens, top_k, held, tile)
    assert bound < capacity
    bounded = _softmax_value_and_gradients(layer, first, held, top_k)
    assert int(bounded[2]["fell_back"]) == 0
    assert int(bounded[2]["rows_walked"]) == bound
    assert int(bounded[2]["dropped"]) == 0
    monkeypatch.setattr(moe, "ROWS_BOUND_FACTOR", 1e9)
    worst = _softmax_value_and_gradients(layer, first, held, top_k)
    assert int(worst[2]["rows_walked"]) == capacity
    np.testing.assert_array_equal(bounded[0], worst[0])
    for name, a, b in zip(
        ("x", "router", "w1", "w3", "w2"), bounded[1], worst[1]
    ):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize(
    "held_pair, active", [(32, 4), (48, 6), (64, 8)]
)
def test_a_softmax_plan_past_the_bound_falls_back_and_drops_nothing(
    held_pair, active
):
    """64 tokens, top 2 of 16, experts 0 and 1 held, tiles of 16 rows:
    the glue walks 80 rows where the worst case has 160.
    ``held_pair`` tokens choose the held pair and
    the rest two experts held elsewhere: inside the bound, past it,
    and every token on the pair."""
    layer = _layer(tokens=64, experts=16)
    d = layer["x"].shape[1]
    on_pair = jnp.arange(64) < held_pair
    x = jnp.where(on_pair[:, None], jnp.eye(d)[0], jnp.eye(d)[1])
    router = (
        jnp.full((d, 16), -6.0)
        .at[0, 0].set(4.0).at[0, 1].set(3.0)
        .at[1, 5].set(4.0).at[1, 6].set(3.0)
    )
    steered = dict(layer, x=x, router=router)
    at = slice(0, 2)
    y, load = moe.routed_experts(
        x, router, None, layer["w1"][at], layer["w3"][at], layer["w2"][at],
        experts_total=16, first_expert=0, top_k=2, router_kind="softmax",
    )
    with jax.default_matmul_precision("highest"):
        want, counts = configurations.module(NAME).reference_routed_ffn(
            {**steered, "w1": layer["w1"][at], "w3": layer["w3"][at],
             "w2": layer["w2"][at]},
            x, configurations.sizes(NAME, num_experts=16), first_expert=0,
        )
    assert moe.rows_bound(64, 2, 2, 16, 16) == 80
    assert int(load["dropped"]) == 0
    assert int(load["fell_back"]) == int(active * 16 > 80)
    assert int(load["rows_active"]) == active * 16
    assert int(load["rows_walked"]) == (160 if active * 16 > 80 else 80)
    np.testing.assert_array_equal(load["held_rows"], [held_pair] * 2)
    np.testing.assert_array_equal(load["held_rows"], counts[:2])
    np.testing.assert_allclose(y, want, atol=5e-5)


# ---- heads of their own width, the untied table -----------------------


def _tree(params):
    return {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }


@pytest.mark.parametrize("family", ["gpt2", "lfm2"])
def test_the_new_options_keep_the_older_parameter_trees(family):
    """``head_dim`` and ``tie_embeddings`` left alone build the trees
    the two older configurations' checkpoints hold (a checkpoint of
    either restores): every leaf by path and shape."""
    if family == "gpt2":
        cfg = TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
            max_seq_len=16, dtype=jnp.float32,
        )
        want = {
            "embed/embedding": (64, 16),
            "LayerNorm_0/scale": (16,),
            "layer_0/LayerNorm_0/scale": (16,),
            "layer_0/LayerNorm_1/scale": (16,),
            "layer_0/attention/qkv/kernel": (16, 3, 2, 8),
            "layer_0/attention/out/kernel": (16, 16),
            "layer_0/ff_up/kernel": (16, 32),
            "layer_0/ff_down/kernel": (32, 16),
        }
    else:
        cfg = TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=2,
            d_model=16, d_ff=32, max_seq_len=16, dtype=jnp.float32,
            norm="rmsnorm", ffn="swiglu", qk_norm=True,
            layer_types=("full_attention",), experts_total=4,
            experts_held=2, experts_top_k=2, d_expert=8,
        )
        want = {
            "embed/embedding": (64, 16),
            "RMSNorm_0/scale": (16,),
            "layer_0/RMSNorm_0/scale": (16,),
            "layer_0/RMSNorm_1/scale": (16,),
            "layer_0/attention/q/kernel": (16, 4, 4),
            "layer_0/attention/kv/kernel": (16, 2, 2, 4),
            "layer_0/attention/q_norm/scale": (4,),
            "layer_0/attention/k_norm/scale": (4,),
            "layer_0/attention/out/kernel": (16, 16),
            "layer_0/moe/router": (16, 4),
            "layer_0/moe/expert_bias": (4,),
            "layer_0/moe/w_gate": (2, 16, 8),
            "layer_0/moe/w_up": (2, 16, 8),
            "layer_0/moe/w_down": (2, 8, 16),
        }
    assert cfg.head_dim is None and cfg.tie_embeddings
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False
    )["params"]
    assert _tree(params) == want


def test_wider_heads_and_the_untied_table():
    """Heads of 32 on a hidden size of 64 (4 x 32 = 128 columns), an
    output table of its own, and no bias buffer under the softmax
    router."""
    sizes = configurations.sizes(NAME)
    _, params = _model(sizes)
    tree = _tree(params)
    assert tree["layer_0/attention/q/kernel"] == (64, 4, 32)
    assert tree["layer_0/attention/kv/kernel"] == (64, 2, 2, 32)
    assert tree["layer_0/attention/out/kernel"] == (128, 64)
    assert tree["layer_0/attention/indexer/index_q/kernel"] == (64, 3, 16)
    assert tree["layer_0/attention/indexer/index_k/kernel"] == (64, 16)
    assert tree["layer_0/attention/indexer/index_w/kernel"] == (64, 3)
    assert tree["lm_head"] == (97, 64) == tree["embed/embedding"]
    assert "layer_0/moe/expert_bias" not in tree
    assert not np.array_equal(params["lm_head"], params["embed"]["embedding"])
