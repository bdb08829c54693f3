"""The block options and the dropless routed expert layer that the
lfm2-8b-a1b configuration forced (PR 30), at small sizes in float32
against the configuration's own plain reference
(``benchmark/configs/lfm2-8b-a1b.py``, which imports nothing from
``adaptdl_tpu``)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from adaptdl_tpu import trace
from adaptdl_tpu.models import moe
from adaptdl_tpu.models.transformer import (
    GroupedQueryAttention,
    ShortConv,
    TransformerConfig,
    TransformerLM,
    causal_attention,
    routed_lm_loss_fn,
)
from adaptdl_tpu.ops import grouped_matmul as gmm
from adaptdl_tpu.ops.flash_attention import (
    flash_attention,
    make_flash_attention,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {
    "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 8, "experts_held": 2,
    "num_experts_per_tok": 2, "vocab_size": 97, "sequence_length": 32,
    "compute_dtype": "float32",
}


@functools.cache
def _config_module():
    from benchmark import manifest

    return manifest.load_module(
        os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b.py")
    )


def _sizes(**changes):
    with open(
        os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b.json")
    ) as f:
        sizes = json.load(f)
    sizes.update(TINY)
    sizes.update(changes)
    return sizes


def _built(monkeypatch, sizes, seed=3):
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    geometry = {"global_batch": 4, "atomic_bsz": 2, "accum_steps": 1}
    return _config_module().build(sizes, geometry, seed)


def _biased(params, sizes, scale=0.3):
    """The same parameters with a seeded non-zero expert bias."""
    params = jax.tree.map(lambda x: x, params)
    for i in range(sizes["num_dense_layers"], sizes["num_hidden_layers"]):
        params[f"layer_{i}"]["moe"]["expert_bias"] = scale * (
            jax.random.normal(jax.random.key(i), (sizes["num_experts"],))
        )
    return params


# ---- the whole model against the plain reference --------------------


def test_loss_and_gradients_equal_the_reference(monkeypatch):
    """Five layers of the cell's pattern (conv, full_attention, conv,
    conv, conv; one dense, four routed), remat on, flash kernel,
    grouped products, a share of 2 of 8 experts, non-zero bias."""
    config, sizes = _config_module(), _sizes()
    built = _built(monkeypatch, sizes)
    params = _biased(built["trainer"]._init_params, sizes)
    data = config.make_dataset(sizes, 5, 4)
    batch = {k: v[:2] for k, v in data.items()}

    def system(p):
        return built["loss_fn"](p, batch, jax.random.key(0))[0]

    def reference(p):
        return config.reference_loss(
            config.reference_weights(p, sizes), batch["inputs"],
            batch["targets"], sizes,
        )[0]

    loss, grads = jax.value_and_grad(system)(params)
    ref_loss, ref_grads = jax.value_and_grad(reference)(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got - want).max()) <= 1e-4 * scale + 1e-7, (
            jax.tree_util.keystr(path)
        )
    # No gradient reaches the bias; routers and experts get theirs
    # (in some layer: a seeded bias may keep a layer's every token off
    # the two experts held).
    reached = set()
    for (path, got) in flat:
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            assert float(jnp.abs(got).max()) == 0.0
        elif float(jnp.abs(got).max()) > 0.0:
            reached.add(path[-1].key)
    assert {"router", "w_gate", "w_up", "w_down", "conv", "scale"} <= reached
    check = config.reference_check(built, params, data, sizes)
    assert check["ok"], check
    assert check["rows_dropped"] == 0 and check["routing_l1_share"] == 0


# ---- the routed layer ------------------------------------------------


def _layer(seed=0, tokens=64, d=16, f=24, experts=8, bias_scale=0.5):
    rng = np.random.default_rng(seed)

    def normal(*shape, scale=1.0):
        return jnp.asarray(
            rng.normal(size=shape).astype(np.float32) * scale
        )

    return {
        "x": normal(tokens, d),
        "router": normal(d, experts),
        "bias": normal(experts, scale=bias_scale),
        "w1": normal(experts, d, f, scale=0.3),
        "w3": normal(experts, d, f, scale=0.3),
        "w2": normal(experts, f, d, scale=0.3),
    }


def _share(layer, first, held, top_k=2, x=None, router=None, bias=None):
    """(system (y, load), reference (y, counts)) of one share."""
    experts = layer["router"].shape[1]
    x = layer["x"] if x is None else x
    router = layer["router"] if router is None else router
    bias = layer["bias"] if bias is None else bias
    got = moe.routed_experts(
        x, router, bias,
        layer["w1"][first:first + held], layer["w3"][first:first + held],
        layer["w2"][first:first + held],
        experts_total=experts, first_expert=first, top_k=top_k,
        norm_eps=1e-6,
    )
    sizes = {
        "num_experts": experts, "num_experts_per_tok": top_k,
        "first_expert": first, "expert_weight_eps": 1e-6,
        "routed_scaling_factor": 1.0,
    }
    ref_layer = {
        "router": router, "bias": bias,
        "w1": layer["w1"][first:first + held],
        "w3": layer["w3"][first:first + held],
        "w2": layer["w2"][first:first + held],
    }
    with jax.default_matmul_precision("highest"):
        want = _config_module().reference_routed_ffn(ref_layer, x, sizes)
    return got, want


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """``shares`` chips, each told which ``E / shares`` experts it
    holds: their partial results, summed, are the whole layer's."""
    layer = _layer()
    experts = layer["router"].shape[1]
    held = experts // shares
    (_, _), (whole, counts) = _share(layer, 0, experts)
    total = jnp.zeros_like(whole)
    rows = []
    for chip in range(shares):
        (y, load), (ref_y, _) = _share(layer, chip * held, held)
        np.testing.assert_allclose(y, ref_y, atol=2e-5)
        assert int(load["dropped"]) == 0
        assert int(load["held_rows"].sum() + load["left_out"]) == 64 * 2
        rows.append(np.asarray(load["held_rows"]))
        total = total + y
    np.testing.assert_allclose(total, whole, atol=5e-5)
    np.testing.assert_array_equal(np.concatenate(rows), np.asarray(counts))


@pytest.mark.parametrize("favourite", [0, 5])
@pytest.mark.parametrize("first, held", [(0, 8), (4, 4), (0, 2)])
def test_dropless_when_every_token_picks_one_expert(favourite, first, held):
    """A bias that forces every token's first choice onto one expert:
    rows in = rows out whatever the imbalance, and the result is the
    reference's."""
    layer = _layer(seed=1)
    bias = jnp.zeros(8).at[favourite].set(100.0)
    (y, load), (ref_y, counts) = _share(layer, first, held, bias=bias)
    np.testing.assert_allclose(y, ref_y, atol=2e-5)
    assert int(counts[favourite]) == 64
    assert int(load["dropped"]) == 0
    np.testing.assert_array_equal(
        load["held_rows"], counts[first:first + held]
    )
    assert int(load["left_out"]) == 128 - int(load["held_rows"].sum())
    if first <= favourite < first + held:
        assert int(load["held_rows"].max()) == 64


def test_the_bias_moves_the_selection_and_not_the_weights():
    layer = _layer(seed=2, bias_scale=0.0)
    scores = jax.nn.sigmoid(layer["x"] @ layer["router"])
    bias = jnp.asarray(np.random.default_rng(9).normal(size=8) * 0.4)
    experts0, weights0 = moe.sigmoid_top_k(
        layer["x"], layer["router"], jnp.zeros(8), 2, 1e-6, 1.0
    )
    experts, weights = moe.sigmoid_top_k(
        layer["x"], layer["router"], bias, 2, 1e-6, 1.0
    )
    # The selection follows score + bias ...
    _, want = jax.lax.top_k(scores + bias, 2)
    np.testing.assert_array_equal(experts, want)
    assert not np.array_equal(experts, experts0)
    # ... the weights are the chosen SCORES over their sum.
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(
        weights, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6
    )
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-5)
    # The whole layer agrees with the reference, and a reference whose
    # weights include the bias does not.
    (y, _), (ref_y, _) = _share(layer, 0, 8, bias=bias)
    np.testing.assert_allclose(y, ref_y, atol=2e-5)
    sizes = {
        "num_experts": 8, "num_experts_per_tok": 2, "first_expert": 0,
        "expert_weight_eps": 1e-6, "routed_scaling_factor": 1.0,
    }
    ref_layer = {**{k: layer[k] for k in ("router", "w1", "w3", "w2")},
                 "bias": bias}
    wrong, _ = _config_module().reference_routed_ffn(
        ref_layer, layer["x"], sizes, variant="weights_with_bias"
    )
    assert float(jnp.abs(wrong - y).max()) > 1e-2


def test_routed_layer_gradients_equal_the_reference():
    layer = _layer(seed=3)
    cot = jnp.asarray(
        np.random.default_rng(4).normal(size=(64, 16)).astype(np.float32)
    )
    keys = ("x", "router", "w1", "w3", "w2")

    def system(*args):
        return (_share(dict(zip(keys, args), bias=layer["bias"]), 2, 4)[0][0]
                * cot).sum()

    def reference(*args):
        return (_share(dict(zip(keys, args), bias=layer["bias"]), 2, 4)[1][0]
                * cot).sum()

    args = [layer[k] for k in keys]
    got = jax.grad(system, range(5))(*args)
    want = jax.grad(reference, range(5))(*args)
    for name, a, b in zip(keys, got, want):
        np.testing.assert_allclose(
            a, b, atol=1e-5 * float(jnp.abs(b).max()) + 1e-6, err_msg=name
        )


@pytest.mark.parametrize("tokens, top_k, held", [(64, 2, 8), (40, 4, 3), (8, 1, 2)])
def test_row_plan_places_every_held_assignment_once(tokens, top_k, held):
    rng = np.random.default_rng(tokens)
    experts = jnp.asarray(
        np.stack([rng.permutation(8)[:top_k] for _ in range(tokens)])
    ).astype(jnp.int32)
    tile = gmm.tile_rows(tokens * min(top_k, held))
    plan = jax.jit(
        functools.partial(
            moe.plan_rows, first_expert=1, experts_held=held, tile=tile
        )
    )(experts)
    rows = moe.rows_capacity(tokens, top_k, held, tile)
    dest = np.asarray(plan.dest).reshape(-1)
    local = np.asarray(experts).reshape(-1) - 1
    is_held = (local >= 0) & (local < held)
    assert plan.row_token.shape == (rows,)
    # Held assignments have distinct rows inside the buffer; the
    # others point one past it.
    assert len(set(dest[is_held])) == is_held.sum()
    assert (dest[~is_held] == rows).all() and (dest[is_held] < rows).all()
    # A row's tile belongs to the assignment's expert, and the two
    # maps are each other's inverse.
    tile_expert = np.asarray(plan.tile_expert)
    assert (tile_expert[dest[is_held] // tile] == local[is_held]).all()
    assignment = np.asarray(plan.row_assignment)
    assert (assignment[dest[is_held]] == np.flatnonzero(is_held)).all()
    assert (assignment >= 0).sum() == is_held.sum()
    sizes = np.asarray(plan.group_sizes)
    np.testing.assert_array_equal(
        sizes, np.bincount(local[is_held], minlength=held)
    )
    assert int(plan.active_tiles[0]) == int((-(-sizes // tile)).sum())


# ---- the grouped products -------------------------------------------


# ---- the bounded row buffer and its dropless fall-back ---------------


@pytest.mark.parametrize(
    "tokens, top_k, held, total, tile",
    [
        (16384, 4, 8, 32, 512),  # the lfm2-8b-a1b cell: 45 056 of 69 632
        # The keye cell: 49 152 would be the bound of 139 264, and the
        # rest is longer than that: one pass over all of them.
        (16384, 8, 16, 128, 512),
        (64, 2, 2, 16, 16),
        (64, 2, 8, 8, 16),  # every expert held: the worst case itself
        (40, 4, 3, 8, 8),
        (8, 1, 1, 64, 8),
    ],
)
def test_rows_bound_is_whole_tiles_under_the_worst_case_and_monotone(
    tokens, top_k, held, total, tile
):
    bound = moe.rows_bound(tokens, top_k, held, total, tile)
    capacity = moe.rows_capacity(tokens, top_k, held, tile)
    assert bound % tile == 0 and 0 < bound <= capacity
    # Room for the expected rows ROWS_BOUND_FACTOR times over, and
    # never less than half of what a fall-back would walk.
    expected = tokens * top_k * held / total
    assert bound >= min(
        moe.ROWS_BOUND_FACTOR * expected + held * (tile - 1), capacity
    )
    assert 2 * bound >= capacity
    if held == total:
        assert bound == capacity
    # More tokens or held experts never shrink it.
    assert moe.rows_bound(tokens + tile, top_k, held, total, tile) >= bound
    assert moe.rows_bound(tokens, top_k, held + 1, total + 1, tile) >= bound
    if (tokens, tile) == (16384, 512):
        assert (bound, capacity) == {
            32: (45056, 69632), 128: (139264, 139264),
        }[total]


def _steered(picks, experts=16, seed=5, d=16):
    """A layer of ``_layer``'s widths whose router sends token ``t`` to
    exactly ``picks[t]`` (first choice first): tokens are one-hot over
    the distinct picks and the router's row of a pick scores its
    experts high. The bias is zero."""
    layer = _layer(seed=seed, tokens=len(picks), experts=experts, d=d)
    kinds = sorted(set(picks))
    assert len(kinds) <= layer["x"].shape[1]
    router = np.full((layer["x"].shape[1], experts), -6.0, np.float32)
    for row, kind in enumerate(kinds):
        for place, expert in enumerate(kind):
            router[row, expert] = 4.0 - place
    x = np.zeros(layer["x"].shape, np.float32)
    x[np.arange(len(picks)), [kinds.index(p) for p in picks]] = 1.0
    return dict(
        layer, x=jnp.asarray(x), router=jnp.asarray(router),
        bias=jnp.zeros(experts),
    )


_LEAVES = ("x", "router", "w1", "w3", "w2")


def _assert_gradients_are_the_references(layer, held):
    """The gradients of a fixed functional of the share ``0 .. held``
    by input, router and the three expert leaves, the layer's against
    its reference's (``_share``'s two results)."""
    cot = jnp.asarray(
        np.random.default_rng(4).normal(size=layer["x"].shape)
        .astype(np.float32)
    )

    def loss(which):
        def of(*args):
            changed = dict(layer, **dict(zip(_LEAVES, args)))
            return (_share(changed, 0, held)[which][0] * cot).sum()
        return of

    args = [layer[k] for k in _LEAVES]
    got = jax.grad(loss(0), range(5))(*args)
    want = jax.grad(loss(1), range(5))(*args)
    for name, a, b in zip(_LEAVES, got, want):
        np.testing.assert_allclose(
            a, b, atol=1e-5 * float(jnp.abs(b).max()) + 1e-6, err_msg=name
        )


# 64 tokens, top 2 of 16 experts, experts 0 and 1 held, tiles of 16
# rows: the glue walks 80 rows (5 tiles) where the worst case has 160.
# (picks of each kind) -> active tiles.
_STEERED = {
    "exactly_the_bound": (
        [(0, 1)] * 32 + [(0, 7)] * 16 + [(5, 6)] * 16, 5
    ),
    "one_tile_over": ([(0, 1)] * 32 + [(0, 7)] * 17 + [(5, 6)] * 15, 6),
    "every_token_on_the_held_pair": ([(0, 1)] * 64, 8),
    "nothing_held": ([(5, 6)] * 64, 0),
}


@pytest.mark.parametrize("case", sorted(_STEERED))
def test_a_plan_past_the_bound_falls_back_and_drops_nothing(
    case, monkeypatch
):
    picks, active = _STEERED[case]
    layer = _steered(picks)
    bound = moe.rows_bound(64, 2, 2, 16, 16)
    capacity = moe.rows_capacity(64, 2, 2, 16)
    assert (bound, capacity) == (80, 160)
    (y, load), (ref_y, counts) = _share(layer, 0, 2)
    np.testing.assert_array_equal(
        np.sort(np.asarray(load["experts"]), -1),
        np.sort(np.asarray(picks), -1),
    )
    assert int(load["dropped"]) == 0
    assert int(load["rows_active"]) == active * 16
    assert int(load["fell_back"]) == int(active * 16 > bound)
    # The first ``bound`` rows, and the rest only where an active tile
    # lies there.
    assert int(load["rows_walked"]) == (
        capacity if active * 16 > bound else bound
    )
    np.testing.assert_array_equal(load["held_rows"], counts[:2])
    np.testing.assert_allclose(y, ref_y, atol=2e-5)
    # ... and so are the five gradients, on either path.
    _assert_gradients_are_the_references(layer, held=2)
    # Inside the bound: the bits of a layer that has none. Past it the
    # rest's sums are added to the first rows': the same to rounding.
    taken = _value_and_gradients(layer, 0, 2, 2, bias=layer["bias"])
    monkeypatch.setattr(moe, "ROWS_BOUND_FACTOR", 1e9)
    unbounded = _value_and_gradients(layer, 0, 2, 2, bias=layer["bias"])
    assert int(unbounded[2]["rows_walked"]) == capacity
    for name, a, b in zip(
        ("y",) + _LEAVES, (taken[0], *taken[1]),
        (unbounded[0], *unbounded[1]),
    ):
        if active * 16 <= bound:
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            np.testing.assert_allclose(
                a, b, atol=1e-6 * float(jnp.abs(b).max()), err_msg=name
            )


def _picks_for(counts, tokens, elsewhere):
    """``tokens`` picks of two experts that give held expert ``e``
    exactly ``counts[e]`` rows: as many tokens with two held picks as
    it takes, the others with one and an expert held ``elsewhere``."""
    rows = [e for e, n in enumerate(counts) for _ in range(n)]
    doubles = len(rows) - tokens
    singles = rows[doubles:len(rows) - doubles]
    picks = list(zip(rows[:doubles], rows[len(rows) - doubles:]))
    assert doubles >= 0 and all(a != b for a, b in picks)
    return picks + [(e, elsewhere) for e in singles]


@pytest.mark.parametrize(
    "counts, tiles",
    [
        # Groups of 2, 2, 2, 1, 1, 1, 2, 1 tiles: the seventh lies
        # across the bound (tile 10).
        ((17, 17, 17, 2, 2, 2, 17, 2), 12),
        # 15 tiles, the fifth group across the bound.
        ((33, 17, 17, 17, 17, 17, 5, 5), 15),
        ((16, 16, 16, 16, 16, 16, 16, 16), 8),  # inside the bound
    ],
)
def test_a_group_across_the_bound_has_both_parts_of_its_gradient(
    counts, tiles
):
    """64 tokens, top 2 of 64 experts, 8 held, tiles of 16 rows: the
    glue walks 160 rows (10 tiles) of the worst case's 256, so a
    group's rows can lie on both sides and its weight gradient is the
    sum of both passes': output and gradients are the reference's,
    nothing is dropped."""
    picks = _picks_for(counts, 64, elsewhere=40)
    layer = _steered(picks, experts=64, d=32)
    bound = moe.rows_bound(64, 2, 8, 64, 16)
    assert (bound, moe.rows_capacity(64, 2, 8, 16)) == (160, 256)
    (y, load), (ref_y, ref_counts) = _share(layer, 0, 8)
    np.testing.assert_array_equal(load["held_rows"], counts)
    np.testing.assert_array_equal(load["held_rows"], ref_counts[:8])
    assert int(load["dropped"]) == 0
    assert int(load["rows_active"]) == tiles * 16
    assert int(load["rows_walked"]) == (256 if tiles > 10 else 160)
    assert int(load["fell_back"]) == int(tiles > 10)
    np.testing.assert_allclose(y, ref_y, atol=2e-5)
    _assert_gradients_are_the_references(layer, held=8)


def _value_and_gradients(layer, first, held, top_k, **router):
    """``routed_experts``' output, load and the gradients of a fixed
    functional of it by input, router and the three expert leaves."""
    cot = jnp.asarray(
        np.random.default_rng(11).normal(size=layer["x"].shape)
        .astype(np.float32)
    )
    at = slice(first, first + held)

    def of(x, router_w, w1, w3, w2):
        y, load = moe.routed_experts(
            x, router_w, router.get("bias"), w1, w3, w2,
            experts_total=layer["router"].shape[1], first_expert=first,
            top_k=top_k, norm_eps=1e-6,
            router_kind="sigmoid" if "bias" in router else "softmax",
        )
        return (y * cot).sum(), (y, load)

    (_, (y, load)), grads = jax.value_and_grad(of, range(5), has_aux=True)(
        layer["x"], layer["router"], layer["w1"][at], layer["w3"][at],
        layer["w2"][at],
    )
    return y, grads, load


@pytest.mark.parametrize(
    "tokens, experts, first, held, top_k",
    [(64, 8, 2, 2, 2), (128, 16, 0, 2, 4), (256, 32, 8, 8, 4)],
)
def test_the_bounded_path_equals_the_worst_case_path_bit_for_bit(
    tokens, experts, first, held, top_k, monkeypatch
):
    """The same rows in the same tiles, so the same bits: output and
    all five gradients, where the router is the random one's and the
    plan fits the bound."""
    layer = _layer(seed=tokens, tokens=tokens, experts=experts)
    tile = gmm.tile_rows(tokens * min(top_k, held))
    bound = moe.rows_bound(tokens, top_k, held, experts, tile)
    capacity = moe.rows_capacity(tokens, top_k, held, tile)
    assert bound < capacity
    bounded = _value_and_gradients(
        layer, first, held, top_k, bias=layer["bias"]
    )
    assert int(bounded[2]["fell_back"]) == 0
    assert int(bounded[2]["rows_walked"]) == bound
    # No bound: the only path is the worst case's.
    monkeypatch.setattr(moe, "ROWS_BOUND_FACTOR", 1e9)
    worst = _value_and_gradients(
        layer, first, held, top_k, bias=layer["bias"]
    )
    assert int(worst[2]["rows_walked"]) == capacity
    assert int(worst[2]["fell_back"]) == 0
    np.testing.assert_array_equal(bounded[0], worst[0])
    for name, a, b in zip(_LEAVES, bounded[1], worst[1]):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("sizes", [(5, 0, 17, 8), (0, 0, 0, 3), (8, 8, 8, 8)])
def test_grouped_matmul_and_its_gradients(sizes):
    """The three products against a per-row einsum: a group without
    rows gets a zero weight gradient, tiles past the active ones are
    never read."""
    rng = np.random.default_rng(0)
    experts, k, n, tile, tiles = 4, 32, 48, 8, 12
    sizes = np.array(sizes)
    padded = -(-sizes // tile) * tile
    tile_expert = np.repeat(np.arange(experts), padded // tile)
    active = len(tile_expert)
    tile_expert = np.concatenate(
        [tile_expert, np.full(tiles - active, tile_expert[-1])]
    ).astype(np.int32)
    starts = np.cumsum(padded) - padded
    rows = tiles * tile
    valid = np.zeros(rows, bool)
    row_expert = np.zeros(rows, int)
    for e in range(experts):
        valid[starts[e]:starts[e] + sizes[e]] = True
        row_expert[starts[e]:starts[e] + padded[e]] = e
    x = jnp.asarray(rng.normal(size=(rows, k)).astype(np.float32))
    # Rows past the active tiles may hold anything, NaN included.
    x = x.at[active * tile:].set(jnp.nan)
    w = jnp.asarray(rng.normal(size=(experts, k, n)).astype(np.float32))
    cot = jnp.asarray(rng.normal(size=(rows, n)).astype(np.float32))
    groups = (
        jnp.asarray(tile_expert), jnp.asarray([active], jnp.int32),
        jnp.asarray(sizes, jnp.int32),
    )

    def system(x, w):
        out = gmm.grouped_matmul(x, w, *groups)
        return jnp.where(valid[:, None], out, 0.0)

    def reference(x, w):
        x = jnp.where(valid[:, None], x, 0.0)
        out = jnp.einsum("rk,rkn->rn", x, w[row_expert], precision="highest")
        return jnp.where(valid[:, None], out, 0.0)

    np.testing.assert_allclose(system(x, w), reference(x, w), atol=1e-4)
    got = jax.grad(lambda *a: (system(*a) * cot).sum(), (0, 1))(x, w)
    want = jax.grad(lambda *a: (reference(*a) * cot).sum(), (0, 1))(x, w)
    np.testing.assert_allclose(
        jnp.where(valid[:, None], got[0], 0.0), want[0], atol=1e-4
    )
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)
    assert got[1].dtype == jnp.float32
    for e in range(experts):
        if sizes[e] == 0:
            assert float(jnp.abs(got[1][e]).max()) == 0.0


def test_grouped_products_are_named_for_the_device_trace():
    """The calls carry the names the benchmark's readers look for."""
    x = jnp.zeros((16, 8)), jnp.zeros((2, 8, 8))
    groups = (
        jnp.zeros((2,), jnp.int32), jnp.ones((1,), jnp.int32),
        jnp.array([8, 0], jnp.int32),
    )
    jaxpr = str(jax.make_jaxpr(
        jax.grad(lambda x, w: gmm.grouped_matmul(x, w, *groups).sum(), (0, 1))
    )(*x))
    assert gmm.GMM_KERNEL_NAME in jaxpr and gmm.TGMM_KERNEL_NAME in jaxpr


# ---- the mixers -------------------------------------------------------


def _mixer_config(**kw):
    return TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=2,
        d_model=32, d_ff=48, max_seq_len=32, dtype=jnp.float32,
        norm="rmsnorm", norm_eps=1e-5, qk_norm=True, rope_theta=1e6,
        **kw,
    )


def test_short_convolution_is_causal_and_equals_the_reference():
    cfg = _mixer_config()
    x = jax.random.normal(jax.random.key(0), (2, 32, 32))
    module = ShortConv(cfg)
    params = module.init(jax.random.key(1), x, None)["params"]
    y = module.apply({"params": params}, x, None)
    ref_layer = {
        "w_in": params["in_proj"]["kernel"], "taps": params["conv"],
        "w_out": params["out_proj"]["kernel"],
    }
    with jax.default_matmul_precision("highest"):
        want = _config_module().reference_short_conv(ref_layer, x)
    np.testing.assert_allclose(y, want, atol=1e-5)
    # Written out for one channel: taps[2] is the current position's.
    bcx = jnp.einsum("bsd,dge->bsge", x, params["in_proj"]["kernel"])
    z = np.asarray(bcx[:, :, 0] * bcx[:, :, 2])
    taps = np.asarray(params["conv"])
    t = 7
    mixed = taps[0] * z[:, t - 2] + taps[1] * z[:, t - 1] + taps[2] * z[:, t]
    np.testing.assert_allclose(
        (np.asarray(bcx[:, t, 1]) * mixed) @ np.asarray(
            params["out_proj"]["kernel"]
        ),
        y[:, t], atol=1e-4,
    )
    # Causal: a change at position 20 leaves every earlier output.
    moved = module.apply({"params": params}, x.at[:, 20].add(1.0), None)
    np.testing.assert_array_equal(moved[:, :20], y[:, :20])
    assert float(jnp.abs(moved[:, 20:23] - y[:, 20:23]).max()) > 1e-3
    np.testing.assert_allclose(moved[:, 23:], y[:, 23:], atol=1e-6)


def test_gqa_with_head_norms_through_the_flash_kernel():
    """Grouped-query attention with per-head RMSNorm on q and k: the
    flash kernel (interpret mode) against plain attention, and both
    against the plain reference; gradients too (dK and dV sum over the
    four... here two query heads of a kv head)."""
    x = jax.random.normal(jax.random.key(0), (2, 32, 32))
    positions = jnp.arange(32)
    plain = GroupedQueryAttention(
        _mixer_config(attention_fn=functools.partial(causal_attention))
    )
    flash = GroupedQueryAttention(
        _mixer_config(
            attention_fn=functools.partial(
                flash_attention, block_q=16, block_k=16
            )
        )
    )
    params = plain.init(jax.random.key(1), x, positions)["params"]
    params["q_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.key(2), (8,)
    )
    assert params["kv"]["kernel"].shape == (32, 2, 2, 8)
    y_plain = plain.apply({"params": params}, x, positions)
    y_flash = flash.apply({"params": params}, x, positions)
    np.testing.assert_allclose(y_flash, y_plain, atol=2e-5)
    sizes = {
        "norm_eps": 1e-5, "rope_theta": 1e6, "num_attention_heads": 4,
        "num_key_value_heads": 2,
    }
    ref_layer = {
        "wq": params["q"]["kernel"], "wk": params["kv"]["kernel"][:, 0],
        "wv": params["kv"]["kernel"][:, 1],
        "q_norm": params["q_norm"]["scale"],
        "k_norm": params["k_norm"]["scale"],
        "wo": params["out"]["kernel"],
    }
    with jax.default_matmul_precision("highest"):
        want = _config_module().reference_attention(ref_layer, x, sizes)
    np.testing.assert_allclose(y_flash, want, atol=2e-5)
    cot = jax.random.normal(jax.random.key(3), y_plain.shape)
    grads = [
        jax.grad(lambda p: (m.apply({"params": p}, x, positions) * cot).sum())(
            params
        )
        for m in (plain, flash)
    ]
    for a, b in zip(*map(jax.tree.leaves, grads)):
        np.testing.assert_allclose(a, b, atol=2e-4)


@pytest.mark.parametrize(
    "attention", ["flash", "made", "wrapped", "causal_attention", "none"]
)
def test_only_the_flash_kernels_are_handed_fewer_kv_heads(attention):
    """lfm2's attention (4 query heads on 2 kv heads, all in one call):
    a ``functools.partial`` of the kernel and ``make_flash_attention``'s
    result get k and v two heads wide — no broadcast to the query
    heads and no sum of dK / dV over a group in the lowered gradient
    (``jnp.repeat`` and its transpose go through ``[b, s, 2, 2, d]``)
    —; any other function, and plain attention, equal head counts."""
    x = jax.random.normal(jax.random.key(0), (2, 32, 32))
    positions = jnp.arange(32)
    seen = []
    kernel = functools.partial(flash_attention, block_q=16, block_k=16)

    def wrapped(q, k, v):
        seen.append((q.shape[1], k.shape[1], v.shape[1]))
        return kernel(q, k, v)

    attention_fn = {
        "flash": kernel,
        "made": make_flash_attention(block_q=16, block_k=16),
        "wrapped": wrapped,
        "causal_attention": functools.partial(causal_attention),
        "none": None,
    }[attention]
    module = GroupedQueryAttention(_mixer_config(attention_fn=attention_fn))
    params = module.init(jax.random.key(1), x, positions)["params"]
    text = jax.jit(jax.grad(
        lambda p, x: module.apply({"params": p}, x, positions).sum(), (0, 1)
    )).lower(params, x).as_text()
    repeated = (
        "dims = [0, 1, 2, 4] : (tensor<2x32x2x8xf32>) -> "
        "tensor<2x32x2x2x8xf32>"
    )
    assert (repeated in text) == (attention not in ("flash", "made"))
    if attention == "wrapped":
        assert set(seen) == {(4, 4, 4)}


def test_default_blocks_keep_their_parameter_tree():
    """A config that asks for none of the new options builds the tree
    it built before (fused qkv, LayerNorm, ff_up / ff_down)."""
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=16, d_ff=32,
        max_seq_len=16, dtype=jnp.float32,
    )
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False
    )["params"]
    assert set(params) == {"embed", "layer_0", "layer_1", "LayerNorm_0"}
    assert set(params["layer_0"]) == {
        "LayerNorm_0", "LayerNorm_1", "attention", "ff_up", "ff_down"
    }
    assert params["layer_0"]["attention"]["qkv"]["kernel"].shape == (
        16, 3, 2, 8
    )
    with pytest.raises(ValueError, match="layer_types"):
        TransformerLM(
            TransformerConfig(
                vocab_size=64, num_layers=1, num_heads=2, d_model=16,
                d_ff=32, dtype=jnp.float32, layer_types=("window",),
            )
        ).init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))


# ---- through the trainer ----------------------------------------------


def _loader_stub(atomic, accum):
    class Loader:
        current_atomic_bsz = atomic
        current_accum_steps = accum

    return Loader()


def test_run_step_save_restore_round_trip(tmp_path, monkeypatch):
    """One ``ElasticTrainer.run_step`` of the tiny model (remat on),
    its load counters journalled as ``moe.load``, a save, and a
    restore into a fresh trainer that continues bit for bit."""
    from adaptdl_tpu import checkpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    config, sizes = _config_module(), _sizes()
    data = config.make_dataset(sizes, 5, 8)
    batch = {k: v[:4] for k, v in data.items()}
    built = _built(monkeypatch, sizes)
    trainer = built["trainer"]
    holder = {"state": trainer.init_state()}
    ck = trainer.make_checkpoint_state(
        lambda: holder["state"], lambda s: holder.__setitem__("state", s)
    )
    before = len(
        [r for r in trace.snapshot_spans() if r["name"] == "moe.load"]
    )
    trainer._calibrated.add(2)  # the calibration program has its own test
    holder["state"], metrics = trainer.run_step(
        holder["state"], batch, _loader_stub(2, 1)
    )
    assert np.isfinite(float(metrics["loss"]))
    load = metrics["counters"]["moe.load"]
    # Two micro-batches of 2 x 32 tokens, top 2: every assignment of
    # the step is held or left out, none dropped.
    assert load["held_rows"].shape == (4, 2)
    np.testing.assert_array_equal(
        load["held_rows"].sum(-1) + load["left_out"], [4 * 32 * 2] * 4
    )
    np.testing.assert_array_equal(load["dropped"], [0] * 4)
    events = [r for r in trace.snapshot_spans() if r["name"] == "moe.load"]
    assert len(events) == before + 1
    attrs = events[-1]["attrs"]
    assert attrs["dropped"] == [0, 0, 0, 0]
    assert attrs["held_rows"] == np.asarray(load["held_rows"]).tolist()
    assert attrs["held_rows_max"] == np.asarray(
        load["held_rows"]
    ).max(-1).tolist()
    checkpoint.save_all_states()
    saved = jax.tree.map(np.asarray, trainer.params_tree(holder["state"]))
    holder["state"], after = trainer.run_step(
        holder["state"], batch, _loader_stub(2, 1)
    )
    ck.unregister()

    again = _built(monkeypatch, sizes, seed=11)["trainer"]
    holder2 = {"state": again.init_state()}
    ck2 = again.make_checkpoint_state(
        lambda: holder2["state"], lambda s: holder2.__setitem__("state", s)
    )
    assert checkpoint.load_state(ck2)
    for a, b in zip(
        jax.tree.leaves(saved),
        jax.tree.leaves(again.params_tree(holder2["state"])),
    ):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert int(holder2["state"].step) == 1
    again._calibrated.add(2)
    holder2["state"], resumed = again.run_step(
        holder2["state"], batch, _loader_stub(2, 1)
    )
    assert float(resumed["loss"]) == pytest.approx(
        float(after["loss"]), rel=1e-6
    )
    ck2.unregister()


def test_calibration_program_takes_a_counting_loss(monkeypatch):
    sizes = _sizes()
    built = _built(monkeypatch, sizes)
    trainer = built["trainer"]
    state = trainer.init_state()
    data = _config_module().make_dataset(sizes, 5, 8)
    assert trainer.calibrate_accum_time(
        state, {k: v[:4] for k, v in data.items()}, 2, repeats=1
    ) > 0


def _donation_events():
    return [
        r["attrs"] for r in trace.snapshot_spans()
        if r["name"] == "step.donation"
    ]


@pytest.mark.parametrize("limit, donated", [(100_000, True), (None, False),
                                            (10**12, False)])
def test_donation_rule(limit, donated, tmp_path, monkeypatch):
    """Under a byte limit that a second state does not fit, the step
    runs donated (the jitted path: its input state is deleted);
    otherwise the AOT path runs the non-donating twin, exactly as
    before. One ``step.donation`` event carries the numbers."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    sizes = _sizes()
    built = _built(monkeypatch, sizes)
    trainer = built["trainer"]
    monkeypatch.setattr(trainer, "_device_bytes_limit", lambda: limit)
    state = trainer.init_state()
    # Nothing keeps the initial parameters as arrays.
    assert all(
        isinstance(leaf, jax.ShapeDtypeStruct)
        for leaf in jax.tree.leaves(trainer.storage.template)
    )
    assert trainer._init_params is None
    data = _config_module().make_dataset(sizes, 5, 8)
    batch = trainer.shard_batch({k: v[:4] for k, v in data.items()})
    seen = len(_donation_events())
    new_state, _ = trainer.train_step(2, 1)(state, batch)
    jax.block_until_ready(new_state)
    events = _donation_events()[seen:]
    assert len(events) == 1
    event = events[0]
    assert event["donated"] is donated
    assert event["bytes_limit"] == (-1 if limit is None else limit)
    assert event["state_bytes"] > event["grad_bytes"] > 0
    assert event["decided_by"] == ("state" if donated else "program")
    leaf = jax.tree.leaves(state.params)[0]
    assert leaf.is_deleted() is donated


@pytest.mark.parametrize(
    "limit_of, fits",
    [
        (lambda state, grad: 2 * (state + grad), True),
        (lambda state, grad: 2 * (state + grad) - 1, False),
        # What the rule compared before it counted a micro-batch's own
        # gradient: a device this size now donates without a twin.
        (lambda state, grad: 2 * state + grad, False),
        # gpt2-124m on a v5e: 16 B of state and 4 B of gradient a
        # parameter against 15.75 GiB; both readings say "fits".
        (lambda state, grad: (state + grad) * 15.75 * 2**30
         / (124.4e6 * 20), True),
    ],
    ids=["at", "one_under", "old_threshold", "gpt2_on_v5e"],
)
def test_donation_rule_first_reading(limit_of, fits, tmp_path, monkeypatch):
    """The reading before any compile: two states, the accumulated
    gradients and one micro-batch's gradient against the device's
    limit, on both sides of the threshold. A state that cannot fit
    twice never has its non-donating twin compiled."""
    from adaptdl_tpu import aot_cache

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    sizes = _sizes()
    built = _built(monkeypatch, sizes)
    trainer = built["trainer"]
    state = trainer.init_state()
    monkeypatch.setattr(trainer, "_device_bytes_limit", lambda: None)
    sized = trainer._second_state_fits(state)
    limit = int(limit_of(sized["state_bytes"], sized["grad_bytes"]))
    monkeypatch.setattr(trainer, "_device_bytes_limit", lambda: limit)
    reading = trainer._second_state_fits(state)
    assert reading["fits"] is fits
    assert reading["decided_by"] == "state"
    assert reading["needed_bytes"] == 2 * (
        sized["state_bytes"] + sized["grad_bytes"]
    )
    if fits:
        return
    monkeypatch.setattr(
        aot_cache, "load_or_compile",
        lambda *a, **k: pytest.fail("the twin was compiled"),
    )
    data = _config_module().make_dataset(sizes, 5, 8)
    batch = trainer.shard_batch({k: v[:4] for k, v in data.items()})
    seen = len(_donation_events())
    new_state, _ = trainer.train_step(2, 1)(state, batch)
    jax.block_until_ready(new_state)
    (event,) = _donation_events()[seen:]
    assert event["donated"] is True and event["decided_by"] == "state"
    assert jax.tree.leaves(state.params)[0].is_deleted()


def test_a_second_fresh_state_takes_the_parameters():
    """A trainer lets go of its initial parameters with its first
    fresh state, whoever else holds them; another takes them as an
    argument, and only the tree it was built for."""
    from adaptdl_tpu.trainer import ElasticTrainer

    params = {"w": jnp.ones((4, 3))}
    trainer = ElasticTrainer(
        lambda p, b, r: jnp.mean((b["x"] @ p["w"]) ** 2), params,
        optax.sgd(0.1), 4,
    )
    first = trainer.init_state()
    with pytest.raises(ValueError, match=r"init_state\(params\)"):
        trainer.init_state()
    second = trainer.init_state(params)
    np.testing.assert_array_equal(first.params["w"], second.params["w"])
    with pytest.raises(ValueError, match="not the parameter tree"):
        trainer.init_state({"w": jnp.ones((4, 2))})


def test_rope_theta_alone_keeps_the_parameter_tree():
    """``rope_theta`` is the plain attention's too: the fused ``qkv``
    stays (and with it every sequence-parallel path), the result
    moves; ``num_kv_heads`` and ``qk_norm`` are what change the tree."""
    from adaptdl_tpu.models import TransformerConfig, init_transformer

    base = dict(
        vocab_size=32, num_layers=1, num_heads=4, d_model=32, d_ff=32,
        max_seq_len=16, dtype=jnp.float32,
    )
    tokens = jnp.arange(16)[None] % 32
    model, params = init_transformer(TransformerConfig(**base), seq_len=16)
    far, far_params = init_transformer(
        TransformerConfig(**base, rope_theta=1e6), seq_len=16
    )
    assert jax.tree.structure(params) == jax.tree.structure(far_params)
    assert "qkv" in params["layer_0"]["attention"]
    near_out = model.apply({"params": params}, tokens, train=False)
    far_out = far.apply({"params": params}, tokens, train=False)
    assert float(jnp.abs(near_out - far_out).max()) > 1e-4
    for option in (dict(num_kv_heads=2), dict(qk_norm=True)):
        _, grouped = init_transformer(
            TransformerConfig(**base, **option), seq_len=16
        )
        assert {"q", "kv"} <= set(grouped["layer_0"]["attention"])


def _faults():
    config = _config_module()
    return (
        [("routed", f) for f in config.ROUTED_FAULTS]
        + [("conv", f) for f in config.CONV_FAULTS]
        + [("attention", f) for f in config.ATTENTION_FAULTS]
    )


@pytest.mark.parametrize("kind, fault", _faults())
def test_a_planted_fault_moves_its_layer(kind, fault):
    """Each wrong variant of a reference layer, read as the cell's
    comparison reads the system (``layer_error``; the routed layer's
    gradients too), lies thousands of times further from the right one
    than a float32 system does (1e-7): the readings behind the cell's
    limits measure something."""
    config = _config_module()
    rng = np.random.default_rng(4)
    if kind == "routed":
        layer = _layer(tokens=96, bias_scale=0.0)
        sizes = {
            "num_experts": 8, "num_experts_per_tok": 2, "first_expert": 0,
            "expert_weight_eps": 1e-6, "routed_scaling_factor": 1.0,
        }
        x = layer["x"]

        def run(variant):
            y, _ = config.reference_routed_ffn(
                layer, x, sizes, variant=variant
            )
            return y, config.reference_routed_vjp(layer, x, x, sizes, variant)

        (want, want_grads), (got, got_grads) = run(""), run(fault)
        (got_w, _), (want_w, _) = got_grads, want_grads
        assert float(config.slice_error(got_w["w2"], want_w["w2"])) > 1e-3
    else:
        d, heads, kv_heads = 32, 4, 2
        u = jnp.asarray(rng.normal(size=(2, 24, d)), jnp.float32)

        def normal(*shape):
            return jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)

        if kind == "conv":
            layer = {
                "w_in": normal(d, 3, d), "taps": normal(3, d),
                "w_out": normal(d, d),
            }
        else:
            layer = {
                "wq": normal(d, heads, 8), "wk": normal(d, kv_heads, 8),
                "wv": normal(d, kv_heads, 8), "wo": normal(d, d),
                "q_norm": 1 + normal(8), "k_norm": 1 + normal(8),
            }
        sizes = {
            "norm_eps": 1e-5, "rope_theta": 1e6,
            "num_attention_heads": heads, "num_key_value_heads": kv_heads,
        }
        want = config.mixer_reference(kind, layer, u, sizes)
        got = config.mixer_reference(kind, layer, u, sizes, fault)
    token, rms = config.layer_error(got, want)
    assert float(rms) > 1e-3 and float(token) >= float(rms)
    same = config.layer_error(want, want)
    assert float(same[0]) == 0.0 == float(same[1])
