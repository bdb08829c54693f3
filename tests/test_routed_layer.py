"""The dropless routed expert layer of ``adaptdl_tpu/models/moe.py`` and
the grouped products under it (``ops/grouped_matmul.py``; PRs 30, 40),
at small sizes in float32 against the lfm2-8b-a1b configuration's own
plain reference (``benchmark/configs/lfm2-8b-a1b.py``, which imports
nothing from ``adaptdl_tpu``): the shares of an expert-parallel layer,
the row plan, the bounded row buffer and its dropless fall-back. (The
whole model, its mixers and the trainer: ``tests/test_routed_lm.py``.)"""

import functools

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adaptdl_tpu.models import moe
from adaptdl_tpu.ops import grouped_matmul as gmm

NAME = "lfm2-8b-a1b"


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
        want = configurations.module(NAME).reference_routed_ffn(
            ref_layer, x, sizes
        )
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
    wrong, _ = configurations.module(NAME).reference_routed_ffn(
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
