"""A routed layer's comparison and the router's tie-break
(``benchmark/near_ties.py``, PR 62), with the faults PLANTED: on the
CPU, at the configurations' tiny widths and 4096 tokens (a token in a
thousand is what ``router_set_tol`` grants, so a layer needs thousands),
the configuration's own ``routed_check`` on the system's own
``RoutedFFN``.

The plant: two HELD experts' router columns are made EQUAL in the
system, so that the system sees an exact tie and takes the lower index,
and the reference's copy of the other column is scaled by ``1 + eps``,
so that the reference takes that one; every other row of the router's
input at which the two columns straddle the k-th place is replaced, so
that they do at the CHOSEN tokens alone. ``eps`` sets the gap: 0.3
margins for a swap inside, a hundred margins for one outside."""

import functools
import os
import sys

import numpy as np
import pytest

from benchmark import near_ties

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "..", "tests")
)
import configurations  # noqa: E402  (tests/configurations.py: tiny sizes)

TOKENS = 4096
PLANTED = ("smallthinker-21b-a3b", "lfm2-8b-a1b", "qwen3-next-80b-a3b")
ROUTED = PLANTED + (
    "keye-vl-2.0-30b-a3b", "kimi-linear-48b-a3b", "laguna-xs.2",
    "glm-4.7-flash",
)
A, B = 0, 1  # the two held experts whose columns are planted
INSIDE, OUTSIDE = 0.3, 100.0  # a planted swap's gap, in margins


def scores_of(name, layer, h):
    """What the configuration's ``top_k`` ranks (float64, the test's
    own arithmetic: it places the plant and measures its gap)."""
    logits = np.asarray(h, np.float64) @ np.asarray(layer["router"], np.float64)
    if name == "smallthinker-21b-a3b":
        return logits
    if name == "lfm2-8b-a1b":
        return 1 / (1 + np.exp(-logits)) + np.asarray(layer["bias"])
    exp = np.exp(logits - logits.max(-1, keepdims=True))
    return exp / exp.sum(-1, keepdims=True)


@functools.cache
def system(name):
    """(config, sizes with rows of ``TOKENS``, built, the first routed
    layer's index, the model's parameters)."""
    import jax

    os.environ["ADAPTDL_NUM_REPLICAS"] = "1"
    config = configurations.module(name)
    sizes = configurations.sizes(name)
    with configurations.rows_of_several_chunks(name):
        built = config.build(sizes, dict(configurations.GEOMETRY), 62)
    trainer = built["trainer"]
    params = jax.tree.map(
        lambda x: x.addressable_shards[0].data,
        trainer.params_tree(trainer.init_state()),
    )
    at = min(
        int(k.split("_")[1]) for k, v in params.items()
        if k.startswith("layer_") and "moe" in v
    )
    return config, {**sizes, "sequence_length": TOKENS}, built, at, params


def routed_inputs(name, seed=0):
    """x [TOKENS, d] and, where the router reads the block's input, h."""
    rng = np.random.default_rng(seed)
    d = configurations.sizes(name)["hidden_size"]
    x = rng.normal(size=(TOKENS, d)).astype(np.float32)
    if name == "smallthinker-21b-a3b":
        return [x, rng.normal(size=(TOKENS, d)).astype(np.float32)]
    return [x]


def run_system(name, moe_params, inputs):
    """The system's routed layer on ``inputs``: (y, the sets it chose)."""
    import jax
    from adaptdl_tpu.models.transformer import RoutedFFN

    config, sizes, *_ = system(name)
    with configurations.rows_of_several_chunks(name):
        cfg = config.model_config(sizes)
    y, sown = jax.jit(
        functools.partial(RoutedFFN(cfg).apply, mutable=["moe_routing"])
    )({"params": moe_params}, *inputs)
    return y, sown["moe_routing"]["experts"][0]


def reference_layer(name, params, at):
    config, sizes, *_ = system(name)
    return dict(config.reference_weights(params, sizes)["layers"][at])


def planted(name, tokens: int, margins: float):
    """The layer with ``tokens`` tokens whose two planted columns
    straddle the k-th place and a reference that prefers the other one:
    by at most ``margins`` x the margin where that is under 1 (a swap
    INSIDE), by at least ``margins`` x the margin where it is over (a
    swap OUTSIDE). Returns (reference layer, the system's parameters,
    the inputs, the planted tokens)."""
    import jax.numpy as jnp

    config, sizes, built, at, params = system(name)
    moe = dict(params[f"layer_{at}"]["moe"])
    router = np.array(moe["router"])
    router[:, B] = router[:, A]
    moe["router"] = jnp.asarray(router)
    inputs = routed_inputs(name)
    h = inputs[-1]
    layer = reference_layer(
        name, {**params, f"layer_{at}": {**params[f"layer_{at}"], "moe": moe}},
        at,
    )
    scores = scores_of(name, layer, h)
    k = np.shape(run_system(name, moe, [t[:8] for t in inputs])[1])[-1]
    others = np.delete(scores, [A, B], axis=1)
    straddle = (others > scores[:, [A]]).sum(-1) == k - 1
    logit = h.astype(np.float64) @ router[:, A].astype(np.float64)
    keep = np.flatnonzero(
        straddle & (logit > 0.5 * logit.std()) & (logit < 1.5 * logit.std())
    )[:tokens]
    assert len(keep) == tokens
    # Every other token whose planted columns straddle is given the
    # row of one whose columns do not.
    away = np.setdiff1d(np.flatnonzero(straddle), keep)
    h[away] = h[np.flatnonzero(~straddle)[: len(away)]]
    # The system takes ONE of the two on its exact tie; the reference
    # is made to prefer the other by ``eps`` of a positive logit.
    _, chosen = run_system(name, moe, inputs)
    chosen = np.asarray(chosen)
    took = A if (chosen[keep] == A).any() else B
    assert not (chosen[keep] == (A + B - took)).any()

    def preferring(eps):
        ref_router = router.copy()
        ref_router[:, A + B - took] *= np.float32(1 + eps)
        return dict(layer, router=jnp.asarray(ref_router))

    unit = gaps(name, preferring(1e-3), inputs, keep) / 1e-3  # a gap an eps
    eps = margins * near_ties.NEAR_TIE_MARGIN / (
        unit.max() if margins < 1 else unit.min()
    )
    return preferring(eps), moe, [jnp.asarray(t) for t in inputs], keep


def gaps(name, layer, inputs, keep):
    """The planted tokens' gaps over ``rms(s)``, by the test's own
    arithmetic."""
    scores = scores_of(name, layer, np.asarray(inputs[-1]))
    return np.abs(scores[keep, A] - scores[keep, B]) / np.sqrt(
        np.mean(scores**2)
    )


def check(name, layer, moe, inputs, y, experts):
    import jax

    config, sizes, built, *_ = system(name)
    found = jax.jit(config.routed_check(built, sizes))(
        layer, moe, *inputs, y, experts
    )
    return {k: float(v) for k, v in found.items()}


def limits(name):
    """(worst token, rms) a routed layer is held to."""
    return configurations.module(name).LAYER_LIMITS["routed"]


def refused(name, result) -> bool:
    config = configurations.module(name)
    token, rms = limits(name)
    return not (
        result["routed_token_err"] <= token
        and result["routed_rms_err"] <= rms
        and near_ties.within(
            result, config.ROUTER_SET_MISMATCH_SHARE, TOKENS
        )
    )


@pytest.mark.parametrize("name", PLANTED)
def test_a_swap_inside_the_margin_is_compared_on_the_systems_set(name):
    """(a) Three tokens whose sets differ by a swap of a few ulps: the
    layer is correct, the tokens are counted, and the worst token reads
    what it reads with no swap at all."""
    layer, moe, inputs, keep = planted(name, 3, INSIDE)
    assert gaps(name, layer, inputs, keep).max() < near_ties.NEAR_TIE_MARGIN
    y, experts = run_system(name, moe, inputs)
    got = check(name, layer, moe, inputs, y, experts)
    assert got[near_ties.TOKENS] == 3
    assert 0 < got[near_ties.GAP] <= near_ties.NEAR_TIE_MARGIN
    assert not refused(name, got)
    # The same layer, its reference preferring what the system took.
    same = dict(layer, router=moe["router"])
    clean = check(name, same, moe, inputs, y, experts)
    assert clean[near_ties.TOKENS] == 0 and clean[near_ties.GAP] == 0
    # (The planted reference's column differs by ``eps``, up to 1e-4
    # of a weight: nowhere near a swapped expert's ~0.5.)
    assert got["routed_token_err"] < clean["routed_token_err"] + 1e-3
    # Without the rule the same run is refused, as before PR 62.
    before = check(name, layer, moe, inputs, y, None)
    assert before["routed_token_err"] > 0.1


@pytest.mark.parametrize("name", PLANTED)
def test_a_swap_a_hundred_margins_wide_is_refused(name):
    """(b) The same swap at 100 x the margin is a router that chose
    another expert: compared on the reference's set, far over the
    limit."""
    layer, moe, inputs, keep = planted(name, 3, OUTSIDE)
    assert gaps(name, layer, inputs, keep).min() > 90 * near_ties.NEAR_TIE_MARGIN
    y, experts = run_system(name, moe, inputs)
    got = check(name, layer, moe, inputs, y, experts)
    assert got[near_ties.TOKENS] == 0
    assert got["routed_token_err"] > 3 * limits(name)[0]
    assert refused(name, got)


@pytest.mark.parametrize("name", PLANTED)
def test_a_near_tied_token_is_still_compared(name):
    """(c) A near-tied token whose output is WRONG for the system's own
    set (the product of the expert it took is dropped) is refused."""
    import jax.numpy as jnp

    layer, moe, inputs, keep = planted(name, 3, INSIDE)
    y, experts = run_system(name, moe, inputs)
    took = A if (np.asarray(experts)[keep] == A).any() else B
    without = dict(moe, w_down=moe["w_down"].at[took].set(0.0))
    dropped, _ = run_system(name, without, inputs)
    wrong = jnp.asarray(y).at[keep[0]].set(dropped[keep[0]])
    got = check(name, layer, moe, inputs, wrong, experts)
    assert got[near_ties.TOKENS] == 3
    assert got["routed_token_err"] > 3 * limits(name)[0]
    assert refused(name, got)


@pytest.mark.parametrize("name", PLANTED)
def test_more_near_ties_than_the_router_is_granted_are_refused(name):
    """(d) ``router_set_tol`` grants a token in a thousand: six
    near-tied tokens of 4096 are a router that disagrees too often,
    however well each is compared."""
    config = configurations.module(name)
    layer, moe, inputs, keep = planted(name, 6, INSIDE)
    y, experts = run_system(name, moe, inputs)
    got = check(name, layer, moe, inputs, y, experts)
    assert got[near_ties.TOKENS] == 6
    assert got[near_ties.TOKENS] > config.ROUTER_SET_MISMATCH_SHARE * TOKENS
    token, _ = limits(name)
    assert got["routed_token_err"] <= token
    assert refused(name, got)


@pytest.mark.parametrize("name", ROUTED)
def test_with_no_mismatch_nothing_but_the_new_keys_moves(name):
    """(e) Where the sets agree the reference handed the system's sets
    computes what it computes alone, BIT FOR BIT: the layer, its row
    counts and every gradient; and the configuration's ``routed_check``
    reads the same under every key it had (keye's lives inside its
    ``layer_checks``: its two reference functions carry the case)."""
    import jax

    config, sizes, built, at, params = system(name)
    moe = params[f"layer_{at}"]["moe"]
    layer = reference_layer(name, params, at)
    inputs = routed_inputs(name, seed=1)
    y, experts = run_system(name, moe, inputs)
    inputs = [jax.numpy.asarray(t) for t in inputs]

    def alone(layer, *inputs):
        with jax.default_matmul_precision("highest"):
            return (
                config.reference_routed_ffn(layer, *inputs, sizes),
                config.reference_routed_vjp(
                    layer, *inputs, inputs[0], sizes
                ),
            )

    def handed(layer, experts, *inputs):
        with jax.default_matmul_precision("highest"):
            y, counts, ties = config.reference_routed_ffn(
                layer, *inputs, sizes, system=experts
            )
            grads, back = config.reference_routed_vjp(
                layer, *inputs, inputs[0], sizes, system=experts
            )
        return ((y, counts), grads), (ties, back)

    want = jax.jit(alone)(layer, *inputs)
    got, ties = jax.jit(handed)(layer, experts, *inputs)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for tie in ties:
        assert int(tie.tokens) == 0 and float(tie.gap) == 0.0
        assert float(tie.least) > 0
    if not hasattr(config, "routed_check"):
        return
    new = check(name, layer, moe, inputs, y, experts)
    old = check(name, layer, moe, inputs, y, None)
    assert set(new) - set(old) == {
        near_ties.TOKENS, near_ties.GAP, near_ties.LEAST
    }
    assert {k: new[k] for k in old} == old


def test_settle_reads_a_written_out_case():
    """The rule on four tokens written out by hand: sets that agree,
    a swap inside the margin, a swap outside, and a swap inside the
    margin beside an expert that is not."""
    import jax.numpy as jnp

    tiny = near_ties.NEAR_TIE_MARGIN / 4
    scores = jnp.asarray(
        [
            [3.0, 2.0, 1.0, 0.0],
            [3.0, 2.0, 2.0 - tiny, 0.0],
            [3.0, 2.0, 1.9, 0.0],
            [3.0, 2.0, 2.0 - tiny, 0.0],
        ],
        jnp.float32,
    )
    reference = jnp.asarray([[0, 1]] * 4)
    system = jnp.asarray([[1, 0], [2, 0], [0, 2], [2, 3]])
    chosen, ties = near_ties.settle(scores, reference, system)
    assert np.asarray(chosen).tolist() == [[0, 1], [2, 0], [0, 1], [0, 1]]
    assert int(ties.tokens) == 1
    scale = float(jnp.sqrt(jnp.mean(scores**2)))
    assert float(ties.gap) == pytest.approx(tiny / scale, rel=0.2)
    assert float(ties.least) == pytest.approx(1.0 / scale, rel=1e-5)
    assert near_ties.NEAR_TIE_MARGIN <= 1e-5
