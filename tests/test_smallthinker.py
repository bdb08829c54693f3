"""What the smallthinker-21b-a3b configuration forced in the model (PR
60), at small sizes against the configuration's own plain reference
(``benchmark/configs/smallthinker-21b-a3b.py``, which imports nothing
from ``adaptdl_tpu``): a router that reads the block's INPUT, ahead of
the mixer (its gradient arrives there, none of it at what the experts
multiply), ReGLU experts and their count of exact zeros, rotary by
layer kind (the full layer has none), groups of seven query heads a kv
head, and the share of an expert-parallel layer. (The whole model, and
that each of its three distinctive readings taken the other way FAILS
the comparison: ``tests/test_smallthinker_model.py``; the band kernels
past one K/V block of reach and the runs of heads under a group:
``tests/test_window_attention.py``; that the configurations of before
are the programs they were: ``tests/test_step_digests.py``.)"""

import dataclasses
import functools

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from configurations import rel

from adaptdl_tpu import trace
from adaptdl_tpu.models import moe
from adaptdl_tpu.models.transformer import (
    AttentionKind,
    GroupedQueryAttention,
    RoutedFFN,
    TransformerConfig,
    TransformerLM,
    Yarn,
    routed_lm_loss_fn,
)
from adaptdl_tpu.ops.flash_attention import flash_attention

NAME = "smallthinker-21b-a3b"
FLASH = functools.partial(flash_attention, block_q=16, block_k=16)


def _system(monkeypatch):
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    data = config.make_dataset(sizes, 5, 4)
    return config, sizes, built, params, data


def test_the_programs_flop_count_is_the_configurations():
    """``adaptdl_tpu/flops.py`` at the tiny sizes against the
    configuration's own table: the router's product over all 16
    experts, 3 x 4 / 16 held experts a token, three bands of 24 keys
    and one causal half, no dense FFN."""
    from adaptdl_tpu.flops import transformer_train_flops

    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    own = transformer_train_flops(config.model_config(sizes), 2, 64)
    assert own.total / (2 * 64) == pytest.approx(
        config.train_flops_per_unit(sizes), rel=1e-6
    )
    parts = config.forward_flops_per_token(sizes)
    assert parts["router"] == 4 * 2 * 32 * 16
    assert parts["sliding_attention"] == pytest.approx(
        3 * 2 * 2 * 8 * 14 * sum(min(i + 1, 24) for i in range(64)) / 64
    )


# ---- the routed layer ------------------------------------------------------


def _layer(keys, d, f, experts):
    return {
        "router": 0.5 * jax.random.normal(keys[0], (d, experts)),
        "w1": jax.random.normal(keys[1], (experts, d, f)) / d**0.5,
        "w3": jax.random.normal(keys[2], (experts, d, f)) / d**0.5,
        "w2": jax.random.normal(keys[3], (experts, f, d)) / f**0.5,
    }


def _share(layer, first, held):
    at = slice(first, first + held)
    return {"params": {
        "router": layer["router"], "w_gate": layer["w1"][at],
        "w_up": layer["w3"][at], "w_down": layer["w2"][at],
    }}


def test_the_eight_shares_add_up_to_the_whole_layer():
    """A 64-expert layer cut into the deployment's 8 shares of 8
    (``first_expert`` 0, 8, .., 56): what the eight chips compute, each
    routing on the block's input over all 64 and gating with ``relu``,
    adds up to the uncut reference's layer; every share counts its own
    experts' rows as the reference does."""
    config = configurations.module(NAME)
    sizes = configurations.sizes(
        NAME, router_width=64, experts_held=8, moe_num_primary_experts=8,
        num_experts_per_tok=6, moe_num_active_primary_experts=6,
    )
    keys = jax.random.split(jax.random.key(11), 6)
    whole = _layer(keys, 32, 16, 64)
    x = jax.random.normal(keys[4], (64, 32))
    h = jax.random.normal(keys[5], (64, 32))
    with jax.default_matmul_precision("highest"):
        want, counts = config.reference_routed_ffn(
            whole, x, h, {**sizes, "first_expert": 0}
        )
    assert int(counts.sum()) == 64 * 6
    total = jnp.zeros_like(x)
    for first in range(0, 64, 8):
        cfg = config.model_config({**sizes, "first_expert": first})
        y, sown = jax.jit(functools.partial(
            RoutedFFN(cfg).apply, mutable=["moe_load", "moe_routing"]
        ))(_share(whole, first, 8), x, h)
        np.testing.assert_array_equal(
            sown["moe_load"]["held_rows"][0], counts[first:first + 8]
        )
        total = total + y
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # Each reading is in the sum: with silu, or routed on x, the
    # reference differs.
    for variant in config.ROUTED_FAULTS:
        faulty, _ = config.reference_routed_ffn(
            whole, x, h, {**sizes, "first_expert": 0}, variant=variant
        )
        assert rel(faulty, want) > 0.1, variant


def test_the_routers_gradient_arrives_at_the_block_input(monkeypatch):
    """A routed layer alone, forward and backward with respect to
    every leaf, to ``x`` (what the experts multiply) and to ``h`` (what
    the router reads): the reference's, and ``h``'s gradient is the
    router's alone — it vanishes with the weights' cotangent."""
    config, sizes, built, params, _ = _system(monkeypatch)
    layer = config.reference_weights(params, sizes)["layers"][1]
    layer = {k: v for k, v in layer.items() if k in config.ROUTED_LEAVES}
    moe_params = params["layer_1"]["moe"]
    keys = jax.random.split(jax.random.key(3), 3)
    x, h, g = (jax.random.normal(k, (64, 32)) for k in keys)
    before = len(trace.snapshot_spans())
    got_w, got_x, got_h = jax.jit(built["routed_vjp"])(moe_params, x, h, g)
    (event,) = [
        r["attrs"] for r in trace.snapshot_spans()[before:]
        if r["name"] == "moe.schedule"
    ]
    assert (event["routed_on"], event["activation"], event["router"]) == (
        "block_input", "relu", "softmax"
    )
    with jax.default_matmul_precision("highest"):
        want_w, want_x, want_h = jax.jit(functools.partial(
            config.reference_routed_vjp, sizes=sizes
        ))(layer, x, h, g)
    assert rel(got_x, want_x) < 1e-4 and rel(got_h, want_h) < 1e-4
    assert float(jnp.abs(want_h).max()) > 1e-3
    for name, path in config.ROUTED_LEAVES.items():
        assert rel(config._leaf(got_w, path), want_w[name]) < 1e-4, name
    # The same layer routed on its experts' input: another function,
    # and nothing of it reaches a second operand.
    on_x = dataclasses.replace(
        config.model_config(sizes), experts_routed_on="ffn_input"
    )
    y_on_h = RoutedFFN(config.model_config(sizes)).apply(
        {"params": moe_params}, x, h
    )
    y_on_x = RoutedFFN(on_x).apply({"params": moe_params}, x)
    assert rel(y_on_x, y_on_h) > 0.1
    before = len(trace.snapshot_spans())
    jax.eval_shape(RoutedFFN(on_x).apply, {"params": moe_params}, x)
    (event,) = [
        r["attrs"] for r in trace.snapshot_spans()[before:]
        if r["name"] == "moe.schedule"
    ]
    assert (event["routed_on"], event["activation"]) == ("ffn_input", "relu")


@pytest.mark.parametrize("pieces_from", [None, 1.5])
def test_hidden_zero_counts_the_placed_rows_exact_zeros(pieces_from):
    """``hidden_zero`` under ``relu`` is the count of exact zeros of
    ``relu(x W_gate) * (x W_up)`` over the rows PLACED for held experts
    (one pass, and walked in pieces: the count is summed over them);
    under ``silu`` the layer journals no such counter and its result
    is a plain array as before."""
    keys = jax.random.split(jax.random.key(5), 5)
    layer = _layer(keys, 32, 16, 16)
    # (Every token's three choices onto the four held experts: with
    # ``pieces_from`` 1.5 the plan is walked in several pieces.)
    x = 1.0 + jax.random.normal(keys[4], (256, 32))
    router = layer["router"].at[:, :4].add(2.0)
    operands = (x, router, None, layer["w1"][:4], layer["w3"][:4],
                layer["w2"][:4])
    said = dict(
        experts_total=16, first_expert=0, top_k=3, router_kind="softmax",
        pieces_from=pieces_from,
    )
    y, load = jax.jit(functools.partial(
        moe.routed_experts, activation="relu", **said
    ))(*operands)
    assert int(load["left_out"]) < 16  # of 768 assignments
    if pieces_from is not None:
        bound = moe.rows_bound(256, 3, 4, 16, 8, pieces_from)
        assert int(load["rows_walked"]) >= 2 * bound
    experts = np.asarray(load["experts"])
    zeros = 0
    for e in range(4):
        rows = x[(experts == e).any(-1)]
        hidden = jax.nn.relu(rows @ layer["w1"][e]) * (rows @ layer["w3"][e])
        zeros += int((hidden == 0).sum())
    assert int(load["hidden_zero"]) == zeros > 0
    assert int(load["dropped"]) == 0
    y_silu, load_silu = jax.jit(functools.partial(
        moe.routed_experts, activation="silu", **said
    ))(*operands)
    assert "hidden_zero" not in load_silu and rel(y_silu, y) > 0.05
    with pytest.raises(ValueError, match="activation"):
        moe.routed_experts(*operands, activation="gelu", **said)


def test_a_prediction_module_routes_on_its_own_blocks_input():
    """``PredictionModule``'s block is a block like any other: with the
    router on the block's input and ReGLU experts the loss runs, and
    ``moe.load`` holds the module's router as its last layer, its
    ``hidden_zero`` beside the trunk's."""
    cfg = TransformerConfig(
        vocab_size=97, num_layers=2, num_heads=4, num_kv_heads=2, d_model=32,
        d_ff=48, max_seq_len=32, dtype=jnp.float32, norm="rmsnorm",
        ffn="swiglu", head_dim=8, experts_total=8, experts_held=4,
        experts_top_k=2, d_expert=16, experts_router="softmax",
        experts_routed_on="block_input", experts_activation="relu",
        tie_embeddings=False, mtp_depth=1,
    )
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 97)
    params = model.init(
        jax.random.key(1), tokens, train=False, next_tokens=tokens
    )["params"]
    loss, counters = jax.jit(routed_lm_loss_fn(model, 16))(
        params, {"inputs": tokens, "targets": tokens}, jax.random.key(2)
    )
    assert np.isfinite(float(loss))
    load = counters["moe.load"]
    assert load["hidden_zero"].shape == load["dropped"].shape == (3,)
    assert int(load["hidden_zero"].min()) > 0


# ---- the mixers ------------------------------------------------------------


@pytest.mark.parametrize("name", ["sliding", "full"])
def test_mixer_equals_the_reference(monkeypatch, name):
    """The system's mixer alone (14 heads on 2 kv heads: groups of
    seven; rotary on the sliding kind and NONE on the full one; the
    band or the full kernels) against the reference's, forward and the
    gradient of every leaf and of the input; and the schedule's event
    says what was traced."""
    config, sizes, built, params, _ = _system(monkeypatch)
    at = config.checked_mixers(sizes)[name]
    layer = config.reference_weights(params, sizes)["layers"][at]["attention"]
    mixer_params = params[f"layer_{at}"]["attention"]
    u = jax.random.normal(jax.random.key(7), (2, 64, 32))
    cfg = config.model_config(sizes, FLASH)
    module = GroupedQueryAttention(cfg, config.MIXER_KINDS[name])
    before = len(trace.snapshot_spans())
    got = jax.jit(module.apply)({"params": mixer_params}, u, jnp.arange(64))
    want = jax.jit(
        lambda layer, u: config.reference_mixer(name, layer, u, sizes)
    )(layer, u)
    assert rel(got, want) < 2e-5
    (event,) = [
        r["attrs"] for r in trace.snapshot_spans()[before:]
        if r["name"] == "attn_kind.schedule"
    ]
    assert (event["kind"], event["heads"], event["kv_heads"],
            event["heads_a_call"]) == (config.MIXER_KINDS[name], 14, 2, 14)
    assert (event["window"], event["rotary_dims"], event["rope_theta"]) == (
        (24, 8, 1.5e6) if name == "sliding" else (0, 0, 1.5e6)
    )
    got_w, got_u = jax.jit(functools.partial(built["mixer_vjp"], name))(
        mixer_params, u[:1], u[:1]
    )
    want_w, want_u = jax.jit(
        lambda layer, u: config.reference_mixer_vjp(name, layer, u, u, sizes)
    )(layer, u[:1])
    assert rel(got_u, want_u) < 1e-4
    for path, leaf in config.MIXER_LEAVES.items():
        assert rel(config._leaf(got_w, path), want_w[leaf]) < 1e-4, leaf
    # A fault of the reference's differs: the comparison can tell.
    for variant in ("rotary_swapped",) + (
        ("band_4095", "band_4097", "band_ahead") if name == "sliding" else ()
    ):
        wrong = jax.jit(functools.partial(
            config.reference_mixer, name, sizes=sizes, variant=variant
        ))(layer, u)
        assert rel(wrong, want) > 1e-3, variant


def test_a_full_layer_without_rotary_knows_no_positions(monkeypatch):
    """The full kind turns nothing: shifted positions give the same
    numbers to the bit, where the sliding kind's move."""
    config, sizes, built, params, _ = _system(monkeypatch)
    cfg = config.model_config(sizes)
    u = jax.random.normal(jax.random.key(9), (1, 64, 32))
    for kind, at, same in (
        ("full_attention", 0, True), ("sliding_attention", 1, False)
    ):
        module = GroupedQueryAttention(cfg, kind)
        variables = {"params": params[f"layer_{at}"]["attention"]}
        here = module.apply(variables, u, jnp.arange(64))
        there = module.apply(variables, u, 7 * jnp.arange(64))
        assert bool(jnp.array_equal(here, there)) == same, kind


def test_rotary_by_kind_is_validated_and_restates_the_config():
    """None = the config's ``rope``; a kind without rotary takes
    neither YaRN nor rotated lanes; kimi's ``rope=False`` with a kind
    that says nothing stays without."""
    base = dict(num_layers=1, num_heads=4, num_kv_heads=2, d_model=32,
                head_dim=8)
    cfg = TransformerConfig(**base, attention_kinds=(
        ("full_attention", AttentionKind(rope=False)),
        ("sliding_attention", AttentionKind(window=8)),
    ))
    assert cfg.attention_kind("full_attention").rope is False
    assert cfg.attention_kind("sliding_attention").rope is True
    off = TransformerConfig(**base, rope=False, attention_kinds=(
        ("full_attention", AttentionKind()),
        ("sliding_attention", AttentionKind(window=8, rope=True)),
    ))
    assert off.attention_kind("full_attention").rope is False
    assert off.attention_kind("sliding_attention").rope is True
    for bad, match in (
        (AttentionKind(rope=False, yarn=Yarn(4.0, 64)), "yarn without rope"),
        (AttentionKind(rope=False, rotary_dims=4), "rotary_dims without"),
    ):
        with pytest.raises(ValueError, match=match):
            TransformerConfig(
                **base, attention_kinds=(("full_attention", bad),)
            )
    for field, match in (
        ("experts_routed_on", "experts_routed_on"),
        ("experts_activation", "experts_activation"),
    ):
        with pytest.raises(ValueError, match=match):
            TransformerConfig(**base, **{field: "other"})
