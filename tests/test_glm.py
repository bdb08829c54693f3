"""What the glm-4.7-flash configuration forced in the model (PR 56), at
small sizes against the configuration's own plain reference
(``benchmark/configs/glm-4.7-flash.py``, which imports nothing from
``adaptdl_tpu``): latent attention with a query bottleneck and a
rotary turn on the 64-wide part alone, v wider than the nope part; the
multi-token-prediction module, which has no tables of its own; the
loss's second stream; the share of an expert-parallel layer, the
trunk's and the module's; and that kimi's form of latent attention is
the program of before. (The whole model, its gradients and a job:
``tests/test_glm_model.py``.)"""

import dataclasses
import functools
import hashlib

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from configurations import rel

from adaptdl_tpu import trace
from adaptdl_tpu.flops import transformer_train_flops
from adaptdl_tpu.models.transformer import (
    LatentAttention,
    PredictionModule,
    TransformerConfig,
    TransformerLM,
    block_remat,
    init_transformer,
    routed_lm_loss_fn,
)
from adaptdl_tpu.ops.flash_attention import flash_attention

NAME = "glm-4.7-flash"
FLASH = functools.partial(flash_attention, block_q=16, block_k=16)


def _events(name, since=0):
    return [
        r["attrs"] for r in trace.snapshot_spans()[since:]
        if r["name"] == name
    ]


def _mixer_case(cfg, seed=0, seq=64):
    x = jax.random.normal(jax.random.key(seed + 1), (2, seq, cfg.d_model))
    positions = jnp.arange(seq)
    params = LatentAttention(cfg).init(
        jax.random.key(seed), x, positions
    )["params"]
    # Norm scales away from 1, so that a scale left out shows.
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 0.1 * jax.random.normal(
            jax.random.key(7), leaf.shape
        ) if "scale" in jax.tree_util.keystr(path) else leaf,
        params,
    )
    return params, x, positions


# ---- latent attention: the bottleneck, the rotary, v at its own width ------


@pytest.mark.parametrize("attn", [None, FLASH], ids=["plain", "flash"])
def test_mixer_equals_the_reference(attn):
    """q through ``q_a`` / ``q_norm`` / ``q_b``, rotary on every head's
    4-wide part and on the ONE shared key part, 12 nope lanes untouched,
    v 16 wide: output and the gradient of every leaf and of the input,
    through plain attention and through the flash kernels."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    cfg = config.model_config(sizes, attn)
    params, x, positions = _mixer_case(cfg)
    layer = config.mla_weights(params)

    def system(params, x):
        y = LatentAttention(cfg).apply({"params": params}, x, positions)
        return jnp.sum(y * x), y

    def reference(layer, x):
        y = config.reference_mixer(layer, x, sizes)
        return jnp.sum(y * x), y

    (got_w, got_x), got = jax.jit(
        jax.grad(system, argnums=(0, 1), has_aux=True)
    )(params, x)
    (want_w, want_x), want = jax.jit(
        jax.grad(reference, argnums=(0, 1), has_aux=True)
    )(layer, x)
    assert rel(got, want) < 2e-5
    assert rel(got_x, want_x) < 5e-5
    errors = config.mixer_leaf_errors(got_w, want_w)
    assert set(errors) == {
        "w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb", "w_out"
    }
    assert all(float(e) < 5e-5 for e in errors.values()), errors


@pytest.mark.parametrize("variant", ["no_rotary", "bf16_angles"])
def test_the_rotary_is_in_the_numbers(variant):
    """A reference without the turn, or with its angles in bfloat16,
    is another function: neither hides inside a tolerance."""
    config = configurations.module(NAME)
    sizes = configurations.sizes(NAME, rope_theta=100.0)
    cfg = config.model_config(sizes)
    params, x, _ = _mixer_case(cfg)
    layer = config.mla_weights(params)
    want = config.reference_mixer(layer, x, sizes)
    other = config.reference_mixer(layer, x, sizes, variant)
    assert float(config.layer_error(other, want)[1]) > 1e-3


def test_only_the_rope_part_turns_and_the_key_part_is_shared():
    """Under ``rope`` a row's first position is unturned (angle 0) and
    the others are not; without it the mixer is the one of before
    (``no_rotary``). The nope lanes never see a position: a model whose
    rope part is all zeros gives the same numbers with and without."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    cfg = config.model_config(sizes)
    params, x, positions = _mixer_case(cfg)
    turned = LatentAttention(cfg).apply({"params": params}, x, positions)
    plain = LatentAttention(dataclasses.replace(cfg, rope=False)).apply(
        {"params": params}, x, positions
    )
    layer = config.mla_weights(params)
    assert rel(
        plain, config.reference_mixer(layer, x, sizes, "no_rotary")
    ) < 2e-5
    assert rel(turned, plain) > 1e-3
    np.testing.assert_allclose(turned[:, 0], plain[:, 0], rtol=1e-5, atol=1e-6)
    # Zero the rope part of q_b's and kv_a's columns: positions vanish.
    nope, rank = sizes["qk_nope_head_dim"], sizes["kv_lora_rank"]
    blind = jax.tree.map(lambda leaf: leaf, params)
    blind["q_b"]["kernel"] = params["q_b"]["kernel"].at[..., nope:].set(0.0)
    blind["kv_a"]["kernel"] = params["kv_a"]["kernel"].at[:, rank:].set(0.0)
    np.testing.assert_allclose(
        LatentAttention(cfg).apply({"params": blind}, x, positions),
        LatentAttention(dataclasses.replace(cfg, rope=False)).apply(
            {"params": blind}, x, positions
        ),
        rtol=1e-5, atol=1e-6,
    )


def test_without_the_bottleneck_it_is_kimis_mixer():
    """``q_lora_rank`` 0 and ``rope`` False: the parameter tree has
    ``q`` (no ``q_a`` / ``q_norm`` / ``q_b``) and the numbers are those
    of kimi-linear-48b-a3b's reference."""
    kimi = configurations.module("kimi-linear-48b-a3b")
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, d_model=32, d_ff=48,
        dtype=jnp.float32, norm="rmsnorm", rope=False,
        layer_types=("mla",), kv_lora_rank=12, qk_nope_head_dim=12,
        qk_rope_head_dim=4, v_head_dim=8,
    )
    params, x, positions = _mixer_case(cfg)
    assert set(params) == {"q", "kv_a", "kv_norm", "kv_b", "out"}
    got = LatentAttention(cfg).apply({"params": params}, x, positions)
    with jax.default_matmul_precision("highest"):
        want = kimi.reference_mla(
            kimi.mla_weights(params), x,
            {"kv_lora_rank": 12, "qk_nope_head_dim": 12,
             "rms_norm_eps": cfg.norm_eps},
        )
    assert rel(got, want) < 2e-5


def _kimi_form_lowered() -> str:
    """The lowered gradient of kimi's form of the mixer at a small size
    (runs on the parent commit too: only fields it has)."""
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, d_model=32, d_ff=48,
        dtype=jnp.bfloat16, norm="rmsnorm", rope=False,
        layer_types=("mla",), kv_lora_rank=12, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8,
    )
    x = jnp.zeros((2, 32, 32), jnp.bfloat16)
    mixer = LatentAttention(cfg)
    params = jax.eval_shape(
        lambda: mixer.init(jax.random.key(0), x, None)["params"]
    )

    def objective(params, x):
        return jnp.sum(mixer.apply({"params": params}, x, None))

    return jax.jit(jax.grad(objective, argnums=(0, 1))).lower(
        params, x
    ).as_text()


# sha256 of ``_kimi_form_lowered()`` on the commit before this PR
# (b23aacb: the function run with the parent checkout on PYTHONPATH).
KIMI_FORM_BEFORE = (
    "169ba1ac009aff83cc88d2262d0f6fdc79d23f217495348d0f9930b55f1f48db"
)


def test_kimis_form_lowers_to_the_program_of_before():
    """The bottleneck and the rotary are additions: a configuration
    with neither (kimi-linear-48b-a3b's mla layer) lowers to the text
    the parent commit lowered it to, so its AOT-cache key and its
    numbers stand."""
    assert hashlib.sha256(
        _kimi_form_lowered().encode()
    ).hexdigest() == KIMI_FORM_BEFORE


def test_mla_schedule_says_the_bottleneck_and_the_rotated_lanes():
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    cfg = config.model_config(sizes)
    params, x, positions = _mixer_case(cfg)
    since = len(trace.snapshot_spans())
    LatentAttention(cfg).apply({"params": params}, x, positions)
    (attrs,) = _events("mla.schedule", since)
    assert (
        attrs["qk_width"], attrs["v_width"], attrs["latent_rank"],
        attrs["q_lora_rank"], attrs["positions"], attrs["rotary_dims"],
    ) == (16, 16, 12, 16, "rotary", 4)
    since = len(trace.snapshot_spans())
    LatentAttention(dataclasses.replace(cfg, rope=False)).apply(
        {"params": params}, x, positions
    )
    (attrs,) = _events("mla.schedule", since)
    assert (attrs["positions"], attrs["rotary_dims"]) == ("none", 0)


@pytest.mark.parametrize(
    "changes, match",
    [
        (dict(rope=True, qk_rope_head_dim=0), "rope"),
        (dict(rope=True, qk_rope_head_dim=3), "rope"),
        (dict(layer_types=("full_attention",), q_lora_rank=8),
         "q_lora_rank"),
        (dict(mtp_depth=2), "mtp_depth"),
        (dict(mtp_depth=1, loop_passes=2), "mtp_depth"),
    ],
)
def test_config_refuses_at_build_with_the_fields_name(changes, match):
    base = dict(
        vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
        dtype=jnp.float32, layer_types=("mla",), rope=False,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8,
    )
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**base, **changes})


# ---- the prediction module -----------------------------------------------


@functools.cache
def _tiny_model():
    """Two trunk layers (dense, routed) and the module, rows of 32;
    initialised under ``jit`` (an eager init is most of a test)."""
    config = configurations.module(NAME)
    sizes = configurations.sizes(NAME, num_hidden_layers=2, sequence_length=32)
    cfg = config.model_config(sizes)
    model = TransformerLM(cfg)
    dummy = jnp.zeros((1, 32), jnp.int32)
    params = jax.jit(
        lambda key: model.init(key, dummy, train=False, next_tokens=dummy)
    )(jax.random.key(2))["params"]
    data = config.make_dataset(sizes, 5, 2)
    batch = {k: jnp.asarray(v[:2]) for k, v in data.items()}
    return config, sizes, cfg, model, params, batch


def test_the_module_has_no_tables_of_its_own():
    """Two norms, the ``[2 d, d]`` projection, one whole routed block
    with its own router and experts, a final norm — and neither an
    embedding nor an output table; without ``next_tokens`` it does not
    run and the model answers as a model without it."""
    _, sizes, cfg, model, params, batch = _tiny_model()
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda: init_transformer(cfg, seq_len=32)[1])
    )
    assert set(params["mtp"]) == {
        "enorm", "hnorm", "eh_proj", "layer_2", "norm"
    }
    assert params["mtp"]["eh_proj"]["kernel"].shape == (64, 32)
    assert set(params["mtp"]["layer_2"]) == set(params["layer_1"])
    assert params["mtp"]["layer_2"]["moe"]["router"].shape == (32, 16)
    tables = [
        jax.tree_util.keystr(path)
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if leaf.shape == (sizes["vocab_size"], 32)
    ]
    assert sorted(tables) == ["['embed']['embedding']", "['lm_head']"]
    alone = jax.jit(model.apply)({"params": params}, batch["inputs"])
    trunk, predicted = jax.jit(
        lambda p, b: model.apply(p, b["inputs"], next_tokens=b["targets"])
    )({"params": params}, batch)
    np.testing.assert_array_equal(alone, trunk)
    assert predicted.shape == trunk.shape
    plain = TransformerLM(dataclasses.replace(cfg, mtp_depth=0))
    with pytest.raises(ValueError, match="next_tokens"):
        jax.eval_shape(
            lambda p, b: plain.apply(
                p, b["inputs"], next_tokens=b["targets"]
            ),
            {"params": {k: v for k, v in params.items() if k != "mtp"}},
            batch,
        )


def test_the_modules_block_is_remated_as_the_trunks_are():
    _, _, cfg, _, _, _ = _tiny_model()
    since = len(trace.snapshot_spans())
    block_remat(cfg, (2, 32))
    (attrs,) = _events("remat.policy", since)
    assert attrs["blocks"] == 3  # two of the trunk and the module's


_REFERENCE: dict = {}


def _reference_loss(config, sizes, params, batch):
    """The reference's (L, parts) of the tiny model, once for both
    cases of the test below."""
    if "loss" not in _REFERENCE:
        _REFERENCE["loss"] = jax.jit(
            lambda w, b: config.reference_loss(
                w, b["inputs"], b["targets"], sizes
            )
        )(config.reference_weights(params, sizes), batch)
    return _REFERENCE["loss"]


@pytest.mark.parametrize("chunk", [None, 16], ids=["logits", "streamed"])
def test_the_loss_is_both_terms_and_the_last_position_weighs_nothing(chunk):
    """``L = mean CE(logits, t_{i+1}) + 0.1 x mean over the s - 1
    targeted positions of CE(logits', t_{i+2})``: the reference's two
    terms, the counters, and a row's last position — whose 'target'
    wraps around to the row's first — out of the module's mean."""
    config, sizes, cfg, model, params, batch = _tiny_model()
    since = len(trace.snapshot_spans())
    loss, counters = jax.jit(routed_lm_loss_fn(model, chunk))(
        params, batch, jax.random.key(0)
    )
    want, parts = _reference_loss(config, sizes, params, batch)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    got = counters["mtp.loss"]
    assert float(got["main"]) == pytest.approx(float(parts["main"]), rel=2e-6)
    assert float(got["mtp"]) == pytest.approx(float(parts["mtp"]), rel=2e-6)
    assert float(loss) == pytest.approx(
        float(got["main"] + 0.1 * got["mtp"]), rel=1e-6
    )
    assert int(got["micro_batches"]) == 1
    # The module's router is the last layer of ``moe.load``.
    assert counters["moe.load"]["held_rows"].shape == (2, 4)
    (attrs,) = _events("mtp.schedule", since)
    assert (
        attrs["depth"], attrs["rows"], attrs["head_rows"],
        attrs["head_calls"], attrs["loss_weight"],
        attrs["shares_embedding"], attrs["shares_head"],
    ) == (1, 64, 128, 1, 0.1, True, True)
    # The module's mean, written out from its logits: positions 0 ..
    # s - 2 against the targets one place on, the last position out.
    _, predicted = jax.jit(
        lambda p, b: model.apply(p, b["inputs"], next_tokens=b["targets"])
    )({"params": params}, batch)
    by_hand = optax.softmax_cross_entropy_with_integer_labels(
        predicted[:, :-1], batch["targets"][:, 1:]
    ).mean()
    assert float(got["mtp"]) == pytest.approx(float(by_hand), rel=2e-6)
    # (What ``no_mtp_loss`` leaves out is a tenth of the module's term.)
    assert float(loss) - float(parts["main"]) == pytest.approx(
        0.1 * float(parts["mtp"]), rel=1e-4
    )


# ---- the share -------------------------------------------------------------


def _whole_layer(keys, d=32, f=16, experts=16):
    return {
        "router": 0.5 * jax.random.normal(keys[0], (d, experts)),
        "w1": jax.random.normal(keys[1], (experts, d, f)) / d**0.5,
        "w3": jax.random.normal(keys[2], (experts, d, f)) / d**0.5,
        "w2": jax.random.normal(keys[3], (experts, f, d)) / f**0.5,
        "s1": jax.random.normal(keys[4], (d, f)) / d**0.5,
        "s3": jax.random.normal(keys[5], (d, f)) / d**0.5,
        "s2": jax.random.normal(keys[6], (f, d)) / f**0.5,
    }


def _share_of(whole, first, held=8):
    span = slice(first, first + held)
    return {
        "router": whole["router"],
        "expert_bias": jnp.zeros((whole["router"].shape[1],)),
        "w_gate": whole["w1"][span], "w_up": whole["w3"][span],
        "w_down": whole["w2"][span],
        "shared": {
            "ff_gate": {"kernel": whole["s1"]},
            "ff_up": {"kernel": whole["s3"]},
            "ff_down": {"kernel": whole["s2"]},
        },
    }


@pytest.mark.parametrize("where", ["trunk", "module"])
def test_the_shares_add_up_to_the_whole_layer(where):
    """A 16-expert layer cut into 2 shares of 8: what the two chips
    compute of the routed result (scaled by 1.8), with the shared
    expert (which every chip computes alike) counted ONCE, adds up to
    the uncut reference's layer — for a trunk layer (``RoutedFFN``
    through a model's ``layer_1``) and for the prediction module's
    (through ``PredictionModule``'s own block)."""
    config, sizes, _, _, params, _ = _tiny_model()
    keys = jax.random.split(jax.random.key(11), 8)
    whole = _whole_layer(keys)
    x = jax.random.normal(keys[7], (1, 64, 32))
    total = ffn_in = None
    counts = jnp.zeros((16,), jnp.int32)
    sizes = {**sizes, "experts_held": 8, "n_routed_experts": 8}
    for first in (0, 8):
        cfg = config.model_config({**sizes, "first_expert": first})
        if where == "trunk":
            from adaptdl_tpu.models.transformer import Block

            module, path = Block(cfg, False, 1), ()
            block = dict(params["layer_1"], moe=_share_of(whole, first))
            args = (x, jnp.arange(64))
        else:
            module = PredictionModule(cfg, block_remat(cfg, (1, 64)))
            path = ("layer_2",)
            block = dict(
                params["mtp"],
                layer_2=dict(
                    params["mtp"]["layer_2"],
                    moe=_share_of(whole, first),
                ),
            )
            args = (x, x[:, ::-1], jnp.arange(64))
        _, seen = jax.jit(functools.partial(
            module.apply, mutable=["moe_load", "intermediates"],
            capture_intermediates=lambda m, _: m.path[len(path):]
            in (("moe",), ("RMSNorm_1",)),
        ))({"params": block}, *args)
        found = config._leaf(seen["intermediates"], path)
        y, u = found["moe"]["__call__"][0], found["RMSNorm_1"]["__call__"][0]
        held = config._leaf(seen["moe_load"], path)["moe"]["held_rows"][0]
        counts = counts.at[first:first + 8].set(held)
        total = y if total is None else total + y
        # The mixer does not depend on the share: one input for all.
        if ffn_in is not None:
            np.testing.assert_array_equal(u, ffn_in)
        ffn_in = u
    with jax.default_matmul_precision("highest"):
        want, ref_counts = config.reference_routed_ffn(
            {**whole, "bias": jnp.zeros((16,))}, ffn_in,
            {**sizes, "first_expert": 0},
        )
        shared = config._gated(ffn_in, whole["s1"], whole["s3"], whole["s2"])
    np.testing.assert_array_equal(counts, ref_counts)
    assert int(counts.sum()) == 64 * 3
    np.testing.assert_allclose(total - shared, want, rtol=2e-5, atol=2e-5)
    unscaled, _ = config.reference_routed_ffn(
        {**whole, "bias": jnp.zeros((16,))}, ffn_in,
        {**sizes, "first_expert": 0}, variant="no_scale",
    )
    assert rel(unscaled, want) > 0.1


# ---- the count of operations ---------------------------------------------------


def test_the_module_and_the_second_head_pass_are_counted():
    """``transformer_train_flops`` of the configuration at its PUBLISHED
    sizes is the benchmark's own count (``forward_flops_per_token``, the
    numbers of ISSUE 56: 1472 MFLOP a token forward, attention 839, the
    module 336), three times forward; without the module 336 fewer."""
    config = configurations.module(NAME)
    sizes = configurations.published(NAME)
    parts = config.forward_flops_per_token(sizes)
    forward = sum(parts.values())
    assert forward / 1e6 == pytest.approx(1471.9, abs=0.1)
    assert parts["mla_attention"] / 1e6 == pytest.approx(838.9, abs=0.1)
    cfg = config.model_config(sizes)
    seq = sizes["sequence_length"]
    counted = transformer_train_flops(cfg, 2, seq)
    assert counted.total == pytest.approx(3.0 * 2 * seq * forward, rel=1e-9)
    assert counted.attention == pytest.approx(
        3.0 * 2 * seq * parts["mla_attention"], rel=1e-9
    )
    trunk = transformer_train_flops(
        dataclasses.replace(cfg, mtp_depth=0), 2, seq
    )
    module = (counted.total - trunk.total) / (3.0 * 2 * seq)
    assert module / 1e6 == pytest.approx(336.0, abs=0.1)
