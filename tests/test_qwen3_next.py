"""What the qwen3-next-80b-a3b configuration forced (PR 49), at small
sizes against the configuration's own plain reference
(``benchmark/configs/qwen3-next-80b-a3b.py``, which imports nothing
from ``adaptdl_tpu``): the delta rule with ONE decay a head and fewer
key heads than value heads, the ``gdn`` mixer, gated grouped-query
attention with a part of each head rotated, the zero-centred norm, the
gated shared expert, the share of an expert-parallel layer
(``tests/test_qwen3_next_rule.py``: the rule itself, the norm, rotary,
the whole model, the counts)."""

import dataclasses
import functools

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adaptdl_tpu import trace
from adaptdl_tpu.models.transformer import (
    GatedDeltaNet,
    GroupedQueryAttention,
    RoutedFFN,
    TransformerConfig,
    causal_attention,
)
from adaptdl_tpu.ops.flash_attention import flash_attention

NAME = "qwen3-next-80b-a3b"


@pytest.fixture(autouse=True)
def _rows_of_several_chunks():
    """The rule's chunk is a constant of ``ops/kda.py`` (64); the
    models of these tests run rows of 64 tokens, several chunks at
    the tiny sizes' ``kda_chunk``."""
    with configurations.rows_of_several_chunks(NAME):
        yield


def _events(name, since=0):
    return [
        r["attrs"] for r in trace.snapshot_spans()[since:]
        if r["name"] == name
    ]


def _close(got, want, tol=2e-4):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(float(jnp.abs(b).max()), 1e-6)
        assert float(jnp.abs(a - b).max()) / scale < tol


# ---- the two mixers ----------------------------------------------------


def _mixer_case(monkeypatch, kind):
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    at = config.checked_mixers(sizes)[kind]
    layer = config.reference_weights(params, sizes)["layers"][at][kind]
    u = jax.random.normal(jax.random.key(7), (2, 64, 32))
    name = kind
    if kind == "attention":
        # The zero-centred head norms away from their initial zero.
        mixer = dict(params[f"layer_{at}"][name])
        for i, norm in enumerate(("q_norm", "k_norm")):
            mixer[norm] = {"scale": 0.3 * jax.random.normal(
                jax.random.key(20 + i), mixer[norm]["scale"].shape
            )}
        layer = config.attention_weights(mixer)
        return config, sizes, built, mixer, layer, u
    return config, sizes, built, params[f"layer_{at}"][name], layer, u


@pytest.mark.parametrize("kind", ["gdn", "attention"])
def test_mixer_equals_the_reference(monkeypatch, kind):
    """The system's mixer alone (gdn: one convolution over q, k and v,
    2 key heads for 4 value heads, one decay a head, the gated head
    norm, the chunked rule through the kernels; attention: 4 query on
    2 kv heads, zero-centred head norms, a quarter of a head rotated,
    the sigmoid gate, the flash kernels) against the reference's,
    forward and the gradient of every leaf and of the input."""
    config, sizes, built, mixer_params, layer, u = _mixer_case(
        monkeypatch, kind
    )
    cfg = config.model_config(
        sizes, functools.partial(flash_attention, block_q=64, block_k=64)
    )
    module = {"gdn": GatedDeltaNet, "attention": GroupedQueryAttention}[
        kind
    ](cfg)
    got = module.apply({"params": mixer_params}, u, jnp.arange(64))
    want = config.reference_mixer(kind, layer, u, sizes)
    token, rms = config.layer_error(got, want)
    assert float(token) < 1e-5 and float(rms) < 1e-5
    errors = config.mixer_grad_errors(
        kind,
        built["mixer_vjp"](kind, mixer_params, u, u),
        config.reference_mixer_vjp(kind, layer, u, u, sizes),
    )
    # (The decay's leaves A_log, dt_bias and a read 1e-4 in float32:
    # a head's dg is a sum over its channels and a chunk's tokens of
    # terms that cancel; every other leaf reads under 5e-6.)
    assert float(errors[f"{kind}_input_grad_err"]) < 2e-5, errors
    assert float(errors[f"{kind}_param_grad_err"]) < 3e-4, errors


@pytest.mark.parametrize(
    "kind,variant",
    [("gdn", "bf16_state"), ("gdn", "bf16_decay"),
     ("attention", "rotary_all"), ("attention", "no_gate")],
)
def test_a_faulty_reference_differs(monkeypatch, kind, variant):
    """What ``qwen3_next_precision.py`` reads on the chip is not a
    no-op."""
    config, sizes, _, _, layer, u = _mixer_case(monkeypatch, kind)
    want = config.reference_mixer(kind, layer, u, sizes)
    low = config.reference_mixer(kind, layer, u, sizes, variant)
    assert float(config.layer_error(low, want)[1]) > 1e-4


def test_gated_attention_at_head_256_through_the_flash_kernels():
    """The kernels (interpret mode) at the cell's head width: a head
    of 256, 64 lanes rotated, 4 query heads on 1 kv head, the gate."""
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=1,
        d_model=64, d_ff=64, max_seq_len=256, dtype=jnp.float32,
        norm="rmsnorm", norm_zero_centred=True, qk_norm=True, head_dim=256,
        rotary_dims=64, rope_theta=1e7, attention_gate=True,
    )
    flash = dataclasses.replace(
        cfg, attention_fn=functools.partial(
            flash_attention, block_q=128, block_k=128
        ),
    )
    x = jax.random.normal(jax.random.key(0), (1, 256, 64))
    positions = jnp.arange(256)
    params = GroupedQueryAttention(cfg).init(
        jax.random.key(1), x, positions
    )["params"]
    assert params["q"]["kernel"].shape == (64, 4, 512)

    def loss(module, params, x):
        y = module.apply({"params": params}, x, positions)
        return jnp.sum(y * jnp.sin(jnp.arange(y.size)).reshape(y.shape))

    want = jax.value_and_grad(
        functools.partial(loss, GroupedQueryAttention(cfg)), (0, 1)
    )(params, x)
    since = len(trace.snapshot_spans())
    got = jax.value_and_grad(
        functools.partial(loss, GroupedQueryAttention(flash)), (0, 1)
    )(params, x)
    _close(got, want, 2e-4)
    schedule = _events("flash.schedule", since)
    assert schedule and all(s["head_dim"] == 256 for s in schedule)


def test_gated_attn_schedule_is_journalled(monkeypatch):
    config, sizes, _, mixer_params, _, u = _mixer_case(
        monkeypatch, "attention"
    )
    since = len(trace.snapshot_spans())
    GroupedQueryAttention(config.model_config(sizes)).apply(
        {"params": mixer_params}, u, jnp.arange(64)
    )
    (attrs,) = _events("gated_attn.schedule", since)
    assert (
        attrs["heads"], attrs["kv_heads"], attrs["head_dim"],
        attrs["rotary_dims"], attrs["gate"],
    ) == (4, 2, 16, 4, "sigmoid")
    # An ungated attention journals none.
    plain = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=2,
        d_model=32, d_ff=64, dtype=jnp.float32,
    )
    since = len(trace.snapshot_spans())
    module = GroupedQueryAttention(plain)
    module.apply(
        module.init(jax.random.key(0), u, jnp.arange(64)), u, jnp.arange(64)
    )
    assert not _events("gated_attn.schedule", since)


@pytest.mark.parametrize("runs", [1, 2])
def test_gated_attention_in_runs_of_heads_is_the_attention(monkeypatch, runs):
    """Where the flash kernels want fewer heads a call, the gated
    attention gives them runs: the same result."""
    config, sizes, _, mixer_params, _, u = _mixer_case(
        monkeypatch, "attention"
    )
    want = GroupedQueryAttention(config.model_config(sizes)).apply(
        {"params": mixer_params}, u, jnp.arange(64)
    )
    attn = functools.partial(causal_attention, causal=True)

    def asked(q, k, v):
        assert q.shape[1] == 4 // runs
        return attn(q, k, v)

    asked.heads_a_call = lambda heads, *_a, **_k: heads // runs
    got = GroupedQueryAttention(config.model_config(sizes, asked)).apply(
        {"params": mixer_params}, u, jnp.arange(64)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("run", [1, 2, 4, 8])
def test_gated_attention_hands_the_kernels_a_runs_own_kv_heads(run):
    """8 gated heads on 4 kv heads (groups of 2) through the flash
    kernels in runs shorter than a group (1: the cell's case, 2 of 8),
    of one group, of two groups, and all at once: a call gets its
    run's kv heads ONCE each (PR 55), and the numbers are plain
    attention's on repeated heads, forward and every gradient (the
    runs' dK / dV of one kv head are added by autodiff)."""
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=8, num_kv_heads=4,
        d_model=64, d_ff=64, dtype=jnp.float32, attention_gate=True,
    )
    u = jax.random.normal(jax.random.key(21), (2, 64, 64))
    positions = jnp.arange(64)
    plain = GroupedQueryAttention(cfg)
    variables = plain.init(jax.random.key(22), u, positions)
    asked = []

    def attn(q, k, v):
        asked.append((q.shape[1], k.shape[1], v.shape[1]))
        return flash_attention(q, k, v, True, None, 16, 16)

    attn.heads_a_call = lambda heads, *_a, **_k: run
    attn.takes_kv_heads = True
    flash = GroupedQueryAttention(dataclasses.replace(cfg, attention_fn=attn))

    def both(module):
        return jax.value_and_grad(
            lambda variables, u: jnp.sum(
                jnp.sin(module.apply(variables, u, positions))
            ),
            (0, 1),
        )(variables, u)

    since = len(trace.snapshot_spans())
    got, want = both(flash), both(plain)
    kv = max(1, run // 2)
    assert asked == [(run, kv, kv)] * (8 // run)
    flash_event, plain_event = _events("gated_attn.schedule", since)
    assert (flash_event["heads_a_call"], flash_event["kv_repeat"]) == (run, 1)
    assert (plain_event["heads_a_call"], plain_event["kv_repeat"]) == (8, 2)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# ---- the routed layer --------------------------------------------------


def test_shared_expert_is_gated(monkeypatch):
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    moe = params["layer_1"]["moe"]
    layer = config.routed_weights(moe)
    x = jax.random.normal(jax.random.key(5), (96, 32))
    since = len(trace.snapshot_spans())
    y, sown = RoutedFFN(config.model_config(sizes)).apply(
        {"params": moe}, x, mutable=["moe_load", "moe_routing"]
    )
    assert _events("moe.schedule", since)[-1]["shared_gate"] == "sigmoid"
    with jax.default_matmul_precision("highest"):
        want, _ = config.reference_routed_ffn(layer, x, sizes)
        routed_only, _ = config.reference_routed_ffn(
            layer, x, sizes, shared=False
        )
        ungated, _ = config.reference_routed_ffn(
            layer, x, sizes, variant="shared_ungated"
        )
        shared = config._gated(x, layer["s1"], layer["s3"], layer["s2"])
        gate = jax.nn.sigmoid(x @ layer["sg"])
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        routed_only + gate * shared, want, rtol=1e-5, atol=1e-6
    )
    assert float(config.layer_error(ungated, want)[1]) > 1e-2
    # shared_rows stays outside held_rows.
    assert int(sown["moe_load"]["shared_rows"][0]) == 96
    assert int(sown["moe_load"]["held_rows"][0].sum()) + int(
        sown["moe_load"]["left_out"][0]
    ) == 96 * 3
    # An ungated shared expert says so, and has no gate leaf.
    since = len(trace.snapshot_spans())
    cfg = dataclasses.replace(
        config.model_config(sizes), shared_expert_gate=False
    )
    RoutedFFN(cfg).apply(
        {"params": {k: v for k, v in moe.items() if k != "shared_gate"}}, x,
        mutable=["moe_load", "moe_routing"],
    )
    assert _events("moe.schedule", since)[-1]["shared_gate"] == "none"


def test_bf16_router_scores_choose_other_sets():
    config, sizes = configurations.module(NAME), configurations.sizes(NAME, 
        router_width=64, num_experts_per_tok=6
    )
    keys = jax.random.split(jax.random.key(0), 2)
    layer = {"router": jax.random.normal(keys[0], (32, 64))}
    x = jax.random.normal(keys[1], (4096, 32))
    want = config.reference_router(layer, x, sizes)
    low = config.reference_router(layer, x, sizes, "bf16_scores")
    assert float(config.router_disagreement(low, want)[0]) > 0.01
    np.testing.assert_allclose(want[1].sum(-1), 1.0, rtol=1e-5)


def test_the_shares_add_up_to_the_whole_layer():
    """A 32-expert layer cut into 16 shares of 2: what the sixteen
    chips compute of the routed result, with the gated shared expert
    (which every chip computes alike) counted ONCE, adds up to the
    uncut reference's layer."""
    config = configurations.module(NAME)
    sizes = configurations.sizes(
        NAME, router_width=32, experts_held=2, num_experts=2,
        num_experts_per_tok=5,
    )
    keys = jax.random.split(jax.random.key(11), 9)
    d, f = 32, 16
    whole = {
        "router": 0.5 * jax.random.normal(keys[0], (d, 32)),
        "w1": jax.random.normal(keys[2], (32, d, f)) / d**0.5,
        "w3": jax.random.normal(keys[3], (32, d, f)) / d**0.5,
        "w2": jax.random.normal(keys[4], (32, f, d)) / f**0.5,
        "s1": jax.random.normal(keys[5], (d, f)) / d**0.5,
        "s3": jax.random.normal(keys[6], (d, f)) / d**0.5,
        "s2": jax.random.normal(keys[7], (f, d)) / f**0.5,
        "sg": jax.random.normal(keys[1], (d, 1)) / d**0.5,
    }
    x = jax.random.normal(keys[8], (64, d))
    with jax.default_matmul_precision("highest"):
        want, counts = config.reference_routed_ffn(
            whole, x, {**sizes, "first_expert": 0}
        )
    assert int(counts.sum()) == 64 * 5
    total = jnp.zeros_like(x)
    for share in range(16):
        first = 2 * share
        cfg = config.model_config({**sizes, "first_expert": first})
        held = slice(first, first + 2)
        y, sown = RoutedFFN(cfg).apply(
            {"params": {
                "router": whole["router"],
                "w_gate": whole["w1"][held], "w_up": whole["w3"][held],
                "w_down": whole["w2"][held],
                "shared": {
                    "ff_gate": {"kernel": whole["s1"]},
                    "ff_up": {"kernel": whole["s3"]},
                    "ff_down": {"kernel": whole["s2"]},
                },
                "shared_gate": {"kernel": whole["sg"]},
            }},
            x, mutable=["moe_load", "moe_routing"],
        )
        np.testing.assert_array_equal(
            sown["moe_load"]["held_rows"][0], counts[held]
        )
        total = total + y
    with jax.default_matmul_precision("highest"):
        shared = config._gated(
            x, whole["s1"], whole["s3"], whole["s2"]
        ) * jax.nn.sigmoid(x @ whole["sg"])
    np.testing.assert_allclose(
        total - 15 * shared, want, rtol=2e-5, atol=2e-5
    )
