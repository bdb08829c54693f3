"""What the laguna-xs.2 configuration forced in the model (PR 54), at
small sizes against the configuration's own plain reference
(``benchmark/configs/laguna-xs.2.py``, which imports nothing from
``adaptdl_tpu``): a mixer kind with a window, query heads and rotary
(base, rotated lanes, YaRN's table) by layer kind, a per-head output
gate, the share of an expert-parallel layer beside a leading dense
one, and that a configuration without the new fields is the program of
before."""

import functools
import math

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from configurations import rel

from adaptdl_tpu import trace
from adaptdl_tpu.flops import transformer_train_flops
from adaptdl_tpu.models.transformer import (
    AttentionKind,
    GroupedQueryAttention,
    RoutedFFN,
    TransformerConfig,
    TransformerLM,
    Yarn,
    causal_attention,
    rope,
    yarn_frequencies,
)
from adaptdl_tpu.ops.flash_attention import flash_attention

NAME = "laguna-xs.2"
FLASH = functools.partial(flash_attention, block_q=16, block_k=16)


# ---- YaRN ---------------------------------------------------------------


def test_yarn_table_is_the_formulas():
    """The program's table (float64 on the host) against the
    reference's (float32 ``jax.numpy``, from the formulas) at the
    published parameters, and the formulas' landmarks by hand."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    said = sizes["rope_parameters"]["full_attention"]
    yarn = Yarn(64.0, 4096, 64.0, 1.0, said["attention_factor"])
    table = yarn_frequencies(500000.0, 64, yarn)
    want, scale = config.yarn_table(said, 64)
    np.testing.assert_allclose(table, want, rtol=2e-6)
    assert scale == said["attention_factor"] == pytest.approx(
        0.1 * math.log(64) + 1
    )
    assert Yarn(64.0, 4096).scale == pytest.approx(said["attention_factor"])
    # lo = floor(64 ln(4096 / (64 x 2 pi)) / (2 ln 500000)) = 5,
    # hi = ceil(64 ln(4096 / (2 pi)) / (2 ln 500000)) = 16.
    base = 500000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(table[:6], base[:6], rtol=1e-6)
    np.testing.assert_allclose(table[16:], base[16:] / 64, rtol=1e-6)
    assert base[10] / 64 < table[10] < base[10]
    # The plain kind: the formula's frequencies, no scale.
    plain, one = config.yarn_table(
        sizes["rope_parameters"]["sliding_attention"], 128
    )
    np.testing.assert_allclose(
        plain, 10000.0 ** (-2.0 * np.arange(64) / 128), rtol=1e-6
    )
    assert one == 1.0


def test_rope_takes_a_table_and_its_scale():
    """A table of ``theta``'s own frequencies turns as ``theta`` does;
    a scale multiplies cosine and sine of the rotated lanes only; the
    program's turn is the reference's."""
    config = configurations.module(NAME)
    x = jax.random.normal(jax.random.key(0), (2, 24, 3, 16))
    positions = jnp.arange(24)
    freqs = (1e4 ** (-2.0 * np.arange(4) / 8)).astype(np.float32)
    np.testing.assert_allclose(
        rope(x, positions, 1e4, 8), rope(x, positions, freqs=freqs),
        rtol=1e-6, atol=1e-6,
    )
    scaled = rope(x, positions, freqs=freqs, scale=1.5)
    np.testing.assert_array_equal(scaled[..., 8:], x[..., 8:])
    np.testing.assert_allclose(
        scaled[..., :8], 1.5 * rope(x, positions, 1e4, 8)[..., :8],
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        scaled, config._rotary(x, jnp.asarray(freqs), 1.5),
        rtol=1e-5, atol=1e-6,
    )


# ---- the config's new fields -------------------------------------------


def test_kinds_give_each_layer_its_heads_rotary_and_window():
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    cfg = config.model_config(sizes)
    assert [cfg.layer_heads(i) for i in range(5)] == [6, 8, 8, 8, 6]
    full = cfg.attention_kind("full_attention")
    sliding = cfg.attention_kind("sliding_attention")
    assert (full.num_heads, full.rope_theta, full.rotary_dims,
            full.window) == (6, 500000.0, 8, None)
    assert full.yarn.factor == 64 and full.yarn.beta_fast == 64
    assert (sliding.num_heads, sliding.rope_theta, sliding.rotary_dims,
            sliding.window, sliding.yarn) == (8, 10000.0, None, 24, None)
    params = jax.eval_shape(
        lambda: TransformerLM(cfg).init(
            jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False
        )["params"]
    )
    shapes = jax.tree.map(lambda x: x.shape, params)
    for layer, heads in enumerate([6, 8, 8, 8, 6]):
        mixer = shapes[f"layer_{layer}"]["attention"]
        assert mixer == {
            "q": {"kernel": (32, heads, 16)},
            "kv": {"kernel": (32, 2, 2, 16)},
            "gate": {"kernel": (32, heads)},
            "out": {"kernel": (heads * 16, 32)},
        }
    assert "ffn" in shapes["layer_0"] and "moe" not in shapes["layer_0"]
    assert all("moe" in shapes[f"layer_{i}"] for i in (1, 2, 3, 4))
    # The program's count prices a sliding layer over its band.
    flops = transformer_train_flops(cfg, 1, 64)
    uncut = transformer_train_flops(
        config.model_config({**sizes, "sliding_window": 64}), 1, 64
    )
    band = sum(min(i + 1, 24) for i in range(64)) / 64
    # (A window is counted with its diagonal: 32.5 keys a query at 64.)
    assert uncut.attention - flops.attention == pytest.approx(
        3 * 64 * 3 * (2 * 8 * 2 * 16) * (32.5 - band)
    )


@pytest.mark.parametrize(
    "changes, match",
    [
        (dict(layer_types=("sliding_attention",)), "its window"),
        (dict(layer_types=("sliding_attention",), seq_axis="seq",
              attention_kinds=(("sliding_attention",
                                AttentionKind(window=8)),)),
         "sequence-parallel"),
        (dict(attention_kinds=(("kda", AttentionKind()),)),
         "no softmax-attention kind"),
        (dict(num_kv_heads=4, attention_kinds=(
            ("full_attention", AttentionKind(num_heads=6)),)), "kv heads"),
        (dict(attention_kinds=(("full_attention",
                                AttentionKind(window=0)),)), "window 0"),
        (dict(attention_gate=True, attention_head_gate=True), "one output"),
        (dict(attention_kinds=(("full_attention", AttentionKind()),) * 2),
         "twice"),
    ],
)
def test_config_refuses_at_build_with_the_reason(changes, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(num_layers=1, num_heads=8, d_model=64, **changes)


def test_per_head_gate_is_one_sigmoid_a_head_and_token():
    """The gate's matrix is ``d_model x heads``; at zero it halves the
    ungated mixer's output; a column moved moves one head alone."""
    cfg = TransformerConfig(
        num_layers=1, num_heads=4, num_kv_heads=2, d_model=32, head_dim=8,
        dtype=jnp.float32, attention_head_gate=True,
    )
    plain = TransformerConfig(
        num_layers=1, num_heads=4, num_kv_heads=2, d_model=32, head_dim=8,
        dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.key(1), (2, 24, 32))
    positions = jnp.arange(24)
    params = GroupedQueryAttention(cfg).init(
        jax.random.key(0), x, positions
    )["params"]
    assert params["gate"]["kernel"].shape == (32, 4)
    ungated = {k: v for k, v in params.items() if k != "gate"}
    want = GroupedQueryAttention(plain).apply(
        {"params": ungated}, x, positions
    )
    zero = dict(params, gate={"kernel": jnp.zeros((32, 4))})
    got = GroupedQueryAttention(cfg).apply({"params": zero}, x, positions)
    np.testing.assert_allclose(got, 0.5 * want, rtol=1e-5, atol=1e-6)
    # Head 2's gate shut: the mixer without that head's rows of ``out``.
    shut = jnp.zeros((32, 4)).at[:, 2].set(-1e4 * jnp.sign(x[0, 0]))
    one = dict(params, gate={"kernel": shut})
    got = GroupedQueryAttention(cfg).apply({"params": one}, x[:1, :1],
                                           positions[:1])
    out = ungated["out"]["kernel"].at[16:24].set(0.0)
    want = GroupedQueryAttention(plain).apply(
        {"params": dict(ungated, out={"kernel": out})}, x[:1, :1],
        positions[:1],
    )
    np.testing.assert_allclose(got, 0.5 * want, rtol=1e-5, atol=1e-6)


def test_plain_attention_takes_the_window():
    q, k, v = (
        jax.random.normal(key, (1, 2, 40, 8))
        for key in jax.random.split(jax.random.key(2), 3)
    )
    got = causal_attention(q, k, v, window=7)
    want = flash_attention(q, k, v, True, None, 8, 8, 7)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        causal_attention(q, k, v, window=40), causal_attention(q, k, v),
        rtol=1e-6,
    )


# ---- the mixers against the reference ------------------------------------


@pytest.mark.parametrize("name", ["sliding", "full"])
def test_mixer_equals_the_reference(monkeypatch, name):
    """The system's mixer alone (per-kind heads, rotary and YaRN, the
    band or the full kernels, the per-head gate) against the
    reference's, forward and the gradient of every leaf and of the
    input; and the schedule's event says what was traced."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
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
    assert (event["kind"], event["gate"], event["kv_heads"]) == (
        config.MIXER_KINDS[name], "head", 2
    )
    assert (event["heads"], event["window"], event["rotary_dims"],
            event["yarn_factor"]) == (
        (8, 24, 16, 0) if name == "sliding" else (6, 0, 8, 64.0)
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
    for variant in ("band_511", "band_ahead") if name == "sliding" else (
        "no_attention_factor",
    ):
        wrong = jax.jit(functools.partial(
            config.reference_mixer, name, sizes=sizes, variant=variant
        ))(layer, u)
        assert rel(wrong, want) > 1e-3, variant


def _sliding_mixer(monkeypatch):
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    u = jax.random.normal(jax.random.key(8), (1, 64, 32))

    def mixer(attn):
        return GroupedQueryAttention(
            config.model_config(sizes, attn), "sliding_attention"
        )

    return mixer, {"params": params["layer_2"]["attention"]}, u


@pytest.mark.parametrize("run", [2, 4])  # half a group of 4, and a group
@pytest.mark.parametrize("kernel", [False, True])
def test_heads_go_in_runs_where_the_function_asks_for_fewer_a_call(
    monkeypatch, kernel, run
):
    """A sliding layer's 8 heads on 2 kv heads in runs (what a
    function's ``heads_a_call`` says: the K-blocked backward's
    partials; until PR 55 also what one call would have had repeated
    for it): the same numbers, forward and gradients. A function that
    does not say ``takes_kv_heads`` is handed equal head counts, each
    run's kv head repeated for that run alone; one that says it (the
    kernels' own mark) the run's kv head ONCE."""
    mixer, variables, u = _sliding_mixer(monkeypatch)

    def loss(module):
        def of(variables, u):
            return jnp.sum(jnp.sin(module.apply(variables, u, jnp.arange(64))))

        return jax.value_and_grad(of, (0, 1))

    want = loss(mixer(FLASH))(variables, u)
    asked = []

    def attn(q, k, v, window=None):
        asked.append((q.shape[1], k.shape[1], v.shape[1], window))
        return FLASH(q, k, v, window=window)

    attn.heads_a_call = lambda heads, *a, **kw: run
    if kernel:
        attn.takes_kv_heads = True
    before = len(trace.snapshot_spans())
    got = loss(mixer(attn))(variables, u)
    kv = 1 if kernel else run
    assert asked == [(run, kv, kv, 24)] * (8 // run)
    (event,) = [
        r["attrs"] for r in trace.snapshot_spans()[before:]
        if r["name"] == "attn_kind.schedule"
    ]
    assert (event["heads_a_call"], event["kv_repeat"]) == (run, kv)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_kernels_are_handed_each_kv_head_once(monkeypatch, kind):
    """On the flash path no broadcast of k or v to the query heads is
    left in a mixer's lowered gradient, nor the sum of dK / dV over a
    group that undoes it (``jnp.repeat`` lowers to a broadcast into
    ``[b, s, kv_heads, group, d]`` and its transpose reduces that
    shape); a function that is not the kernel still gets both, and
    plain attention too."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    at = {"sliding_attention": 2, "full_attention": 0}[kind]
    variables = {"params": params[f"layer_{at}"]["attention"]}
    u = jax.random.normal(jax.random.key(9), (1, 64, 32))
    heads = 8 if kind == "sliding_attention" else 6
    repeated = (
        "dims = [0, 1, 2, 4] : (tensor<1x64x2x16xf32>) -> "
        f"tensor<1x64x2x{heads // 2}x16xf32>"
    )

    def lowered(attn):
        module = GroupedQueryAttention(config.model_config(sizes, attn), kind)
        before = len(trace.snapshot_spans())
        text = jax.jit(jax.grad(
            lambda variables, u: jnp.sum(
                module.apply(variables, u, jnp.arange(64))
            ),
            (0, 1),
        )).lower(variables, u).as_text()
        (event,) = [
            r["attrs"] for r in trace.snapshot_spans()[before:]
            if r["name"] == "attn_kind.schedule"
        ]
        return text, event

    text, event = lowered(FLASH)
    assert repeated not in text and event["kv_repeat"] == 1
    assert event["heads_a_call"] == heads

    def wrapped(q, k, v, window=None):  # says nothing of itself
        assert q.shape[1] == k.shape[1] == v.shape[1] == heads
        return FLASH(q, k, v, window=window)

    for other in (wrapped, None):
        text, event = lowered(other)
        assert repeated in text and event["kv_repeat"] == heads // 2


# ---- the share ------------------------------------------------------------


def test_the_shares_add_up_to_the_whole_layer():
    """A 16-expert layer cut into 4 shares of 4: what the four chips
    compute of the routed result (scaled by 2.5), with the shared
    expert (which every chip computes alike) counted ONCE, adds up to
    the uncut reference's layer."""
    config = configurations.module(NAME)
    sizes = configurations.sizes(NAME)
    keys = jax.random.split(jax.random.key(11), 8)
    d, f = 32, 16
    whole = {
        "router": 0.5 * jax.random.normal(keys[0], (d, 16)),
        "w1": jax.random.normal(keys[1], (16, d, f)) / d**0.5,
        "w3": jax.random.normal(keys[2], (16, d, f)) / d**0.5,
        "w2": jax.random.normal(keys[3], (16, f, d)) / f**0.5,
        "s1": jax.random.normal(keys[4], (d, f)) / d**0.5,
        "s3": jax.random.normal(keys[5], (d, f)) / d**0.5,
        "s2": jax.random.normal(keys[6], (f, d)) / f**0.5,
    }
    x = jax.random.normal(keys[7], (64, d))
    with jax.default_matmul_precision("highest"):
        want, counts = config.reference_routed_ffn(
            whole, x, {**sizes, "first_expert": 0}
        )
    assert int(counts.sum()) == 64 * 3
    total = jnp.zeros_like(x)
    for share in range(4):
        first = 4 * share
        cfg = config.model_config({**sizes, "first_expert": first})
        held = slice(first, first + 4)
        y, sown = jax.jit(functools.partial(
            RoutedFFN(cfg).apply, mutable=["moe_load", "moe_routing"]
        ))(
            {"params": {
                "router": whole["router"],
                "expert_bias": jnp.zeros((16,)),
                "w_gate": whole["w1"][held], "w_up": whole["w3"][held],
                "w_down": whole["w2"][held],
                "shared": {
                    "ff_gate": {"kernel": whole["s1"]},
                    "ff_up": {"kernel": whole["s3"]},
                    "ff_down": {"kernel": whole["s2"]},
                },
            }},
            x,
        )
        np.testing.assert_array_equal(
            sown["moe_load"]["held_rows"][0], counts[held]
        )
        total = total + y
    with jax.default_matmul_precision("highest"):
        shared = config._gated(x, whole["s1"], whole["s3"], whole["s2"])
    np.testing.assert_allclose(
        total - 3 * shared, want, rtol=2e-5, atol=2e-5
    )
    # The scale is in the sum: without it the reference differs.
    unscaled, _ = config.reference_routed_ffn(
        whole, x, {**sizes, "first_expert": 0}, variant="no_scale"
    )
    assert rel(unscaled, want) > 0.1
