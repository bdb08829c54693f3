"""The block options that the lfm2-8b-a1b configuration forced (PR 30),
at small sizes in float32 against the configuration's own plain
reference (``benchmark/configs/lfm2-8b-a1b.py``, which imports nothing
from ``adaptdl_tpu``): the whole model, the mixers, the model through
the trainer (the donation rule among it), and that the cell's
comparison sees a planted fault. (The dropless routed expert layer
itself and the grouped products: ``tests/test_routed_layer.py``.)"""

import functools

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from configurations import loader_stub
from test_routed_layer import _layer

from adaptdl_tpu import trace
from adaptdl_tpu.models.transformer import (
    GroupedQueryAttention,
    ShortConv,
    TransformerConfig,
    TransformerLM,
    causal_attention,
)
from adaptdl_tpu.ops.flash_attention import (
    flash_attention,
    make_flash_attention,
)

NAME = "lfm2-8b-a1b"


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
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
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
        want = configurations.module(NAME).reference_short_conv(ref_layer, x)
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
        want = configurations.module(NAME).reference_attention(
            ref_layer, x, sizes
        )
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


def test_run_step_save_restore_round_trip(tmp_path, monkeypatch):
    """One ``ElasticTrainer.run_step`` of the tiny model (remat on),
    its load counters journalled as ``moe.load``, a save, and a
    restore into a fresh trainer that continues bit for bit."""
    from adaptdl_tpu import checkpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    data = config.make_dataset(sizes, 5, 8)
    batch = {k: v[:4] for k, v in data.items()}
    built = configurations.built(monkeypatch, NAME, sizes)
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
        holder["state"], batch, loader_stub(2, 1)
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
        holder["state"], batch, loader_stub(2, 1)
    )
    ck.unregister()

    again = configurations.built(monkeypatch, NAME, sizes, seed=11)["trainer"]
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
        holder2["state"], batch, loader_stub(2, 1)
    )
    assert float(resumed["loss"]) == pytest.approx(
        float(after["loss"]), rel=1e-6
    )
    ck2.unregister()


def test_calibration_program_takes_a_counting_loss(monkeypatch):
    sizes = configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    trainer = built["trainer"]
    state = trainer.init_state()
    data = configurations.module(NAME).make_dataset(sizes, 5, 8)
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
    sizes = configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    trainer = built["trainer"]
    monkeypatch.setattr(trainer, "_device_bytes_limit", lambda: limit)
    state = trainer.init_state()
    # Nothing keeps the initial parameters as arrays.
    assert all(
        isinstance(leaf, jax.ShapeDtypeStruct)
        for leaf in jax.tree.leaves(trainer.storage.template)
    )
    assert trainer._init_params is None
    data = configurations.module(NAME).make_dataset(sizes, 5, 8)
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
    sizes = configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
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
    data = configurations.module(NAME).make_dataset(sizes, 5, 8)
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
    config = configurations.module(NAME)
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
    config = configurations.module(NAME)
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
