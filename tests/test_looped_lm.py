"""The looped stack that the ouro-2.6b configuration forced (PR 42):
blocks applied several times a forward pass with ONE set of weights,
sandwich normalisation, an exit head and a learned gate after every
pass, the loss over all exits. At small sizes in float32 against the
configuration's own plain reference (``benchmark/configs/ouro-2.6b.py``,
which imports nothing from ``adaptdl_tpu``)."""

import configurations
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from configurations import loader_stub

from adaptdl_tpu import device_budget, trace
from adaptdl_tpu.models import transformer
from adaptdl_tpu.models.transformer import (
    Block,
    TransformerConfig,
    TransformerLM,
    exit_log_probs,
    looped_lm_loss_fn,
)

NAME = "ouro-2.6b"
# One row a micro-batch: the looped model's tests build at this geometry.
GEOMETRY = {"global_batch": 2, "atomic_bsz": 1, "accum_steps": 1}


def _seeded(params, seed=7, scale=0.3):
    """The same tree with every norm scale, the gate and its bias moved
    off their initial 1 / 0, so that a test can tell them apart."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(
        tree,
        [
            x + scale * jax.random.normal(k, x.shape, x.dtype)
            if x.ndim <= 1 or x.shape[-1] == 1 else x
            for x, k in zip(leaves, keys)
        ],
    )


def _system(built, sizes, seed=5, rows=2):
    """(params, batch) of the tiny model on rows of its own data."""
    config = configurations.module(NAME)
    trainer = built["trainer"]
    params = _seeded(trainer.params_tree(trainer.init_state()))
    data = config.make_dataset(sizes, seed, 4)
    return params, {k: jnp.asarray(v[:rows]) for k, v in data.items()}


# ---- the whole model against the plain reference --------------------


def test_exits_loss_and_gradients_equal_the_reference(monkeypatch):
    """Two blocks four times, remat on, the flash kernel (interpreted),
    the streamed head: every exit's state and per-token cross-entropy,
    the gate, the exit distribution, the loss and the gradient of
    every leaf."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes, geometry=GEOMETRY)
    params, batch = _system(built, sizes)
    key = jax.random.key(0)
    weights = config.reference_weights(params, sizes)
    want_loss, want = config.reference_loss(
        weights, batch["inputs"], batch["targets"], sizes, details=True
    )
    z, gate, p, xent = jax.jit(built["exits_io"])(params, batch, key)
    assert z.shape == (4, 2, 32, 32) and gate.shape == (4, 2, 32)
    np.testing.assert_allclose(z, want["z"], atol=2e-5)
    np.testing.assert_allclose(xent, want["xent"], atol=2e-5)
    np.testing.assert_allclose(jax.nn.sigmoid(gate), want["lambda"], atol=1e-5)
    np.testing.assert_allclose(p, want["p"], atol=1e-5)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    loss, counters = jax.jit(built["loss_fn"])(params, batch, key)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    exits = counters["loop.exit"]
    np.testing.assert_allclose(
        exits["xent"], want["xent"].reshape(4, -1).mean(1), rtol=1e-5
    )
    np.testing.assert_allclose(
        exits["p"], want["p"].reshape(4, -1).mean(1), rtol=1e-5
    )
    assert 1.0 < float(exits["expected_exit"]) < 4.0
    assert float(exits["expected_exit"]) == pytest.approx(
        float((jnp.arange(1, 5)[:, None, None] * want["p"]).sum(0).mean()),
        rel=1e-5,
    )
    got = jax.jit(jax.grad(lambda q: built["loss_fn"](q, batch, key)[0]))(
        params
    )
    errors = jax.jit(config.grad_errors)(
        config.reference_weights(got, sizes),
        *config.reference_gradient(
            weights, batch["inputs"], batch["targets"], sizes
        ),
    )
    assert set(errors) == {f"{k}_grad_err" for k in config.GRAD_LIMITS}
    for name, err in errors.items():
        assert float(err) < 2e-5, (name, float(err))


def test_reference_check_passes_and_holds_every_comparison(monkeypatch):
    """The on-chip check itself at the small size: ok, and every limit
    it names is one it compared."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    monkeypatch.setattr(config, "GRADIENT_TOKENS", 16)
    built = configurations.built(monkeypatch, NAME, sizes, geometry=GEOMETRY)
    params, _ = _system(built, sizes)
    result = config.reference_check(
        built, params, config.make_dataset(sizes, 5, 4), sizes
    )
    assert result["ok"] is True, result
    assert result["gradient_tokens"] == 16
    assert result["rel_diff"] < 1e-6
    assert max(result[f"{k}_grad_err"] for k in config.GRAD_LIMITS) < 2e-5
    for t in (1, 4):
        assert result[f"block_pass{t}_rms_err"] < 1e-5
    assert len(result["exit_p_mean"]) == 4
    broken = dict(result, gate_grad_err=1.0)
    assert config.limits_ok(result) and not config.limits_ok(broken)


def test_gate_gradient_is_held_to_its_terms_not_to_their_sum(monkeypatch):
    """The gate's gradient is the sum over tokens and passes of
    ``g [z; 1]``; the check's ``gate_grad_err`` is a share of the
    terms' root-sum-square, which a seed on which they cancel does not
    shrink, and takes weight and bias as one leaf."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes, geometry=GEOMETRY)
    params, batch = _system(built, sizes, rows=1)
    weights = config.reference_weights(params, sizes)
    args = (weights, batch["inputs"], batch["targets"], sizes)
    _, details = config.reference_loss(*args, details=True)
    grads, terms = config.reference_gradient(*args)
    g = config.gate_logit_grads(weights, details, sizes)
    assert g.shape == details["lambda"].shape and not np.any(g[-1])
    np.testing.assert_allclose(
        jnp.einsum("tbs,tbsd->d", g, details["z"]), grads["gate_w"][:, 0],
        rtol=1e-4, atol=1e-8,
    )
    np.testing.assert_allclose(g.sum(), grads["gate_b"][0], rtol=1e-4)
    each = jnp.sqrt(g ** 2 * ((details["z"] ** 2).sum(-1) + 1.0))
    assert float(terms) == pytest.approx(
        float(jnp.sqrt((each ** 2).sum())), rel=1e-5
    )
    # A bias whose gradient all but cancels: off by its own size, it
    # moves the reading by that over the terms, not by 1.
    off = dict(grads, gate_b=grads["gate_b"] * 2)
    reading = jax.jit(config.grad_errors)(off, grads, terms)
    assert float(reading["gate_grad_err"]) == pytest.approx(
        abs(float(grads["gate_b"][0])) / float(terms), rel=1e-4
    )
    assert all(
        float(v) == 0 for k, v in reading.items() if k != "gate_grad_err"
    )
    # The whole gradient missing reads the share the sum keeps.
    none = dict(grads, gate_w=grads["gate_w"] * 0, gate_b=grads["gate_b"] * 0)
    kept = float(jax.jit(config.grad_errors)(none, grads, terms)["gate_grad_err"])
    assert kept == pytest.approx(
        float(jnp.sqrt((grads["gate_w"] ** 2).sum() + grads["gate_b"][0] ** 2))
        / float(terms), rel=1e-4,
    )


# Which comparison of the check refuses each control (Tentpole 5): the
# variant of the reference against the right reference, read as the
# check reads the system.
CONTROLS = {
    "bf16_logits": "head_token_loss_err",
    "no_post_norm": "state_rms_err",
    "next_from_h": "state_rms_err",
    "last_exit_only": "rel_diff",
    "stop_gradient": "block_grad_err",
}


@pytest.mark.parametrize("variant", sorted(CONTROLS))
def test_a_control_fails_its_comparison(variant, monkeypatch):
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    assert set(CONTROLS) == set(config.VARIANTS)
    built = configurations.built(monkeypatch, NAME, sizes, geometry=GEOMETRY)
    params, batch = _system(built, sizes, rows=1)
    weights = config.reference_weights(params, sizes)
    args = (weights, batch["inputs"], batch["targets"], sizes)
    loss, right = config.reference_loss(*args, details=True)
    wrong_loss, wrong = config.reference_loss(
        *args, variant=variant, details=True
    )
    token, rms = max(
        config.layer_error(wrong["z"][t], right["z"][t]) for t in range(4)
    )
    grads = jax.jit(config.grad_errors)(
        config.reference_gradient(*args, variant=variant)[0],
        *config.reference_gradient(*args),
    )
    reading = {
        "head_token_loss_err": float(
            jnp.max(jnp.abs(wrong["xent"] - right["xent"]))
        ),
        "state_rms_err": float(rms),
        "rel_diff": abs(float(wrong_loss) - float(loss)) / float(loss),
        "block_grad_err": float(grads["block_grad_err"]),
    }
    failed = CONTROLS[variant]
    assert reading[failed] > config.limits()[failed], (variant, reading)
    if variant == "stop_gradient":
        # The forward pass is the right one: only the gradient tells.
        assert reading["rel_diff"] == 0 and reading["state_rms_err"] == 0


# ---- the tie --------------------------------------------------------


class _Unrolled(nn.Module):
    """``passes x n`` blocks with weights of their OWN, the final norm
    (one a pass) after every ``n``: what the looped model computes,
    without the tie."""

    config: TransformerConfig
    passes: int

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed"
        )(tokens)
        positions = jnp.arange(tokens.shape[1])
        states = []
        for t in range(self.passes):
            for layer in range(cfg.num_layers):
                x = Block(cfg, False, layer, name=f"pass_{t}_layer_{layer}")(
                    x, positions
                )
            x = nn.RMSNorm(
                epsilon=cfg.norm_eps, dtype=cfg.dtype, name=f"norm_{t}"
            )(x)
            states.append(x)
        return jnp.stack(states)


def test_looped_equals_unlooped_copies_and_sums_their_gradients(monkeypatch):
    """``T`` passes over ``n`` blocks = an unlooped model of ``T x n``
    blocks given copies of the weights; a shared leaf's gradient is
    the SUM of the copies' gradients."""
    sizes = configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes, geometry=GEOMETRY)
    params, batch = _system(built, sizes, rows=1)
    cfg = configurations.module(NAME).model_config(sizes)
    looped, unrolled = TransformerLM(cfg), _Unrolled(cfg, 4)
    copies = {"embed": params["embed"]}
    for t in range(4):
        copies[f"norm_{t}"] = params["RMSNorm_0"]
        for layer in range(2):
            copies[f"pass_{t}_layer_{layer}"] = params[f"layer_{layer}"]
    cotangent = jax.random.normal(jax.random.key(1), (4, 1, 32, 32))

    def tied(p):
        z, _ = looped.apply({"params": p}, batch["inputs"], return_exits=True)
        return jnp.sum(z * cotangent), z

    def untied(p):
        z = unrolled.apply({"params": p}, batch["inputs"])
        return jnp.sum(z * cotangent), z

    (_, z), grad = jax.value_and_grad(tied, has_aux=True)(params)
    (_, z_copies), grad_copies = jax.value_and_grad(untied, has_aux=True)(
        copies
    )
    np.testing.assert_allclose(z, z_copies, atol=2e-5)
    for layer in range(2):
        summed = jax.tree.map(
            lambda *g: sum(g),
            *(grad_copies[f"pass_{t}_layer_{layer}"] for t in range(4)),
        )
        for a, b in zip(
            jax.tree.leaves(grad[f"layer_{layer}"]), jax.tree.leaves(summed)
        ):
            assert float(configurations.module(NAME).leaf_error(a, b)) < 1e-5
    assert float(
        configurations.module(NAME).leaf_error(
            grad["RMSNorm_0"]["scale"],
            sum(grad_copies[f"norm_{t}"]["scale"] for t in range(4)),
        )
    ) < 1e-5
    # One pass's own share is NOT the whole: the sum is held, not a copy.
    one = jax.tree.leaves(grad_copies["pass_3_layer_0"])[-1]
    whole = jax.tree.leaves(grad["layer_0"])[-1]
    assert float(configurations.module(NAME).leaf_error(one, whole)) > 0.1


def test_one_pass_without_sandwich_is_the_model_of_before():
    """``loop_passes = 1`` and no sandwich norm: the parameter tree and
    the logits of a config that names neither option, bit for bit;
    the sandwich norm alone adds two scales a block and no gate."""
    base = dict(
        vocab_size=64, num_layers=2, num_heads=2, d_model=16, d_ff=32,
        max_seq_len=16, dtype=jnp.float32, norm="rmsnorm", ffn="swiglu",
    )
    tokens = jnp.arange(16, dtype=jnp.int32)[None] % 64
    plain = TransformerLM(TransformerConfig(**base))
    named = TransformerLM(
        TransformerConfig(**base, loop_passes=1, sandwich_norm=False)
    )
    params = plain.init(jax.random.key(0), tokens, train=False)["params"]
    params_named = named.init(jax.random.key(0), tokens, train=False)["params"]
    assert jax.tree.structure(params) == jax.tree.structure(params_named)
    assert set(params) == {"embed", "layer_0", "layer_1", "RMSNorm_0"}
    assert set(params["layer_0"]) == {
        "RMSNorm_0", "RMSNorm_1", "attention", "ffn"
    }
    np.testing.assert_array_equal(
        plain.apply({"params": params}, tokens),
        named.apply({"params": params_named}, tokens),
    )
    with pytest.raises(ValueError, match="return_exits"):
        plain.apply({"params": params}, tokens, return_exits=True)
    sandwich = TransformerLM(
        TransformerConfig(**base, sandwich_norm=True)
    ).init(jax.random.key(0), tokens, train=False)["params"]
    assert set(sandwich) == set(params)
    assert set(sandwich["layer_0"]) == set(params["layer_0"]) | {
        "RMSNorm_2", "RMSNorm_3"
    }
    looped = TransformerLM(
        TransformerConfig(**base, sandwich_norm=True, loop_passes=3)
    )
    tree = looped.init(jax.random.key(0), tokens, train=False)["params"]
    # Each block ONCE whatever the passes, beside them the gate.
    assert set(tree) == set(params) | {"exit_gate"}
    assert tree["exit_gate"]["kernel"].shape == (16, 1)
    assert jax.tree.map(jnp.shape, tree["layer_0"]) == jax.tree.map(
        jnp.shape, sandwich["layer_0"]
    )
    # Without return_exits a looped model gives its LAST exit's logits.
    z, _ = looped.apply({"params": tree}, tokens, return_exits=True)
    np.testing.assert_allclose(
        looped.apply({"params": tree}, tokens),
        z[-1] @ tree["embed"]["embedding"].T, rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_array_equal(
        looped.apply({"params": tree}, tokens, return_hidden=True), z[-1]
    )


@pytest.mark.parametrize(
    "option",
    [
        {"dropout_rate": 0.1},
        {"experts_total": 4, "d_expert": 8},
        {"moe_every_n": 1, "moe_num_experts": 2},
    ],
)
def test_a_looped_stack_refuses_what_has_no_pass_axis(option):
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
        dtype=jnp.float32, loop_passes=2, **option,
    )
    with pytest.raises(ValueError, match="loop_passes"):
        TransformerLM(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False
        )


def test_exit_log_probs_are_the_plain_products():
    gate = jax.random.normal(jax.random.key(2), (4, 3, 5)) * 3.0
    want = configurations.module(NAME).reference_exit_probs(
        jax.nn.sigmoid(gate)
    )
    np.testing.assert_allclose(
        jnp.exp(exit_log_probs(gate)), want, rtol=1e-5, atol=1e-7
    )
    # A gate far out on either side stays finite in the logarithm.
    far = exit_log_probs(jnp.asarray([[60.0], [-60.0], [0.0]]))
    assert np.isfinite(np.asarray(far)).all()


# ---- remat ------------------------------------------------------------


def test_remat_on_and_off_agree(monkeypatch):
    sizes = configurations.sizes(NAME)
    on = configurations.built(monkeypatch, NAME, sizes, geometry=GEOMETRY)
    off = configurations.built(
        monkeypatch, NAME, configurations.sizes(NAME, remat=False),
        geometry=GEOMETRY,
    )
    params, batch = _system(on, sizes)
    key = jax.random.key(0)

    def loss_and_grad(built):
        return jax.jit(
            jax.value_and_grad(lambda q: built["loss_fn"](q, batch, key)[0])
        )(params)

    (loss_on, grad_on), (loss_off, grad_off) = (
        loss_and_grad(on), loss_and_grad(off)
    )
    assert float(loss_on) == pytest.approx(float(loss_off), rel=1e-6)
    for a, b in zip(jax.tree.leaves(grad_on), jax.tree.leaves(grad_off)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("passes", [1, 4])
def test_the_ladder_prices_applications(passes):
    """A rung costs ``loop_passes x num_layers`` applications, and the
    ``remat.policy`` event counts them."""
    cfg = TransformerConfig(
        vocab_size=128, num_layers=3, num_heads=2, d_model=64, d_ff=256,
        dtype=jnp.bfloat16, norm="rmsnorm", ffn="swiglu",
        loop_passes=passes, sandwich_norm=passes > 1,
    )
    tokens_shape = (2, 256)
    head = 2 * 512 * 128 * 4
    one_width = 3 * passes * 512 * 2  # applications x tokens x itemsize
    qkv, mixed = one_width * 3 * 64, one_width * 64
    for free, rungs, spent in (
        (head + qkv - 1, "", 0),
        (head + qkv, "qkv", qkv),
        (head + qkv + mixed - 1, "qkv", qkv),
        (head + qkv + mixed, "qkv,mixed", qkv + mixed),
    ):
        with device_budget.tracing_with(
            device_budget.Activations(free, 2**34)
        ):
            _, attrs = transformer._remat_ladder(cfg, tokens_shape)
        assert (attrs["rungs"], attrs["rung_bytes"]) == (rungs, spent)
    before = len(trace.snapshot_spans())
    transformer.block_remat(cfg, tokens_shape)
    events = [
        r for r in trace.snapshot_spans()[before:]
        if r["name"] == "remat.policy"
    ]
    assert [e["attrs"]["blocks"] for e in events] == [3 * passes]


# ---- through the trainer ----------------------------------------------


def test_run_step_journals_the_loop_and_restores_exactly(
    tmp_path, monkeypatch
):
    """One ``ElasticTrainer.run_step`` of the tiny looped model: the
    ``loop.schedule`` event of the traced model, the step's
    ``loop.exit`` counters journalled, a save, and a restore into a
    fresh trainer that holds the same leaves and continues bit for
    bit."""
    from adaptdl_tpu import checkpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    data = config.make_dataset(sizes, 5, 8)
    batch = {k: v[:2] for k, v in data.items()}
    built = configurations.built(monkeypatch, NAME, sizes, geometry=GEOMETRY)
    trainer = built["trainer"]
    holder = {"state": trainer.init_state()}
    ck = trainer.make_checkpoint_state(
        lambda: holder["state"], lambda s: holder.__setitem__("state", s)
    )
    before = len(trace.snapshot_spans())
    trainer._calibrated.add(1)
    holder["state"], metrics = trainer.run_step(
        holder["state"], batch, loader_stub(1, 1)
    )
    assert np.isfinite(float(metrics["loss"]))
    exits = metrics["counters"]["loop.exit"]
    assert int(exits["micro_batches"]) == 2
    np.testing.assert_allclose(np.sum(exits["p"]) / 2, 1.0, rtol=1e-5)
    new = trace.snapshot_spans()[before:]
    schedule = [r["attrs"] for r in new if r["name"] == "loop.schedule"]
    assert schedule and schedule[-1] == {
        "passes": 4, "blocks": 2, "applications": 8, "how": "scan",
        "exits": 4,
        "head": "xent_sum, 32 of 128 rows a chunk, gradients in the "
        "forward",
    }
    policy = [r["attrs"] for r in new if r["name"] == "remat.policy"]
    assert policy and policy[-1]["blocks"] == 8
    journalled = [r["attrs"] for r in new if r["name"] == "loop.exit"]
    assert len(journalled) == 1
    assert journalled[0]["micro_batches"] == 2
    assert len(journalled[0]["xent"]) == len(journalled[0]["p"]) == 4
    assert 2.0 < journalled[0]["expected_exit"] < 8.0  # two micro-batches
    assert journalled[0]["entropy"] > 0
    checkpoint.save_all_states()
    saved = jax.tree.map(np.asarray, trainer.params_tree(holder["state"]))
    assert set(saved) == {
        "embed", "layer_0", "layer_1", "RMSNorm_0", "exit_gate", "lm_head"
    }
    holder["state"], after = trainer.run_step(
        holder["state"], batch, loader_stub(1, 1)
    )
    ck.unregister()

    again = configurations.built(
        monkeypatch, NAME, sizes, seed=11, geometry=GEOMETRY
    )["trainer"]
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
    again._calibrated.add(1)
    holder2["state"], resumed = again.run_step(
        holder2["state"], batch, loader_stub(1, 1)
    )
    assert float(resumed["loss"]) == float(after["loss"])
    ck2.unregister()


def test_the_step_applies_the_gradient_summed_over_the_passes(monkeypatch):
    """What ``run_step`` accumulates, squares for the GNS and hands to
    the optimizer is ``jax.grad`` of the looped loss — a shared leaf's
    sum over the passes: under plain SGD at rate 1 a step moves every
    leaf by exactly that gradient, and by another than the last pass's
    alone."""
    import optax

    from adaptdl_tpu.trainer import ElasticTrainer

    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    model = TransformerLM(config.model_config(sizes))
    loss_fn = looped_lm_loss_fn(model, beta=0.1, chunk_size=32)
    data = config.make_dataset(sizes, 5, 4)
    batch = {k: jnp.asarray(v[:1]) for k, v in data.items()}
    params = _seeded(
        model.init(jax.random.key(0), batch["inputs"], train=False)["params"]
    )
    trainer = ElasticTrainer(
        loss_fn=loss_fn, params=params, optimizer=optax.sgd(1.0),
        init_batch_size=1, seed=0,
    )
    state = trainer.init_state()
    trainer._calibrated.add(1)
    state, _ = trainer.run_step(state, batch, loader_stub(1, 0))
    moved = jax.tree.map(
        lambda a, b: a - b, params, trainer.params_tree(state)
    )
    weights = config.reference_weights(params, sizes)
    args = (weights, batch["inputs"], batch["targets"], sizes)
    errors = jax.jit(config.grad_errors)(
        config.reference_weights(moved, sizes),
        *config.reference_gradient(*args),
    )
    for name, err in errors.items():
        assert float(err) < 1e-4, (name, float(err))
    last_pass_only = jax.jit(config.grad_errors)(
        config.reference_weights(moved, sizes),
        *config.reference_gradient(*args, variant="stop_gradient"),
    )
    assert float(last_pass_only["block_grad_err"]) > 0.1
