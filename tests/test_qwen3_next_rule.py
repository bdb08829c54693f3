"""The rest of what the qwen3-next-80b-a3b configuration forced (PR 49;
``tests/test_qwen3_next.py`` holds the mixers and the routed layer): the
delta rule with ONE decay a head and fewer key heads than value heads
against the recurrence, the zero-centred norm, rotary over a part of a
head, the whole model's loss and gradient against the configuration's
plain reference, its parameter and FLOP counts, and what a config
refuses."""

import functools

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_qwen3_next import (  # noqa: F401 (the fixture is autouse)
    NAME,
    _close,
    _events,
    _rows_of_several_chunks,
)

from adaptdl_tpu import trace
from adaptdl_tpu.models import transformer
from adaptdl_tpu.models.transformer import (
    TransformerConfig,
    ZeroCentredRMSNorm,
    rope,
)
from adaptdl_tpu.ops import kda as kda_op

# ---- the delta rule with one decay a head ------------------------------


def _rule_inputs(seed, batch=2, seq=40, key_heads=2, heads=2, dk=8, dv=8):
    keys = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(keys[0], (batch, seq, key_heads, dk))
    k = jax.random.normal(keys[1], (batch, seq, key_heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, seq, heads, dv))
    g = -0.5 * jnp.exp(jax.random.normal(keys[3], (batch, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, heads)))
    return q, k, v, g, beta


def _value_and_grads(fn, args):
    def weighted(*a):
        out = fn(*a).astype(jnp.float32)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size)).reshape(out.shape))

    return jax.jit(jax.value_and_grad(weighted, tuple(range(len(args)))))(
        *args
    )


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("chunk,seq", [(16, 40), (32, 64)])
def test_kda_with_a_decay_a_head_is_the_rule(chunk, seq, use_kernel):
    """``g`` [b, s, h] equals the same call with ``g`` broadcast over
    the channels and equals the recurrence token by token: forward and
    every operand's gradient (g's: the sum over its channels)."""
    args = _rule_inputs(0, seq=seq)
    rule = functools.partial(kda_op.kda, chunk=chunk, use_kernel=use_kernel)

    def a_channel(q, k, v, g, beta):
        return rule(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)

    head = _value_and_grads(rule, args)
    _close(head, _value_and_grads(a_channel, args), 1e-5)
    _close(head, _value_and_grads(kda_op.kda_recurrent, args))


@pytest.mark.parametrize("use_kernel", [True, False])
def test_key_heads_serve_several_value_heads(use_kernel):
    """2 key heads for 4 value heads equals the rule on q and k
    repeated a head, forward and every gradient (q's and k's: summed
    over the value heads a key head serves)."""
    args = _rule_inputs(1, key_heads=2, heads=4)
    rule = functools.partial(kda_op.kda, chunk=16, use_kernel=use_kernel)

    def repeated(q, k, v, g, beta):
        return rule(
            jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta
        )

    got = _value_and_grads(rule, args)
    _close(got, _value_and_grads(repeated, args), 1e-5)
    _close(got, _value_and_grads(kda_op.kda_recurrent, args))


def test_key_heads_in_head_groups_is_the_rule(monkeypatch):
    """A group of heads at a time holds whole key heads: 4 key heads
    for 8 value heads in 4 groups equal the call in one."""
    args = _rule_inputs(2, seq=32, key_heads=4, heads=8)
    want = kda_op.kda(*args, chunk=16)
    monkeypatch.setattr(kda_op, "_GROUP_ELEMENTS", 2 * 2 * 32 * 8)
    since = len(trace.snapshot_spans())
    got = _value_and_grads(functools.partial(kda_op.kda, chunk=16), args)
    attrs = _events("kda.schedule", since)[-1]
    assert attrs["head_groups"] == 4
    monkeypatch.undo()
    _close(got[0], jnp.sum(
        want * jnp.cos(jnp.arange(want.size)).reshape(want.shape)
    ), 1e-5)
    _close(got, _value_and_grads(kda_op.kda_recurrent, args))


def test_kda_schedule_says_which_decay_ran():
    """One decay a head runs the chunk body that takes it as such
    (``head``) on the kernel path and, broadcast over the channels,
    the XLA ``_prepare`` on the fallback (``head_as_channel``); a
    decay a channel says ``channel``."""
    q, k, v, g, beta = _rule_inputs(3, key_heads=2, heads=4)
    since = len(trace.snapshot_spans())
    kda_op.kda(q, k, v, g, beta, chunk=16)
    kda_op.kda(q, k, v, g, beta, chunk=16, use_kernel=False)
    kda_op.kda(
        jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v,
        jnp.broadcast_to(g[..., None], v.shape), beta, chunk=16,
    )
    head, fallback, channel = _events("kda.schedule", since)
    assert (head["decay"], head["key_heads"], head["value_heads"]) == (
        "head", 2, 4
    )
    assert head["own_work"] == (
        "pallas:delta_chunk_head_fwd,delta_chunk_head_bwd"
    )
    assert (fallback["decay"], fallback["own_work"]) == (
        "head_as_channel", "xla"
    )
    assert (channel["decay"], channel["key_heads"]) == ("channel", 4)
    assert channel["own_work"] == "pallas:delta_chunk_fwd,delta_chunk_bwd"
    assert head["heads"] == channel["heads"] == 4


# ---- the norm, rotary over a part of a head ----------------------------


def test_zero_centred_norm_scales_by_one_plus_w():
    x = jax.random.normal(jax.random.key(0), (3, 5, 16)) * 3.0
    norm = ZeroCentredRMSNorm(epsilon=1e-6)
    params = norm.init(jax.random.key(1), x)["params"]
    assert not np.asarray(params["scale"]).any()  # initialised 0
    w = jax.random.normal(jax.random.key(2), (16,))
    got = norm.apply({"params": {"scale": w}}, x)
    want = configurations.module(NAME)._rms_norm(x, w, 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    plain = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(
        norm.apply({"params": params}, x), plain, rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("lanes", [4, 8, 16])
def test_rotary_over_the_first_lanes_only(lanes):
    x = jax.random.normal(jax.random.key(0), (2, 24, 3, 16))
    positions = jnp.arange(24)
    got = rope(x, positions, 1e7, lanes)
    want = configurations.module(NAME)._rotary(x, 1e7, lanes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[..., lanes:], x[..., lanes:])
    turned = got[:, 1:, :, :lanes] - x[:, 1:, :, :lanes]
    assert float(jnp.abs(turned).max()) > 0.1
    if lanes == 16:  # every lane: the rotary of before, to the bit
        np.testing.assert_array_equal(got, rope(x, positions, 1e7))


# ---- the whole model ---------------------------------------------------


def test_loss_and_gradients_equal_the_reference(monkeypatch):
    """Four layers of the cell's pattern (gdn, gdn, gdn, gated
    attention; all routed with a gated shared expert), remat on, the
    flash kernels, the delta rule's kernels, a share of 4 of 16
    experts, the untied head."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    data = config.make_dataset(sizes, 5, 4)
    batch = {k: jnp.asarray(v[:2]) for k, v in data.items()}

    def system(params):
        return built["loss_fn"](params, batch, jax.random.key(0))[0]

    def reference(params):
        return config.reference_loss(
            config.reference_weights(params, sizes),
            batch["inputs"], batch["targets"], sizes,
        )[0]

    loss, grads = jax.value_and_grad(system)(params)
    want, want_grads = jax.value_and_grad(reference)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        scale = max(float(jnp.abs(ref).max()), 1e-6)
        assert float(jnp.abs(got - ref).max()) / scale < 5e-4, (
            jax.tree_util.keystr(path)
        )
    # (``reference_check`` itself on such a model: the cell's CPU
    # rehearsal, benchmark/tests/test_qwen3_next_cell.py.)


def test_the_parameters_are_the_files_sum(monkeypatch):
    """The published widths give the count the configuration's file
    states: 625.7 M."""
    monkeypatch.setattr(kda_op, "CHUNK", 64)  # the real sizes' chunk
    config, sizes = configurations.module(NAME), configurations.published(NAME)
    model = transformer.TransformerLM(config.model_config(sizes))
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 128), jnp.int32), train=False
        )
    )["params"]
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    d = 2048
    gdn = d * 12288 + d * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * d
    attention = d * 16 * 512 + d * 2 * 2 * 256 + 4096 * d + 2 * 256
    routed = d * 512 + 3 * d * 512 + d + 32 * 3 * d * 512
    assert count == (
        3 * gdn + attention + 4 * (routed + 2 * d) + 2 * 18992 * d + d
    )
    assert round(count / 1e6, 1) == 625.7
    assert shapes["layer_0"]["gdn"]["in_proj"]["kernel"].shape == (d, 12288)
    assert shapes["layer_3"]["attention"]["q"]["kernel"].shape == (d, 16, 512)


def test_flops_count_agrees_with_the_configurations(monkeypatch):
    """``adaptdl_tpu.flops`` (the goodput model's MFU) counts the new
    mixers, the gates and the shared expert as the configuration's
    ``train_flops_per_unit`` (the cell's ``mfu``) does."""
    from adaptdl_tpu.flops import transformer_train_flops

    monkeypatch.setattr(kda_op, "CHUNK", 64)  # the real sizes' chunk
    config, sizes = configurations.module(NAME), configurations.published(NAME)
    seq = sizes["sequence_length"]
    got = transformer_train_flops(config.model_config(sizes), 1, seq)
    want = config.train_flops_per_unit(sizes) * seq
    assert got.total == pytest.approx(want, rel=1e-9)
    parts = config.forward_flops_per_token(sizes)
    assert got.attention == pytest.approx(
        3 * seq * (parts["attention"] + parts["gdn_mixing"]), rel=1e-9
    )
    # The issue's reckoning: 534 MFLOP forward, 76% in the two mixers.
    forward = sum(parts.values())
    assert round(forward / 1e6) == 535
    mixers = sum(
        parts[k] for k in ("gdn_projections", "gdn_mixing",
                           "attention_projections", "attention")
    )
    assert 0.75 < mixers / forward < 0.78


_BASE = dict(
    vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
    dtype=jnp.float32, head_dim=8,
)
_GDN = dict(
    layer_types=("gdn",), linear_key_heads=1, linear_value_heads=2,
    linear_key_head_dim=8, linear_value_head_dim=8,
)


@pytest.mark.parametrize(
    "options,field",
    [
        ({**_GDN, "seq_axis": "seq"}, "seq_axis"),
        ({**_GDN, "linear_value_heads": 0}, "linear_value_heads"),
        ({**_GDN, "linear_key_head_dim": 0}, "linear_key_head_dim"),
        ({**_GDN, "linear_key_heads": 2, "linear_value_heads": 3},
         "key heads"),
        (dict(norm_zero_centred=True), "norm_zero_centred"),
        (dict(shared_expert_gate=True), "shared_expert_gate"),
        (dict(layer_types=("gda",)), "gdn"),
    ],
)
def test_config_refuses_with_the_fields_name(options, field):
    with pytest.raises(ValueError, match=field):
        cfg = TransformerConfig(**_BASE, **options)
        transformer._mixer(cfg, 0)


def test_a_remat_block_keeps_the_rules_output_by_name():
    """``block_remat`` of a model with gdn layers saves ``kda_out``
    beside the flash kernel's names."""
    since = len(trace.snapshot_spans())
    config = configurations.module(NAME).model_config(
        configurations.sizes(NAME)
    )
    transformer.block_remat(config, (2, 64))
    (attrs,) = _events("remat.policy", since)
    assert attrs["saved_names"].split(",") == [
        "flash_out", "flash_lse", "kda_out"
    ]
