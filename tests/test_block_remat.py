"""What a remat'd ``Block`` keeps from forward to backward
(``models.transformer.block_remat``): the flash kernel's ``out`` and
``lse``, by the names the kernel's forward rule gives them, under
every ``remat_policy`` — attention is never computed twice — and
nothing else changes: outside a remat, and without the kernel, the
program is the one the bare ``nn.remat`` gave."""

import importlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from adaptdl_tpu import trace
from adaptdl_tpu.models import TransformerConfig, init_transformer
from adaptdl_tpu.models import transformer
from adaptdl_tpu.ops import make_flash_attention
from tests.test_flash_attention import _kernel_calls

# ``import adaptdl_tpu.ops.flash_attention as m`` yields the FUNCTION
# (the package re-exports it under the module's name).
flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")

NUM_LAYERS = 2
SEQ = 32


def _model(remat, policy=None, causal=True, flash=True):
    cfg = TransformerConfig(
        vocab_size=64, num_layers=NUM_LAYERS, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=SEQ, dtype=jnp.float32, remat=remat,
        remat_policy=policy, causal=causal,
        attention_fn=(
            make_flash_attention(causal=causal, block_q=16, block_k=16)
            if flash else None
        ),
    )
    model, params = init_transformer(cfg, seq_len=SEQ)
    tokens = np.random.default_rng(3).integers(0, 64, size=(2, SEQ + 1))
    inputs, targets = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])

    def loss(p):
        logits = model.apply({"params": p}, inputs, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()

    return loss, params


def _events(name):
    return [
        rec["attrs"] for rec in trace.snapshot_spans() if rec["name"] == name
    ]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "remat, policy",
    [
        (False, None),
        (True, None),
        (True, "dots_with_no_batch_dims_saveable"),
    ],
)
def test_gradient_runs_the_forward_kernel_once_a_layer(remat, policy, causal):
    """``jax.grad`` of the model holds one forward and one backward
    kernel a layer, remat'd or not, whatever policy is named (a
    ``pallas_call`` is not a dot: no named policy keeps its output, and
    the bare ``nn.remat`` re-ran it in every backward)."""
    loss, params = _model(remat, policy, causal)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert _kernel_calls(jaxpr.jaxpr) == (NUM_LAYERS, NUM_LAYERS)


def _lowered(remat, policy, flash):
    """The gradient's StableHLO, without the serial numbers that
    lowering appends to the names of private functions."""
    loss, params = _model(remat, policy, flash=flash)
    text = jax.jit(jax.grad(loss)).lower(params).as_text()
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


@pytest.mark.parametrize(
    "remat, policy, flash",
    [
        (False, None, True),
        (True, "dots_with_no_batch_dims_saveable", False),
        (True, None, False),
    ],
    ids=["kernel_no_remat", "remat_named_policy_plain", "remat_plain"],
)
def test_bypass_lowers_to_the_program_without_names(
    monkeypatch, remat, policy, flash
):
    """Outside a remat a name is an identity, and without the kernel
    nothing is named: the lowered gradient is the same with
    ``checkpoint_name`` in place, with it patched to the identity, and
    with the blocks wrapped as before this policy existed (the bare
    ``nn.remat`` under the named policy alone)."""
    text = _lowered(remat, policy, flash)
    monkeypatch.setattr(flash_mod, "checkpoint_name", lambda x, name: x)

    def bare(config):
        if not config.remat:
            return transformer.Block
        kwargs = {}
        if config.remat_policy is not None:
            kwargs["policy"] = getattr(
                jax.checkpoint_policies, config.remat_policy
            )
        return nn.remat(transformer.Block, static_argnums=(), **kwargs)

    monkeypatch.setattr(transformer, "block_remat", bare)
    assert _lowered(remat, policy, flash) == text


def test_names_are_what_makes_the_difference(monkeypatch):
    """The teeth of the tests above: with ``checkpoint_name`` patched
    to the identity the remat'd model re-runs the kernel in every
    backward, as the bare ``nn.remat`` did."""
    monkeypatch.setattr(flash_mod, "checkpoint_name", lambda x, name: x)
    loss, params = _model(True)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert _kernel_calls(jaxpr.jaxpr) == (2 * NUM_LAYERS, NUM_LAYERS)


@pytest.mark.parametrize(
    "policy", [None, "dots_with_no_batch_dims_saveable"]
)
def test_remat_policy_event_once_per_traced_model(policy):
    """``remat.policy``: recorded when a remat'd model is traced, once
    a trace, with what the blocks keep; a model without remat records
    none."""
    loss, params = _model(True, policy)
    before = len(_events("remat.policy"))
    jax.make_jaxpr(jax.grad(loss))(params)
    (attrs,) = _events("remat.policy")[before:]
    assert attrs == {
        "saved_names": "flash_out,flash_lse",
        "policy": policy or "none",
        "blocks": NUM_LAYERS,
    }
    assert attrs["saved_names"] == ",".join(
        (flash_mod.SAVED_OUT, flash_mod.SAVED_LSE)
    )
    loss, params = _model(False)
    before = len(_events("remat.policy"))
    jax.make_jaxpr(jax.grad(loss))(params)
    assert len(_events("remat.policy")) == before


def _pipeline_config(policy, remat=True):
    return TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=16, d_ff=32,
        max_seq_len=8, dtype=jnp.float32, remat=remat, remat_policy=policy,
    )


def _pipeline_dots(policy, remat=True):
    """``dot_general``s in the gradient of the pipelined LM's loss on a
    two-stage mesh: what the backward recomputes shows in their
    number."""
    from adaptdl_tpu.models.pipeline_lm import (
        init_pipeline_lm,
        pipeline_lm_sharding_fn,
    )
    from adaptdl_tpu.parallel import create_mesh
    from adaptdl_tpu.parallel.mesh import STAGE_AXIS

    loss_fn, params = init_pipeline_lm(
        _pipeline_config(policy, remat), num_stages=2, num_micro=2, seq_len=8
    )
    mesh = create_mesh({"data": 1, STAGE_AXIS: 2}, devices=jax.devices()[:2])
    specs = jax.tree_util.tree_map_with_path(pipeline_lm_sharding_fn, params)
    batch = {"tokens": jnp.zeros((4, 9), jnp.int32)}
    grad = jax.shard_map(
        lambda p, b: jax.grad(loss_fn)(p, b, jax.random.key(0)),
        mesh=mesh, in_specs=(specs, P()), out_specs=specs,
    )
    count = 0

    def walk(jaxpr):
        nonlocal count
        for eqn in jaxpr.eqns:
            count += eqn.primitive.name == "dot_general"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(grad)(params, batch).jaxpr)
    return count


def test_pipeline_lm_honours_remat_policy():
    """``init_pipeline_lm`` wraps its blocks through ``block_remat``
    too: a named policy keeps what it says (fewer matmuls re-run in
    the backward), a typo fails when the model is built, and the event
    names the policy."""
    from adaptdl_tpu.models.pipeline_lm import init_pipeline_lm

    before = len(_events("remat.policy"))
    no_remat = _pipeline_dots(None, remat=False)
    assert len(_events("remat.policy")) == before
    bare = _pipeline_dots(None)
    saved = _pipeline_dots("dots_with_no_batch_dims_saveable")
    assert no_remat <= saved < bare
    assert [a["policy"] for a in _events("remat.policy")[before:]] == [
        "none", "dots_with_no_batch_dims_saveable",
    ]
    with pytest.raises(ValueError, match="remat_policy"):
        init_pipeline_lm(
            _pipeline_config("dots_savable"),  # typo
            num_stages=2, num_micro=2, seq_len=8,
        )
