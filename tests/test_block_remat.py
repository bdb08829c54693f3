"""What a remat'd ``Block`` keeps from forward to backward
(``models.transformer.block_remat``): the flash kernel's ``out`` and
``lse``, by the names the kernel's forward rule gives them, under
every ``remat_policy`` — attention is never computed twice — and,
as far as the budget the trainer sets around tracing allows
(``adaptdl_tpu.device_budget``), the rungs of a ladder: q / k / v,
the residual after the mixer, ``ff_up``'s result. Nothing else
changes: outside a remat, and without the kernel and a budget, the
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

from adaptdl_tpu import device_budget, trace
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
        d_ff=96, max_seq_len=SEQ, dtype=jnp.float32, remat=remat,
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


def _no_names(monkeypatch):
    """``checkpoint_name`` patched to the identity wherever a block or
    the kernel names something."""
    for mod in (flash_mod, transformer):
        monkeypatch.setattr(mod, "checkpoint_name", lambda x, name: x)


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
    and a budget nothing named is saved: the lowered gradient is the
    same with ``checkpoint_name`` in place, with it patched to the
    identity, and with the blocks wrapped as before this policy
    existed (the bare ``nn.remat`` under the named policy alone)."""
    text = _lowered(remat, policy, flash)
    _no_names(monkeypatch)

    def bare(config, tokens_shape=None):
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
    _no_names(monkeypatch)
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
        # No trainer set a budget around this trace: no rung.
        "rungs": "", "rung_bytes": 0, "budget_bytes": -1, "bytes_limit": -1,
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


# ---- the ladder (PR 41) ---------------------------------------------

BATCH, VOCAB, D_MODEL, D_FF = 2, 64, 32, 96  # _model's
TOKENS = BATCH * SEQ
LOGITS = 2 * TOKENS * VOCAB * 4  # float32 logits and their gradient
PER_WIDTH = NUM_LAYERS * TOKENS * 4  # float32 blocks
RUNG_BYTES = {
    "qkv": 3 * D_MODEL * PER_WIDTH,
    "mixed": D_MODEL * PER_WIDTH,
    "ff_up": D_FF * PER_WIDTH,
}
PATHS = {
    "plain": dict(flash=False),
    "flash": dict(flash=True),
    "flash_bidirectional": dict(flash=True, causal=False),
}


def _budget(*rungs, slack=0):
    """What the trainer would hand a model so that exactly ``rungs``
    fit: the logits' bytes, the rungs' and ``slack``."""
    free = LOGITS + sum(RUNG_BYTES[r] for r in rungs) + slack
    return device_budget.Activations(free, 1 << 34)


def _forward_dots(jaxpr, out_shape, rhs_shape):
    """``dot_general``s with this result and this right operand: a
    projection's forward product, first pass and re-runs alike (its
    gradients have other shapes)."""
    count = 0

    def walk(jaxpr):
        nonlocal count
        for eqn in jaxpr.eqns:
            count += (
                eqn.primitive.name == "dot_general"
                and eqn.outvars[0].aval.shape == out_shape
                and eqn.invars[1].aval.shape == rhs_shape
            )
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    return count


def _projections(jaxpr):
    """(QKV, ``ff_up``) forward products in a gradient's jaxpr."""
    heads, head_dim = 2, D_MODEL // 2
    return (
        _forward_dots(
            jaxpr, (BATCH, SEQ, 3, heads, head_dim),
            (D_MODEL, 3, heads, head_dim),
        ),
        _forward_dots(jaxpr, (BATCH, SEQ, D_FF), (D_MODEL, D_FF)),
    )


@pytest.mark.parametrize("path", ["plain", "flash"])
@pytest.mark.parametrize(
    "rungs, a_layer",
    [
        ((), (2, 2)),
        (("qkv",), (1, 2)),
        (("qkv", "mixed"), (1, 2)),
        (("qkv", "mixed", "ff_up"), (1, 1)),
    ],
    ids=["none", "one", "two", "all"],
)
def test_ladder_keeps_what_the_budget_allows(path, rungs, a_layer):
    """With a budget of no rung, of one, of all three, the gradient of
    a two-layer model holds 2, 1 and 1 QKV and 2, 2 and 1 ``ff_up``
    products a layer: a rung that fits is not computed again in the
    backward, and the ``remat.policy`` event says which were taken,
    their bytes and the budget they were held against. One byte short
    of a rung is the rung below."""
    loss, params = _model(True, **PATHS[path])
    before = len(_events("remat.policy"))
    with device_budget.tracing_with(_budget(*rungs)):
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    qkv, ff_up = _projections(jaxpr.jaxpr)
    assert (qkv, ff_up) == tuple(NUM_LAYERS * n for n in a_layer)
    (attrs,) = _events("remat.policy")[before:]
    spent = sum(RUNG_BYTES[r] for r in rungs)
    assert attrs["rungs"] == ",".join(rungs)
    assert attrs["rung_bytes"] == spent
    assert attrs["budget_bytes"] == spent
    assert attrs["bytes_limit"] == 1 << 34
    assert attrs["saved_names"].startswith("flash_out,flash_lse")
    if rungs:
        with device_budget.tracing_with(_budget(*rungs, slack=-1)):
            jax.make_jaxpr(jax.grad(loss))(params)
        assert _events("remat.policy")[-1]["rungs"] == ",".join(rungs[:-1])


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize(
    "rungs",
    [(), ("qkv",), ("qkv", "mixed"), ("qkv", "mixed", "ff_up")],
    ids=["none", "one", "two", "all"],
)
def test_ladder_gradients_equal_no_remat(path, rungs):
    """Every saved value is the value the backward would recompute:
    at every depth the gradients are those of ``remat=False``."""
    loss, params = _model(False, **PATHS[path])
    want = jax.grad(loss)(params)
    loss, params = _model(True, **PATHS[path])
    with device_budget.tracing_with(_budget(*rungs)):
        got = jax.jit(jax.grad(loss))(params)
    for (where, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)
    ):
        np.testing.assert_allclose(a, b, atol=1e-6, err_msg=str(where))


@pytest.mark.parametrize("path", ["plain", "flash"])
def test_no_budget_lowers_to_the_program_before_the_ladder(
    monkeypatch, path
):
    """Where nobody set a budget, or the device does not say its
    ``bytes_limit`` (``None``), the remat'd model lowers to the text it
    had before the ladder's names existed: only ``out`` and ``lse``
    still carry one."""
    flash = PATHS[path]["flash"]
    text = _lowered(True, None, flash)
    with device_budget.tracing_with(None):
        assert _lowered(True, None, flash) == text
    with device_budget.tracing_with(_budget("qkv", "mixed", "ff_up")):
        assert _lowered(True, None, flash) != text
    name = flash_mod.checkpoint_name
    monkeypatch.setattr(
        flash_mod, "checkpoint_name",
        lambda x, n: x if n == flash_mod.SAVED_QKV else name(x, n),
    )
    monkeypatch.setattr(transformer, "checkpoint_name", lambda x, n: x)
    assert _lowered(True, None, flash) == text


def test_named_policy_adds_to_the_ladder():
    """``remat_policy`` keeps its meaning beside a budget: what the
    named policy saves is saved too (``ff_up``'s product, with a
    budget that stops a rung short of it, is not re-run), and the
    rungs that fit are still taken."""
    counts = {}
    for policy in (None, "dots_with_no_batch_dims_saveable"):
        loss, params = _model(True, policy)
        with device_budget.tracing_with(_budget("qkv")):
            jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
        assert _events("remat.policy")[-1]["rungs"] == "qkv"
        counts[policy] = _projections(jaxpr.jaxpr)
    assert counts[None] == (NUM_LAYERS, 2 * NUM_LAYERS)
    assert counts["dots_with_no_batch_dims_saveable"] == (
        NUM_LAYERS, NUM_LAYERS,
    )


class _Stats(dict):
    """A device's ``memory_stats()`` that remembers what was asked."""

    asked: list = []

    def get(self, key, default=None):
        self.asked.append(key)
        return super().get(key, default)

    def __getitem__(self, key):
        self.asked.append(key)
        return super().__getitem__(key)


def _trainer(monkeypatch, tmp_path, stats, checkpointed=True):
    """A trainer over the two-layer model on one CPU device that
    reports ``stats`` (a callable) as its memory."""
    from adaptdl_tpu import trainer as trainer_mod
    from adaptdl_tpu.parallel import create_mesh

    if checkpointed:  # the AOT cache, so the non-donating twin
        monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    else:
        monkeypatch.delenv("ADAPTDL_CHECKPOINT_PATH", raising=False)
        monkeypatch.delenv("ADAPTDL_COMPILE_CACHE", raising=False)
    monkeypatch.setattr(trainer_mod, "_memory_stats", lambda d: stats())
    cfg = TransformerConfig(
        vocab_size=VOCAB, num_layers=NUM_LAYERS, num_heads=2,
        d_model=D_MODEL, d_ff=D_FF, max_seq_len=SEQ, dtype=jnp.float32,
    )
    model, params = init_transformer(cfg, seq_len=SEQ)

    def loss_fn(p, batch, _rng):
        logits = model.apply({"params": p}, batch["inputs"], train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["targets"]
        ).mean()

    trainer = trainer_mod.ElasticTrainer(
        loss_fn=loss_fn, params=params, optimizer=optax.adam(1e-3),
        init_batch_size=BATCH,
        mesh=create_mesh(devices=jax.devices()[:1]),
    )
    batch = {
        k: np.zeros((BATCH, SEQ), np.int32) for k in ("inputs", "targets")
    }
    return trainer, trainer.init_state(), batch


def test_trainer_budget_is_a_pure_function_of_the_job(monkeypatch, tmp_path):
    """The budget a trainer sets around tracing its programs:
    ``bytes_limit`` less the copies of state and gradient the program
    holds and a sixteenth in reserve — two for the non-donating twin —
    from shapes and the limit alone. A device whose ``bytes_in_use``
    moves between two traces gets the same rungs twice, and is never
    asked for it."""
    in_use = iter(range(1 << 20, 1 << 30, 1 << 20))
    limit = 1 << 28  # (room for the twin's own program, too)
    _Stats.asked = []

    def stats():
        return _Stats(
            bytes_limit=limit, bytes_in_use=next(in_use),
            peak_bytes_in_use=next(in_use),
        )

    trainer, state, batch = _trainer(monkeypatch, tmp_path, stats)
    held = trainer._held_bytes
    from adaptdl_tpu import storage

    assert held == storage.device_bytes(state) + storage.device_bytes(
        state.params
    )
    free = limit - 2 * held - limit // 16
    assert trainer._activations() == trainer._activations() == (free, limit)
    seen = []
    # A step's first call traces its twin. (Two programs, not one
    # twice: the second would find the first's entry in the AOT cache,
    # and XLA:CPU cannot run a deserialized executable here.)
    for accum in (0, 1):
        before = len(_events("remat.policy"))
        doubled = jax.tree.map(lambda x: np.tile(x, (accum + 1, 1)), batch)
        trainer._build_step(BATCH, accum)(
            state, trainer.shard_batch(doubled)
        )
        seen.append(_events("remat.policy")[before:])
    assert seen[0] == seen[1] and len(seen[0]) == 1
    assert seen[0][0]["bytes_limit"] == limit
    assert seen[0][0]["budget_bytes"] == free - LOGITS
    assert seen[0][0]["rungs"] == "qkv,mixed,ff_up"
    assert set(_Stats.asked) == {"bytes_limit"}
    assert device_budget.activations() is None  # nothing left set
    donated = [a["donated"] for a in _events("step.donation")[-2:]]
    assert donated == [False] * 2


def test_donating_for_want_of_memory_gets_no_rung(monkeypatch, tmp_path):
    """A job whose state does not fit twice runs the donating step, and
    a job that cannot afford a second copy of its state has no bytes
    for activations: that step is traced with no budget, though one
    copy and all three rungs would fit the limit by arithmetic."""
    trainer, state, batch = _trainer(monkeypatch, tmp_path, dict)
    limit = 2 * trainer._held_bytes - 1
    from adaptdl_tpu import trainer as trainer_mod

    monkeypatch.setattr(
        trainer_mod, "_memory_stats", lambda d: {"bytes_limit": limit}
    )
    one_copy = limit - trainer._held_bytes - limit // 16
    assert one_copy - LOGITS > sum(RUNG_BYTES.values())
    before = len(_events("remat.policy"))
    trainer.train_step(BATCH, 0)(state, trainer.shard_batch(batch))
    (attrs,) = _events("remat.policy")[before:]
    assert (attrs["rungs"], attrs["bytes_limit"]) == ("", -1)
    donation = _events("step.donation")[-1]
    assert donation["donated"] and donation["decided_by"] == "state"


@pytest.mark.parametrize(
    "limit, rungs",
    [(None, ""), (1 << 24, "qkv,mixed,ff_up"), (1 << 20, "")],
    ids=["unknown_limit", "room", "no_room"],
)
def test_trainer_budgets_its_programs(monkeypatch, tmp_path, limit, rungs):
    """A job with no checkpoint path has no twin: its donating step
    and its calibration program are traced under the same budget a
    twin would be (no compiler-checked fall-back stands behind them).
    A device that does not say its limit (the CPU) gives no budget; a
    limit the state nearly fills gives a budget with no room."""
    from adaptdl_tpu import metrics

    trainer, state, batch = _trainer(
        monkeypatch, tmp_path,
        lambda: {} if limit is None else {"bytes_limit": limit},
        checkpointed=False,
    )
    monkeypatch.setattr(metrics, "profile_accum_time", lambda *a: None)
    before = len(_events("remat.policy"))
    trainer.calibrate_accum_time(state, batch, BATCH, repeats=1)
    # (Donates ``state``.)
    trainer.train_step(BATCH, 0)(state, trainer.shard_batch(batch))
    events = _events("remat.policy")[before:]
    assert [a["rungs"] for a in events] == [rungs, rungs]
    if limit is None:
        assert trainer._activations() is None
        assert events[0]["bytes_limit"] == -1
    else:
        free = limit - 2 * trainer._held_bytes - limit // 16
        assert trainer._activations() == (free, limit)
        assert events[0]["budget_bytes"] == free - LOGITS
