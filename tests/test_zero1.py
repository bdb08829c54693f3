"""ZeRO-1 optimizer-state sharding tests: the sharded update must be
indistinguishable from the replicated one (same params, same GNS
statistics, same LR factors), and checkpoints must rescale across
replica counts through the canonical flat layout."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from adaptdl_tpu.models import TransformerConfig, init_transformer, lm_loss_fn
from adaptdl_tpu.parallel import create_mesh
from adaptdl_tpu.scaling_rules import AdamScale
from adaptdl_tpu.trainer import ElasticTrainer


def _lm_setup(seed=0):
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=16, dtype=jnp.float32, remat=False,
    )
    model, params = init_transformer(cfg, seq_len=8)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, size=(8, 9), dtype=np.int32)
    return model, params, {"tokens": tokens}


def _run_steps(trainer, batch_np, steps=5):
    state = trainer.init_state()
    step = trainer.train_step(8 // trainer.num_replicas, 0)
    batch = trainer.shard_batch(batch_np)
    for _ in range(steps):
        state, m = step(state, batch)
    return state, m


@pytest.mark.parametrize(
    "optimizer,rule,precond",
    [
        (optax.adamw(1e-2), AdamScale(), "adam"),
        (optax.sgd(0.05, momentum=0.9), None, None),
    ],
)
def test_zero1_matches_replicated(optimizer, rule, precond):
    """5 steps on a data=4 mesh: sharded-moment trainer reproduces
    the replicated trainer's parameters and GNS statistics."""
    model, params, batch_np = _lm_setup()
    loss = lm_loss_fn(model)
    mesh = create_mesh({"data": 4}, devices=jax.devices()[:4])

    results = []
    for zero1 in (False, True):
        trainer = ElasticTrainer(
            loss, params, optimizer, 8, scaling_rule=rule,
            mesh=mesh, precondition=precond, zero1=zero1,
        )
        results.append(_run_steps(trainer, batch_np))
    (s_ref, m_ref), (s_z, m_z) = results
    for ref, z in zip(
        jax.tree.leaves(s_ref.params), jax.tree.leaves(s_z.params)
    ):
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(ref), rtol=2e-5, atol=2e-6
        )
    for key in ("loss", "gain", "grad_sqr", "grad_var", "lr_factor"):
        assert float(m_z[key]) == pytest.approx(
            float(m_ref[key]), rel=1e-4
        ), key


def test_zero1_param_groups_match():
    """Per-group LR factors apply to the right flat positions: a
    2-group model under zero1 matches the replicated run."""
    model, params, batch_np = _lm_setup(seed=3)
    loss = lm_loss_fn(model)
    mesh = create_mesh({"data": 4}, devices=jax.devices()[:4])

    def group_fn(path, leaf):
        # Embedding table in its own group, everything else group 1.
        return 0 if any(
            getattr(p, "key", None) == "embed" for p in path
        ) else 1

    results = []
    for zero1 in (False, True):
        trainer = ElasticTrainer(
            loss, params, optax.adamw(1e-2), 8,
            scaling_rule=AdamScale(), mesh=mesh,
            param_group_fn=group_fn, zero1=zero1,
        )
        results.append(_run_steps(trainer, batch_np))
    (s_ref, _), (s_z, _) = results
    for ref, z in zip(
        jax.tree.leaves(s_ref.params), jax.tree.leaves(s_z.params)
    ):
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(ref), rtol=2e-5, atol=2e-6
        )


def test_zero1_moments_are_sharded():
    """The Adam moment leaves really are [dp, shard] rows sharded over
    the data axis — the memory claim, structurally."""
    model, params, batch_np = _lm_setup()
    mesh = create_mesh({"data": 4}, devices=jax.devices()[:4])
    trainer = ElasticTrainer(
        lm_loss_fn(model), params, optax.adamw(1e-2), 8,
        mesh=mesh, zero1=True,
    )
    state, _ = _run_steps(trainer, batch_np, steps=1)
    mu_like = [
        leaf
        for leaf in jax.tree.leaves(state.opt_state)
        if getattr(leaf, "ndim", 0) == 2
    ]
    assert mu_like, "expected flat [dp, shard] moment leaves"
    n = sum(
        int(np.size(leaf)) for leaf in jax.tree.leaves(params)
    )
    for leaf in mu_like:
        assert leaf.shape[0] == 4
        assert leaf.shape[0] * leaf.shape[1] >= n
        # One distinct shard per device, not a replicated copy.
        assert len(leaf.sharding.device_set) == 4
        shard_shapes = {
            s.data.shape for s in leaf.addressable_shards
        }
        assert shard_shapes == {(1, leaf.shape[1])}


def test_zero1_rescale_across_replica_counts(tmp_path, monkeypatch):
    """Save under dp=4, restore under dp=2: moments round-trip through
    the canonical flat layout and training continues bit-identically
    with the replicated-trainer reference."""
    from adaptdl_tpu import checkpoint as ckpt_mod

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    model, params, batch_np = _lm_setup(seed=5)
    loss = lm_loss_fn(model)

    mesh4 = create_mesh({"data": 4}, devices=jax.devices()[:4])
    tr4 = ElasticTrainer(
        loss, params, optax.adamw(1e-2), 8,
        scaling_rule=AdamScale(), mesh=mesh4, zero1=True,
    )
    holder = {"state": tr4.init_state()}
    ck = tr4.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
        name="zero1-rescale",
    )
    step4 = tr4.train_step(2, 0)
    batch4 = tr4.shard_batch(batch_np)
    for _ in range(3):
        holder["state"], _ = step4(holder["state"], batch4)
    ckpt_mod.save_all_states()
    ck.unregister()

    # Restore at dp=2 and take 2 more steps.
    mesh2 = create_mesh({"data": 2}, devices=jax.devices()[:2])
    tr2 = ElasticTrainer(
        loss, params, optax.adamw(1e-2), 8,
        scaling_rule=AdamScale(), mesh=mesh2, zero1=True,
    )
    holder2 = {"state": tr2.init_state()}
    ck2 = tr2.make_checkpoint_state(
        lambda: holder2["state"],
        lambda s: holder2.__setitem__("state", s),
        name="zero1-rescale",
    )
    ckpt_mod.load_state(ck2)
    assert int(holder2["state"].step) == 3
    step2 = tr2.train_step(4, 0)
    batch2 = tr2.shard_batch(batch_np)
    for _ in range(2):
        holder2["state"], m2 = step2(holder2["state"], batch2)
    ck2.unregister()

    # Reference: replicated trainer, same 5 steps at dp=4 then dp=2
    # is equivalent to 5 uninterrupted steps (same global batch).
    tr_ref = ElasticTrainer(
        loss, params, optax.adamw(1e-2), 8,
        scaling_rule=AdamScale(), mesh=mesh4,
    )
    s_ref, _ = _run_steps(tr_ref, batch_np, steps=5)
    for ref, z in zip(
        jax.tree.leaves(s_ref.params),
        jax.tree.leaves(holder2["state"].params),
    ):
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(ref), rtol=5e-5, atol=5e-6
        )


def test_zero1_sharded_checkpoint_rescale(tmp_path, monkeypatch):
    """The orbax path (multi-host checkpointing): moments save in the
    canonical [n] layout on device — no host gather — and a dp=4 save
    restores into a dp=2 trainer's [dp, shard] rows."""
    from adaptdl_tpu import checkpoint as ckpt_mod
    from adaptdl_tpu.sharded_checkpoint import ShardedTrainerCheckpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    model, params, batch_np = _lm_setup(seed=9)
    loss = lm_loss_fn(model)

    mesh4 = create_mesh({"data": 4}, devices=jax.devices()[:4])
    tr4 = ElasticTrainer(
        loss, params, optax.adamw(1e-2), 8, mesh=mesh4, zero1=True
    )
    holder = {"state": tr4.init_state()}
    ck = ShardedTrainerCheckpoint(
        "zero1-orbax", tr4,
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
    )
    step4 = tr4.train_step(2, 0)
    batch4 = tr4.shard_batch(batch_np)
    for _ in range(3):
        holder["state"], _ = step4(holder["state"], batch4)
    ckpt_mod.save_all_states()
    ck.unregister()

    mesh2 = create_mesh({"data": 2}, devices=jax.devices()[:2])
    tr2 = ElasticTrainer(
        loss, params, optax.adamw(1e-2), 8, mesh=mesh2, zero1=True
    )
    holder2 = {"state": tr2.init_state()}
    ck2 = ShardedTrainerCheckpoint(
        "zero1-orbax", tr2,
        lambda: holder2["state"],
        lambda s: holder2.__setitem__("state", s),
    )
    ckpt_mod.load_state(ck2)
    ck2.unregister()
    assert int(holder2["state"].step) == 3
    # Moments landed as this trainer's [2, shard2] rows and match the
    # canonical content of the dp=4 run.
    canon4 = tr4.storage.moments_to_canonical(
        jax.tree.map(np.asarray, holder["state"].opt_state)
    )
    canon2 = tr2.storage.moments_to_canonical(
        jax.tree.map(np.asarray, holder2["state"].opt_state)
    )
    for a, b in zip(jax.tree.leaves(canon4), jax.tree.leaves(canon2)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-6, atol=0
        )
    # And training continues.
    step2 = tr2.train_step(4, 0)
    state2, m2 = step2(holder2["state"], tr2.shard_batch(batch_np))
    assert np.isfinite(float(m2["loss"]))


def test_zero1_with_sequence_parallelism():
    """zero1 composes with the seq axis: a data=2 x seq=2 mesh trains
    and matches the replicated data=2 x seq=2 run."""
    import optax as ox

    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=32, dtype=jnp.float32, remat=False,
        seq_axis="seq",
    )
    model, params = init_transformer(cfg, seq_len=16)
    rng = np.random.default_rng(7)
    toks = rng.integers(0, 64, size=(8, 17), dtype=np.int32)
    batch_np = {
        "inputs": toks[:, :-1].copy(),
        "targets": toks[:, 1:].copy(),
    }

    def loss_fn(p, batch, rng):
        logits = model.apply({"params": p}, batch["inputs"], train=False)
        return ox.softmax_cross_entropy_with_integer_labels(
            logits, batch["targets"]
        ).mean()

    mesh = create_mesh(
        {"data": 2, "seq": 2}, devices=jax.devices()[:4]
    )
    results = []
    for zero1 in (False, True):
        trainer = ElasticTrainer(
            loss_fn, params, ox.adamw(1e-2), 8, mesh=mesh,
            zero1=zero1,
        )
        state = trainer.init_state()
        step = trainer.train_step(4, 0)
        batch = trainer.shard_batch(batch_np)
        for _ in range(3):
            state, m = step(state, batch)
        results.append((state, m))
    (s_ref, m_ref), (s_z, m_z) = results
    assert float(m_z["loss"]) == pytest.approx(
        float(m_ref["loss"]), rel=1e-5
    )
    for ref, z in zip(
        jax.tree.leaves(s_ref.params), jax.tree.leaves(s_z.params)
    ):
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(ref), rtol=2e-5, atol=2e-6
        )


def test_zero1_rejects_sharded_param_axes():
    model, params, _ = _lm_setup()
    mesh = create_mesh(
        {"data": 2, "stage": 2}, devices=jax.devices()[:4]
    )
    with pytest.raises(ValueError, match="zero1"):
        ElasticTrainer(
            lm_loss_fn(model), params, optax.adamw(1e-2), 8,
            mesh=mesh, zero1=True,
        )
