"""ZeRO-3-lite (FSDP-style parameter storage sharding) tests: params
live as flat [dp, shard] rows, assemble in-step, and the whole run
must be indistinguishable from the replicated trainer."""

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


def _params_tree(trainer, state):
    """Materialize a zero3 state's params back to the tree layout."""
    return trainer.storage.params_to_canonical(
        jax.tree.map(np.asarray, state.params)
    )


@pytest.mark.parametrize(
    "optimizer,rule,precond",
    [
        (optax.adamw(1e-2), AdamScale(), "adam"),
        (optax.sgd(0.05, momentum=0.9), None, None),
    ],
)
def test_zero3_matches_replicated(optimizer, rule, precond):
    model, params, batch_np = _lm_setup()
    loss = lm_loss_fn(model)
    mesh = create_mesh({"data": 4}, devices=jax.devices()[:4])

    results = []
    for zero3 in (False, True):
        trainer = ElasticTrainer(
            loss, params, optimizer, 8, scaling_rule=rule,
            mesh=mesh, precondition=precond, zero3=zero3,
        )
        state = trainer.init_state()
        step = trainer.train_step(2, 0)
        batch = trainer.shard_batch(batch_np)
        for _ in range(5):
            state, m = step(state, batch)
        results.append((_params_tree(trainer, state), m))
    (p_ref, m_ref), (p_z, m_z) = results
    for ref, z in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_z)):
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(ref), rtol=2e-5, atol=2e-6
        )
    for key in ("loss", "gain", "grad_sqr", "grad_var", "lr_factor"):
        assert float(m_z[key]) == pytest.approx(
            float(m_ref[key]), rel=1e-4
        ), key


def test_zero3_params_and_moments_are_sharded():
    """Both the params and the Adam moments really live as one
    distinct [1, shard] row per device."""
    model, params, batch_np = _lm_setup()
    mesh = create_mesh({"data": 4}, devices=jax.devices()[:4])
    trainer = ElasticTrainer(
        lm_loss_fn(model), params, optax.adamw(1e-2), 8,
        mesh=mesh, zero3=True,
    )
    state = trainer.init_state()
    step = trainer.train_step(2, 0)
    state, _ = step(state, trainer.shard_batch(batch_np))
    rows_leaves = [state.params] + [
        leaf
        for leaf in jax.tree.leaves(state.opt_state)
        if getattr(leaf, "ndim", 0) == 2
    ]
    assert len(rows_leaves) >= 3  # params + mu + nu
    for leaf in rows_leaves:
        assert leaf.shape[0] == 4
        shard_shapes = {s.data.shape for s in leaf.addressable_shards}
        assert shard_shapes == {(1, leaf.shape[1])}


def test_zero3_rescale_across_replica_counts(tmp_path, monkeypatch):
    """dp=4 save -> dp=2 restore through the canonical tree/flat
    layouts; the continued run matches an uninterrupted replicated
    run."""
    from adaptdl_tpu import checkpoint as ckpt_mod

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    model, params, batch_np = _lm_setup(seed=5)
    loss = lm_loss_fn(model)

    mesh4 = create_mesh({"data": 4}, devices=jax.devices()[:4])
    tr4 = ElasticTrainer(
        loss, params, optax.adamw(1e-2), 8,
        scaling_rule=AdamScale(), mesh=mesh4, zero3=True,
    )
    holder = {"state": tr4.init_state()}
    ck = tr4.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
        name="zero3-rescale",
    )
    step4 = tr4.train_step(2, 0)
    batch4 = tr4.shard_batch(batch_np)
    for _ in range(3):
        holder["state"], _ = step4(holder["state"], batch4)
    ckpt_mod.save_all_states()
    ck.unregister()

    mesh2 = create_mesh({"data": 2}, devices=jax.devices()[:2])
    tr2 = ElasticTrainer(
        loss, params, optax.adamw(1e-2), 8,
        scaling_rule=AdamScale(), mesh=mesh2, zero3=True,
    )
    holder2 = {"state": tr2.init_state()}
    ck2 = tr2.make_checkpoint_state(
        lambda: holder2["state"],
        lambda s: holder2.__setitem__("state", s),
        name="zero3-rescale",
    )
    ckpt_mod.load_state(ck2)
    assert int(holder2["state"].step) == 3
    step2 = tr2.train_step(4, 0)
    batch2 = tr2.shard_batch(batch_np)
    for _ in range(2):
        holder2["state"], _ = step2(holder2["state"], batch2)
    ck2.unregister()

    tr_ref = ElasticTrainer(
        loss, params, optax.adamw(1e-2), 8,
        scaling_rule=AdamScale(), mesh=mesh4,
    )
    s_ref = tr_ref.init_state()
    step_ref = tr_ref.train_step(2, 0)
    batch_ref = tr_ref.shard_batch(batch_np)
    for _ in range(5):
        s_ref, _ = step_ref(s_ref, batch_ref)
    p_z = _params_tree(tr2, holder2["state"])
    for ref, z in zip(
        jax.tree.leaves(s_ref.params), jax.tree.leaves(p_z)
    ):
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(ref), rtol=5e-5, atol=5e-6
        )


def test_zero3_sharded_checkpoint_rescale(tmp_path, monkeypatch):
    """The orbax path: params write as the canonical (replicated)
    tree, moments as canonical flat vectors; a dp=4 save restores
    into a dp=2 trainer's rows."""
    from adaptdl_tpu import checkpoint as ckpt_mod
    from adaptdl_tpu.sharded_checkpoint import ShardedTrainerCheckpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    model, params, batch_np = _lm_setup(seed=9)
    loss = lm_loss_fn(model)

    mesh4 = create_mesh({"data": 4}, devices=jax.devices()[:4])
    tr4 = ElasticTrainer(
        loss, params, optax.adamw(1e-2), 8, mesh=mesh4, zero3=True
    )
    holder = {"state": tr4.init_state()}
    ck = ShardedTrainerCheckpoint(
        "zero3-orbax", tr4,
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
        loss, params, optax.adamw(1e-2), 8, mesh=mesh2, zero3=True
    )
    holder2 = {"state": tr2.init_state()}
    ck2 = ShardedTrainerCheckpoint(
        "zero3-orbax", tr2,
        lambda: holder2["state"],
        lambda s: holder2.__setitem__("state", s),
    )
    ckpt_mod.load_state(ck2)
    ck2.unregister()
    assert int(holder2["state"].step) == 3
    for a, b in zip(
        jax.tree.leaves(_params_tree(tr4, holder["state"])),
        jax.tree.leaves(_params_tree(tr2, holder2["state"])),
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-6, atol=0
        )
    step2 = tr2.train_step(4, 0)
    state2, m2 = step2(holder2["state"], tr2.shard_batch(batch_np))
    assert np.isfinite(float(m2["loss"]))


def test_zero3_with_sequence_parallelism():
    """zero3 composes with the seq axis (data=2 x seq=2) and matches
    the replicated run."""
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
    for zero3 in (False, True):
        trainer = ElasticTrainer(
            loss_fn, params, ox.adamw(1e-2), 8, mesh=mesh,
            zero3=zero3,
        )
        state = trainer.init_state()
        step = trainer.train_step(4, 0)
        batch = trainer.shard_batch(batch_np)
        for _ in range(3):
            state, m = step(state, batch)
        results.append((_params_tree(trainer, state), m))
    (p_ref, m_ref), (p_z, m_z) = results
    assert float(m_z["loss"]) == pytest.approx(
        float(m_ref["loss"]), rel=1e-5
    )
    for ref, z in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_z)):
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(ref), rtol=2e-5, atol=2e-6
        )


def test_zero3_run_step_calibration_path(monkeypatch):
    """run_step's compute-only calibration (the profiling split) works
    with rows-layout params."""
    from adaptdl_tpu.data import AdaptiveDataLoader

    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "4")
    model, params, _ = _lm_setup(seed=11)
    rng = np.random.default_rng(11)
    data = {"tokens": rng.integers(0, 64, size=(64, 9), dtype=np.int32)}
    mesh = create_mesh({"data": 4}, devices=jax.devices()[:4])
    trainer = ElasticTrainer(
        lm_loss_fn(model), params, optax.adamw(1e-2), 8,
        mesh=mesh, zero3=True,
    )
    state = trainer.init_state()
    loader = AdaptiveDataLoader(data, batch_size=8, name="z3-loader")
    steps = 0
    for batch in loader:
        state, m = trainer.run_step(state, batch, loader)
        steps += 1
        if steps >= 2:
            break
    assert np.isfinite(float(m["loss"]))


def test_params_tree_and_eval_step():
    """params_tree returns the user-facing tree under any layout, and
    eval_step produces identical totals for dense and zero3 trainers
    (and under seq sharding)."""
    import optax as ox

    model, params, batch_np = _lm_setup(seed=13)
    mesh = create_mesh({"data": 4}, devices=jax.devices()[:4])

    def metric_fn(p, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits = model.apply({"params": p}, inputs, train=False)
        correct = (logits.argmax(-1) == targets).sum()
        return {"correct": correct, "seen": jnp.asarray(targets.size)}

    totals = []
    for zero3 in (False, True):
        trainer = ElasticTrainer(
            lm_loss_fn(model), params, ox.adamw(1e-2), 8,
            mesh=mesh, zero3=zero3,
        )
        state = trainer.init_state()
        step = trainer.train_step(2, 0)
        batch = trainer.shard_batch(batch_np)
        for _ in range(2):
            state, _ = step(state, batch)
        # params_tree matches the init tree's structure either way.
        tree = trainer.params_tree(state)
        assert jax.tree_util.tree_structure(
            tree
        ) == jax.tree_util.tree_structure(params)
        ev = trainer.eval_step(metric_fn)
        out = ev(state, batch)
        totals.append(
            (int(out["correct"]), int(out["seen"]))
        )
    assert totals[0] == totals[1]
    assert totals[0][1] == 8 * 8  # rows x positions
