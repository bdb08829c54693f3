"""Tensor parallelism: TP-sharded training matches unsharded training."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from adaptdl_tpu.models import TransformerConfig, init_transformer
from adaptdl_tpu.parallel import create_mesh
from adaptdl_tpu.parallel.tensor_parallel import transformer_tp_specs
from adaptdl_tpu.trainer import ElasticTrainer


def _loss_fn(model):
    def loss_fn(params, batch, rng):
        logits = model.apply(
            {"params": params}, batch["inputs"], train=False
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["targets"]
        ).mean()

    return loss_fn


def test_tp_specs_cover_transformer():
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, remat=False,
    )
    _, params = init_transformer(cfg, seq_len=16)
    specs = jax.tree_util.tree_map_with_path(
        transformer_tp_specs, params
    )
    flat = jax.tree_util.tree_leaves_with_path(specs, is_leaf=lambda x: isinstance(x, P))
    sharded = [p for p, s in flat if s != P()]
    names = {"/".join(str(getattr(k, "key", k)) for k in p) for p in sharded}
    assert any("qkv" in n for n in names)
    assert any("ff_up" in n for n in names)
    assert any("ff_down" in n for n in names)
    assert any("out" in n for n in names)


def test_tp_training_matches_replicated():
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, remat=False,
    )
    model, params = init_transformer(cfg, seq_len=16)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, size=(8, 17), dtype=np.int32)
    batch_np = {
        "inputs": tokens[:, :-1].copy(),
        "targets": tokens[:, 1:].copy(),
    }

    def run(mesh, sharding_fn):
        tr = ElasticTrainer(
            _loss_fn(model),
            params,
            optax.adam(1e-2),
            8,
            mesh=mesh,
            param_sharding_fn=sharding_fn,
        )
        state = tr.init_state()
        step = tr.train_step(4, 0)
        for _ in range(3):
            state, m = step(state, tr.shard_batch(batch_np))
        return state, m

    mesh_dp = create_mesh({"data": 2}, devices=jax.devices()[:2])
    s_dp, m_dp = run(mesh_dp, None)

    mesh_tp = create_mesh(
        {"data": 2, "model": 2}, devices=jax.devices()[:4]
    )
    s_tp, m_tp = run(mesh_tp, transformer_tp_specs)

    assert float(m_tp["loss"]) == pytest.approx(
        float(m_dp["loss"]), rel=2e-4
    )
    assert float(m_tp["grad_var"]) == pytest.approx(
        float(m_dp["grad_var"]), rel=1e-2, abs=1e-6
    )
    w_dp = np.asarray(s_dp.params["layer_0"]["ff_up"]["kernel"])
    w_tp = np.asarray(
        jax.device_get(s_tp.params["layer_0"]["ff_up"]["kernel"])
    )
    np.testing.assert_allclose(w_tp, w_dp, atol=2e-4)
    # The TP run's params really are sharded over the model axis.
    spec = s_tp.params["layer_0"]["ff_up"]["kernel"].sharding.spec
    assert "model" in str(spec)


def test_trainer_checkpoint_restores_tp_sharded(tmp_path, monkeypatch):
    """TrainerCheckpoint.load honors param_sharding_fn: params, their
    optimizer moments, and the GNS prev-grad all come back laid out
    over the model axis — never replicated (which would OOM a model
    that only fits sharded)."""
    from adaptdl_tpu import checkpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, remat=False,
    )
    model, params = init_transformer(cfg, seq_len=16)
    mesh = create_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])
    tr = ElasticTrainer(
        _loss_fn(model),
        params,
        optax.adam(1e-2),
        8,
        mesh=mesh,
        param_sharding_fn=transformer_tp_specs,
    )
    holder = {"state": tr.init_state()}
    ck = tr.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
        name="tp_trainer",
    )
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, size=(8, 17), dtype=np.int32)
    batch = {
        "inputs": tokens[:, :-1].copy(),
        "targets": tokens[:, 1:].copy(),
    }
    step = tr.train_step(4, 0)
    holder["state"], _ = step(holder["state"], tr.shard_batch(batch))
    w_before = np.asarray(
        jax.device_get(holder["state"].params["layer_0"]["ff_up"]["kernel"])
    )
    checkpoint.save_all_states()

    holder["state"] = None
    assert checkpoint.load_state(ck)
    restored = holder["state"]

    def spec_of(leaf):
        return str(leaf.sharding.spec)

    assert "model" in spec_of(
        restored.params["layer_0"]["ff_up"]["kernel"]
    )
    # Adam moments mirror the params' TP layout (matched by path
    # suffix through state_spec_tree).
    mu = restored.opt_state[0].mu["layer_0"]["ff_up"]["kernel"]
    nu = restored.opt_state[0].nu["layer_0"]["ff_up"]["kernel"]
    assert "model" in spec_of(mu) and "model" in spec_of(nu)
    assert "model" in spec_of(
        restored.gns.prev_grad["layer_0"]["ff_up"]["kernel"]
    )
    # Scalars stay replicated and values round-trip exactly.
    assert spec_of(restored.progress) == "PartitionSpec()"
    np.testing.assert_allclose(
        np.asarray(
            jax.device_get(restored.params["layer_0"]["ff_up"]["kernel"])
        ),
        w_before,
    )
    # Training continues from the restored sharded state.
    s2, m = step(restored, tr.shard_batch(batch))
    assert np.isfinite(float(m["loss"]))
