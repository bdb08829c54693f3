"""Pipeline parallelism: the GPipe schedule matches sequential layer
application, and a dp x stage ElasticTrainer run matches a pure-DP run
on the same model (gradients, GNS statistics, losses)."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from adaptdl_tpu.parallel import create_mesh
from adaptdl_tpu.parallel.mesh import STAGE_AXIS
from adaptdl_tpu.parallel.pipeline import (
    gpipe,
    gpipe_loss,
    stack_stage_params,
)
from adaptdl_tpu.trainer import ElasticTrainer

try:
    shard_map = jax.shard_map
except AttributeError:  # pragma: no cover
    from jax.experimental.shard_map import shard_map

D = 8


def _stage_fn(params_local, x):
    # params leaves carry the leading stage axis (size 1 locally).
    w = params_local["w"][0]
    b = params_local["b"][0]
    return jax.nn.relu(x @ w + b)


def _make_stage_params(rng, num_stages):
    per_stage = [
        {
            "w": jnp.asarray(
                rng.normal(size=(D, D)).astype(np.float32) * 0.5
            ),
            "b": jnp.asarray(rng.normal(size=D).astype(np.float32) * 0.1),
        }
        for _ in range(num_stages)
    ]
    return per_stage, stack_stage_params(per_stage)


def _sequential(per_stage, x):
    for stage in per_stage:
        x = jax.nn.relu(x @ stage["w"] + stage["b"])
    return x


@pytest.mark.parametrize("num_stages,num_micro", [(2, 2), (4, 3)])
def test_gpipe_matches_sequential(num_stages, num_micro):
    rng = np.random.default_rng(0)
    per_stage, stacked = _make_stage_params(rng, num_stages)
    x = jnp.asarray(
        rng.normal(size=(num_micro, 4, D)).astype(np.float32)
    )
    mesh = create_mesh(
        {STAGE_AXIS: num_stages}, devices=jax.devices()[:num_stages]
    )

    def run(params, micro):
        outs = gpipe(_stage_fn, params, micro)
        stage = jax.lax.axis_index(STAGE_AXIS)
        # Broadcast the last stage's (only valid) output to everyone.
        return jax.lax.psum(
            jnp.where(stage == num_stages - 1, outs, 0.0), STAGE_AXIS
        )

    piped = shard_map(
        run,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(STAGE_AXIS), stacked),
            P(),
        ),
        out_specs=P(),
    )(stacked, x)
    want = _sequential(per_stage, x.reshape(-1, D)).reshape(piped.shape)
    np.testing.assert_allclose(
        np.asarray(piped), np.asarray(want), atol=1e-5, rtol=1e-5
    )


def test_trainer_dp_x_stage_matches_pure_dp():
    """The whole elastic step over a dp x stage mesh — stage-sharded
    params, GPipe forward, stage-summed GNS statistics — reproduces
    the pure-DP run of the same network."""
    rng = np.random.default_rng(1)
    per_stage, stacked = _make_stage_params(rng, 2)
    data = {
        "x": rng.normal(size=(64, D)).astype(np.float32),
        "y": rng.normal(size=64).astype(np.float32),
    }

    def loss_head(final, batch):
        return jnp.mean((final.sum(axis=-1) - batch["y"]) ** 2)

    # Pipelined: dp=2 x stage=2 over 4 devices.
    pp_trainer = ElasticTrainer(
        gpipe_loss(_stage_fn, loss_head, num_micro=2),
        stacked,
        optax.sgd(0.05),
        16,
        mesh=create_mesh(
            {"data": 2, STAGE_AXIS: 2}, devices=jax.devices()[:4]
        ),
        param_sharding_fn=lambda path, leaf: P(STAGE_AXIS),
    )
    pp_state = pp_trainer.init_state()
    pp_step = pp_trainer.train_step(8, 0)

    # Reference: dp=2 applying the stages sequentially.
    def dp_loss(params, batch, rng_):
        final = _sequential(
            [jax.tree.map(lambda p: p[i], params) for i in range(2)],
            batch["x"],
        )
        return loss_head(final, batch)

    dp_trainer = ElasticTrainer(
        dp_loss,
        stacked,
        optax.sgd(0.05),
        16,
        mesh=create_mesh({"data": 2}, devices=jax.devices()[:2]),
    )
    dp_state = dp_trainer.init_state()
    dp_step = dp_trainer.train_step(8, 0)

    for step_idx in range(4):
        idx = rng.integers(0, 64, size=16)
        batch = {k: v[idx] for k, v in data.items()}
        pp_state, pp_m = pp_step(pp_state, pp_trainer.shard_batch(batch))
        dp_state, dp_m = dp_step(dp_state, dp_trainer.shard_batch(batch))
        assert float(pp_m["loss"]) == pytest.approx(
            float(dp_m["loss"]), rel=1e-4
        ), step_idx
        assert float(pp_m["grad_sqr"]) == pytest.approx(
            float(dp_m["grad_sqr"]), rel=1e-3, abs=1e-8
        )
        assert float(pp_m["grad_var"]) == pytest.approx(
            float(dp_m["grad_var"]), rel=1e-3, abs=1e-8
        )
    # Parameters evolved identically (gather the stage shards).
    pp_w = np.asarray(jax.device_get(pp_state.params["w"]))
    dp_w = np.asarray(jax.device_get(dp_state.params["w"]))
    np.testing.assert_allclose(pp_w, dp_w, atol=1e-5)
    # And the pipelined params really are stage-sharded.
    assert "stage" in str(pp_state.params["w"].sharding.spec)


def test_trainer_stage_with_accumulation():
    """Pipeline microbatching composes with the trainer's gradient
    accumulation (scan of GPipe schedules)."""
    rng = np.random.default_rng(2)
    _, stacked = _make_stage_params(rng, 2)
    data = {
        "x": rng.normal(size=(64, D)).astype(np.float32),
        "y": rng.normal(size=64).astype(np.float32),
    }

    def loss_head(final, batch):
        return jnp.mean((final.sum(axis=-1) - batch["y"]) ** 2)

    trainer = ElasticTrainer(
        gpipe_loss(_stage_fn, loss_head, num_micro=2),
        stacked,
        optax.sgd(0.05),
        16,
        mesh=create_mesh(
            {"data": 2, STAGE_AXIS: 2}, devices=jax.devices()[:4]
        ),
        param_sharding_fn=lambda path, leaf: P(STAGE_AXIS),
    )
    state = trainer.init_state()
    step = trainer.train_step(4, 1)  # 2 accumulation microbatches
    losses = []
    for _ in range(5):
        idx = rng.integers(0, 64, size=16)
        state, m = step(
            state,
            trainer.shard_batch({k: v[idx] for k, v in data.items()}),
        )
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


# ---- interleaved (circular) schedule ------------------------------------


def _chunk_fn(chunk_params, x):
    return jax.nn.relu(x @ chunk_params["w"] + chunk_params["b"])


def _make_chunk_params(rng, num_chunks):
    chunks = [
        {
            "w": jnp.asarray(
                rng.normal(size=(D, D)).astype(np.float32) * 0.5
            ),
            "b": jnp.asarray(
                rng.normal(size=D).astype(np.float32) * 0.1
            ),
        }
        for _ in range(num_chunks)
    ]
    return chunks


def _sequential_chunks(chunks, x):
    for c in chunks:
        x = jax.nn.relu(x @ c["w"] + c["b"])
    return x


@pytest.mark.parametrize(
    "num_stages,v,num_micro", [(2, 2, 2), (2, 3, 4), (4, 2, 5)]
)
def test_interleaved_matches_sequential(num_stages, v, num_micro):
    from adaptdl_tpu.parallel.pipeline import (
        interleaved_pipeline,
        stack_interleaved_params,
    )

    rng = np.random.default_rng(2)
    chunks = _make_chunk_params(rng, num_stages * v)
    stacked = stack_interleaved_params(chunks, num_stages)
    x = jnp.asarray(
        rng.normal(size=(num_micro, 4, D)).astype(np.float32)
    )
    mesh = create_mesh(
        {STAGE_AXIS: num_stages}, devices=jax.devices()[:num_stages]
    )

    def run(params_local, micro):
        # leaves arrive [1, v, ...]; drop the sharded stage axis.
        local = jax.tree.map(lambda leaf: leaf[0], params_local)
        outs = interleaved_pipeline(_chunk_fn, local, micro)
        stage = jax.lax.axis_index(STAGE_AXIS)
        return jax.lax.psum(
            jnp.where(stage == num_stages - 1, outs, 0.0), STAGE_AXIS
        )

    piped = shard_map(
        run,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(STAGE_AXIS), stacked),
            P(),
        ),
        out_specs=P(),
    )(stacked, x)
    want = _sequential_chunks(chunks, x.reshape(-1, D)).reshape(
        piped.shape
    )
    np.testing.assert_allclose(
        np.asarray(piped), np.asarray(want), atol=1e-5, rtol=1e-5
    )


def test_interleaved_trainer_matches_pure_dp():
    """dp x stage with the interleaved schedule (v=2) reproduces the
    pure-DP evolution of the same 4-chunk network."""
    from adaptdl_tpu.parallel.pipeline import (
        interleaved_loss,
        stack_interleaved_params,
    )

    rng = np.random.default_rng(3)
    chunks = _make_chunk_params(rng, 4)  # S=2, v=2
    stacked = stack_interleaved_params(chunks, 2)
    data = {
        "x": rng.normal(size=(64, D)).astype(np.float32),
        "y": rng.normal(size=64).astype(np.float32),
    }

    def loss_head(final, batch):
        return jnp.mean((final.sum(axis=-1) - batch["y"]) ** 2)

    pp_trainer = ElasticTrainer(
        interleaved_loss(_chunk_fn, loss_head, num_micro=2),
        stacked,
        optax.sgd(0.05),
        16,
        mesh=create_mesh(
            {"data": 2, STAGE_AXIS: 2}, devices=jax.devices()[:4]
        ),
        param_sharding_fn=lambda path, leaf: P(STAGE_AXIS),
    )
    pp_state = pp_trainer.init_state()
    pp_step = pp_trainer.train_step(8, 0)

    def dp_loss(params, batch, rng_):
        # params leaves [S=2, v=2, ...] in global order g = k*S + d.
        flat = [
            jax.tree.map(lambda p: p[d, k], params)
            for k in range(2)
            for d in range(2)
        ]
        return loss_head(_sequential_chunks(flat, batch["x"]), batch)

    dp_trainer = ElasticTrainer(
        dp_loss,
        stacked,
        optax.sgd(0.05),
        16,
        mesh=create_mesh({"data": 2}, devices=jax.devices()[:2]),
    )
    dp_state = dp_trainer.init_state()
    dp_step = dp_trainer.train_step(8, 0)

    for step_idx in range(4):
        idx = rng.integers(0, 64, size=16)
        batch = {k: v[idx] for k, v in data.items()}
        pp_state, pp_m = pp_step(pp_state, pp_trainer.shard_batch(batch))
        dp_state, dp_m = dp_step(dp_state, dp_trainer.shard_batch(batch))
        assert float(pp_m["loss"]) == pytest.approx(
            float(dp_m["loss"]), rel=1e-4
        ), step_idx
    pp_w = np.asarray(jax.device_get(pp_state.params["w"]))
    dp_w = np.asarray(jax.device_get(dp_state.params["w"]))
    np.testing.assert_allclose(pp_w, dp_w, atol=1e-5)


# ---- pipelined transformer LM -------------------------------------------


@pytest.mark.parametrize("interleave", [1, 2])
def test_pipeline_lm_matches_sequential_dp(interleave):
    """The staged transformer (GPipe and interleaved) reproduces the
    sequential run of the same params under pure DP: losses and the
    evolved block/embed params match."""
    import optax

    from adaptdl_tpu.models import TransformerConfig
    from adaptdl_tpu.models.pipeline_lm import (
        init_pipeline_lm,
        pipeline_lm_sharding_fn,
    )

    cfg = TransformerConfig(
        vocab_size=64,
        num_layers=4,
        num_heads=2,
        d_model=16,
        d_ff=32,
        max_seq_len=8,
        dtype=jnp.float32,
        remat=False,
    )
    num_micro = 2
    loss_fn, params = init_pipeline_lm(
        cfg, num_stages=2, num_micro=num_micro,
        interleave=interleave, seq_len=8,
    )
    pp_trainer = ElasticTrainer(
        loss_fn,
        params,
        optax.sgd(0.05),
        8,
        mesh=create_mesh(
            {"data": 2, STAGE_AXIS: 2}, devices=jax.devices()[:4]
        ),
        param_sharding_fn=pipeline_lm_sharding_fn,
    )
    pp_state = pp_trainer.init_state()
    pp_step = pp_trainer.train_step(4, 0)

    # Sequential reference over the same param tree, pure DP.
    import flax.linen as nn
    from adaptdl_tpu.models.transformer import Block

    block = Block(cfg)
    embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype)
    ln_f = nn.LayerNorm(dtype=cfg.dtype, use_bias=False)

    def seq_loss(params, batch, rng_):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = embed.apply({"params": params["embed"]}, inputs)
        positions = jnp.arange(x.shape[1])
        # blocks leaves: [S, (v,) lpc, ...] in device-major order;
        # global chunk g = k*S + d lives at [d, k].
        leaves_shape = jax.tree.leaves(params["blocks"])[0].shape
        v = leaves_shape[1] if interleave > 1 else 1
        lpc = leaves_shape[2] if interleave > 1 else leaves_shape[1]
        for k in range(v):
            for d in range(2):
                for i in range(lpc):
                    if interleave > 1:
                        layer = jax.tree.map(
                            lambda p: p[d, k, i], params["blocks"]
                        )
                    else:
                        layer = jax.tree.map(
                            lambda p: p[d, i], params["blocks"]
                        )
                    x = block.apply(
                        {"params": layer}, x, positions
                    )
        h = ln_f.apply({"params": params["ln_f"]}, x)
        logits = embed.apply(
            {"params": params["embed"]}, h, method="attend"
        ).astype(jnp.float32)
        import optax as _optax

        return _optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()

    dp_trainer = ElasticTrainer(
        seq_loss,
        params,
        optax.sgd(0.05),
        8,
        mesh=create_mesh({"data": 2}, devices=jax.devices()[:2]),
    )
    dp_state = dp_trainer.init_state()
    dp_step = dp_trainer.train_step(4, 0)

    rng = np.random.default_rng(7)
    for step_idx in range(3):
        tokens = rng.integers(0, 64, size=(8, 9), dtype=np.int32)
        batch = {"tokens": tokens}
        pp_state, pp_m = pp_step(
            pp_state, pp_trainer.shard_batch(batch)
        )
        dp_state, dp_m = dp_step(
            dp_state, dp_trainer.shard_batch(batch)
        )
        assert float(pp_m["loss"]) == pytest.approx(
            float(dp_m["loss"]), rel=1e-4
        ), (interleave, step_idx)
    pp_leaf = np.asarray(
        jax.device_get(jax.tree.leaves(pp_state.params["blocks"])[0])
    )
    dp_leaf = np.asarray(
        jax.device_get(jax.tree.leaves(dp_state.params["blocks"])[0])
    )
    np.testing.assert_allclose(pp_leaf, dp_leaf, atol=2e-5)
    pp_emb = np.asarray(
        jax.device_get(pp_state.params["embed"]["embedding"])
    )
    dp_emb = np.asarray(
        jax.device_get(dp_state.params["embed"]["embedding"])
    )
    np.testing.assert_allclose(pp_emb, dp_emb, atol=2e-5)


def test_pipeline_lm_rescales_across_stage_topologies(tmp_path, monkeypatch):
    """A checkpoint written under (S=2, GPipe) restores into a
    (S=2, interleaved v=2) incarnation — the structure-changing
    rescale: block weights AND adam moments restack layer-major on
    disk and re-stack for the new schedule on load."""
    import optax

    from adaptdl_tpu import checkpoint as ckpt_mod
    from adaptdl_tpu.models import TransformerConfig
    from adaptdl_tpu.models.pipeline_lm import (
        init_pipeline_lm,
        pipeline_checkpoint_transforms,
        pipeline_lm_sharding_fn,
    )

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    cfg = TransformerConfig(
        vocab_size=64, num_layers=4, num_heads=2, d_model=16,
        d_ff=32, max_seq_len=8, dtype=jnp.float32, remat=False,
    )
    rng = np.random.default_rng(12)
    tokens = rng.integers(0, 64, size=(8, 9), dtype=np.int32)

    def build(interleave):
        loss_fn, params = init_pipeline_lm(
            cfg, num_stages=2, num_micro=2,
            interleave=interleave, seq_len=8,
        )
        trainer = ElasticTrainer(
            loss_fn, params, optax.adam(1e-3), 8,
            mesh=create_mesh(
                {"data": 2, STAGE_AXIS: 2}, devices=jax.devices()[:4]
            ),
            param_sharding_fn=pipeline_lm_sharding_fn,
        )
        save_t, load_t = pipeline_checkpoint_transforms(
            2, interleave
        )
        return trainer, save_t, load_t

    # Incarnation 0: GPipe (v=1), two steps, save.
    t0, save0, load0 = build(1)
    holder = {"state": t0.init_state()}
    ck0 = t0.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
        transform_save=save0, transform_load=load0,
    )
    step0 = t0.train_step(4, 0)
    for _ in range(2):
        holder["state"], m0 = step0(
            holder["state"], t0.shard_batch({"tokens": tokens})
        )
    ckpt_mod.save_all_states()
    ck0.unregister()
    saved_state_v1 = holder["state"]
    blocks_v1 = jax.device_get(saved_state_v1.params["blocks"])

    # Incarnation 1: interleaved v=2 — different leaf shapes.
    t1, save1, load1 = build(2)
    holder1 = {"state": t1.init_state()}
    ck1 = t1.make_checkpoint_state(
        lambda: holder1["state"],
        lambda s: holder1.__setitem__("state", s),
        transform_save=save1, transform_load=load1,
    )
    assert ckpt_mod.load_state(ck1)
    assert int(holder1["state"].step) == 2
    # Same layers, new stacking: compare via the layer-major
    # canonicalization of both layouts.
    from adaptdl_tpu.models.pipeline_lm import _to_layer_major

    flat_v1 = jax.tree.map(
        lambda leaf: _to_layer_major(np.asarray(leaf), 2, 1),
        blocks_v1,
    )
    flat_v2 = jax.tree.map(
        lambda leaf: _to_layer_major(
            np.asarray(jax.device_get(leaf)), 2, 2
        ),
        holder1["state"].params["blocks"],
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
        flat_v1, flat_v2,
    )
    # Adam moments restacked too: the v=2 incarnation's canonical mu
    # equals the saved v=1 incarnation's canonical mu.
    def blocks_mu(state):
        for node in jax.tree.leaves(
            state.opt_state, is_leaf=lambda n: isinstance(n, dict)
        ):
            if isinstance(node, dict) and "blocks" in node:
                return node["blocks"]
        raise AssertionError("no params-shaped mu found in opt_state")

    mu_v1 = jax.tree.map(
        lambda leaf: _to_layer_major(
            np.asarray(jax.device_get(leaf)), 2, 1
        ),
        blocks_mu(saved_state_v1),
    )
    mu_v2 = jax.tree.map(
        lambda leaf: _to_layer_major(
            np.asarray(jax.device_get(leaf)), 2, 2
        ),
        blocks_mu(holder1["state"]),
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
        mu_v1, mu_v2,
    )
    # And the restored job keeps training under the new schedule.
    step1 = t1.train_step(4, 0)
    holder1["state"], m1 = step1(
        holder1["state"], t1.shard_batch({"tokens": tokens})
    )
    assert np.isfinite(float(m1["loss"]))
    assert int(holder1["state"].step) == 3
    ck1.unregister()


def test_dense_and_pipelined_share_canonical_checkpoints(
    tmp_path, monkeypatch
):
    """Structure-changing rescale both directions: a plain (ss=1)
    TransformerLM checkpoint restores into a pipelined (ss=2)
    incarnation and vice versa — same canonical layer-major disk
    layout from both builds."""
    import optax

    from adaptdl_tpu import checkpoint as ckpt_mod
    from adaptdl_tpu.models import (
        TransformerConfig,
        init_transformer,
        lm_loss_fn,
    )
    from adaptdl_tpu.models.pipeline_lm import (
        _to_layer_major,
        dense_lm_checkpoint_transforms,
        init_pipeline_lm,
        pipeline_checkpoint_transforms,
        pipeline_lm_sharding_fn,
    )

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    cfg = TransformerConfig(
        vocab_size=64, num_layers=4, num_heads=2, d_model=16,
        d_ff=32, max_seq_len=8, dtype=jnp.float32, remat=False,
    )
    rng = np.random.default_rng(13)
    tokens = rng.integers(0, 64, size=(8, 9), dtype=np.int32)

    # Dense incarnation: 2 steps, save.
    model, params = init_transformer(cfg, seq_len=8)
    dense_trainer = ElasticTrainer(
        lm_loss_fn(model), params, optax.adam(1e-3), 8,
        mesh=create_mesh({"data": 2}, devices=jax.devices()[:2]),
    )
    d_save, d_load = dense_lm_checkpoint_transforms(cfg.num_layers)
    holder = {"state": dense_trainer.init_state()}
    ck = dense_trainer.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
        transform_save=d_save, transform_load=d_load,
    )
    step = dense_trainer.train_step(4, 0)
    for _ in range(2):
        holder["state"], _m = step(
            holder["state"], dense_trainer.shard_batch({"tokens": tokens})
        )
    ckpt_mod.save_all_states()
    ck.unregister()
    dense_layer0_attn = np.asarray(
        jax.device_get(
            holder["state"].params["layer_0"]["attention"]["qkv"][
                "kernel"
            ]
        )
    )

    # Pipelined incarnation (ss=2) restores the dense save.
    loss_fn, pp_params = init_pipeline_lm(
        cfg, num_stages=2, num_micro=2, interleave=1, seq_len=8
    )
    pp_trainer = ElasticTrainer(
        loss_fn, pp_params, optax.adam(1e-3), 8,
        mesh=create_mesh(
            {"data": 2, STAGE_AXIS: 2}, devices=jax.devices()[:4]
        ),
        param_sharding_fn=pipeline_lm_sharding_fn,
    )
    p_save, p_load = pipeline_checkpoint_transforms(2, 1)
    holder2 = {"state": pp_trainer.init_state()}
    ck2 = pp_trainer.make_checkpoint_state(
        lambda: holder2["state"],
        lambda s: holder2.__setitem__("state", s),
        transform_save=p_save, transform_load=p_load,
    )
    assert ckpt_mod.load_state(ck2)
    assert int(holder2["state"].step) == 2
    # Layer 0 of the canonical stack == the dense layer_0 weights.
    blocks_flat = jax.tree.map(
        lambda leaf: _to_layer_major(
            np.asarray(jax.device_get(leaf)), 2, 1
        ),
        holder2["state"].params["blocks"],
    )
    np.testing.assert_allclose(
        blocks_flat["attention"]["qkv"]["kernel"][0],
        dense_layer0_attn,
        atol=1e-6,
    )
    # The pipelined incarnation trains on, saves, and the DENSE build
    # restores that save (the reverse direction).
    pp_step = pp_trainer.train_step(4, 0)
    holder2["state"], m2 = pp_step(
        holder2["state"], pp_trainer.shard_batch({"tokens": tokens})
    )
    assert np.isfinite(float(m2["loss"]))
    ckpt_mod.save_all_states()
    ck2.unregister()

    model3, params3 = init_transformer(cfg, seq_len=8)
    dense3 = ElasticTrainer(
        lm_loss_fn(model3), params3, optax.adam(1e-3), 8,
        mesh=create_mesh({"data": 2}, devices=jax.devices()[:2]),
    )
    holder3 = {"state": dense3.init_state()}
    ck3 = dense3.make_checkpoint_state(
        lambda: holder3["state"],
        lambda s: holder3.__setitem__("state", s),
        transform_save=d_save, transform_load=d_load,
    )
    assert ckpt_mod.load_state(ck3)
    assert int(holder3["state"].step) == 3
    step3 = dense3.train_step(4, 0)
    holder3["state"], m3 = step3(
        holder3["state"], dense3.shard_batch({"tokens": tokens})
    )
    assert np.isfinite(float(m3["loss"]))
    ck3.unregister()


def test_pipeline_lm_composes_with_tensor_parallel():
    """dp x stage x model: block leaves manual on stage, GSPMD-auto on
    model — the composed run reproduces the stage-only run exactly."""
    import optax

    from adaptdl_tpu.models import TransformerConfig
    from adaptdl_tpu.models.pipeline_lm import (
        init_pipeline_lm,
        pipeline_lm_sharding_fn,
        pipeline_lm_tp_sharding_fn,
    )
    from adaptdl_tpu.parallel.mesh import MODEL_AXIS

    cfg = TransformerConfig(
        vocab_size=64, num_layers=4, num_heads=2, d_model=16,
        d_ff=32, max_seq_len=8, dtype=jnp.float32, remat=False,
    )
    loss_fn, params = init_pipeline_lm(
        cfg, num_stages=2, num_micro=2, seq_len=8
    )
    tokens = np.random.default_rng(14).integers(
        0, 64, size=(8, 9), dtype=np.int32
    )

    def run(mesh_axes, sharding_fn, n_dev):
        tr = ElasticTrainer(
            loss_fn, params, optax.adam(1e-3), 8,
            mesh=create_mesh(
                mesh_axes, devices=jax.devices()[:n_dev]
            ),
            param_sharding_fn=sharding_fn,
        )
        state = tr.init_state()
        step = tr.train_step(4, 0)
        for _ in range(2):
            state, m = step(
                state, tr.shard_batch({"tokens": tokens})
            )
        return float(m["loss"]), state

    loss_pp, _ = run(
        {"data": 2, STAGE_AXIS: 2}, pipeline_lm_sharding_fn, 4
    )
    loss_pp_tp, state_tp = run(
        {"data": 2, STAGE_AXIS: 2, MODEL_AXIS: 2},
        pipeline_lm_tp_sharding_fn,
        8,
    )
    assert loss_pp_tp == pytest.approx(loss_pp, rel=1e-5)
    # The composed run's qkv projection really is model-sharded.
    qkv = state_tp.params["blocks"]["attention"]["qkv"]["kernel"]
    assert "model" in str(qkv.sharding.spec)
