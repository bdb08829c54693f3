"""Expert parallelism: the all_to_all Switch-MoE dispatch matches the
dense reference, and a dp x expert ElasticTrainer run trains with
correct gradients for both sharded (expert) and replicated (router)
parameters."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from adaptdl_tpu.models.moe import (
    dense_switch_moe,
    stack_expert_params,
    switch_moe,
)
from adaptdl_tpu.parallel import create_mesh
from adaptdl_tpu.parallel.mesh import EXPERT_AXIS

try:
    shard_map = jax.shard_map
except AttributeError:  # pragma: no cover
    from jax.experimental.shard_map import shard_map

D, F, E = 8, 16, 4


def _params(rng):
    router = jnp.asarray(rng.normal(size=(D, E)).astype(np.float32))
    per_expert = [
        {
            "w_up": jnp.asarray(
                rng.normal(size=(D, F)).astype(np.float32) * 0.3
            ),
            "w_down": jnp.asarray(
                rng.normal(size=(F, D)).astype(np.float32) * 0.3
            ),
        }
        for _ in range(E)
    ]
    return router, stack_expert_params(per_expert)


def test_expert_parallel_matches_dense():
    rng = np.random.default_rng(0)
    router, stacked = _params(rng)
    x = jnp.asarray(rng.normal(size=(32, D)).astype(np.float32))
    mesh = create_mesh({EXPERT_AXIS: E}, devices=jax.devices()[:E])
    params = {"router": router, **stacked}

    piped = shard_map(
        lambda p, xx: switch_moe(p, xx),
        mesh=mesh,
        in_specs=(
            {
                "router": P(),
                "w_up": P(EXPERT_AXIS),
                "w_down": P(EXPERT_AXIS),
            },
            P(),
        ),
        out_specs=P(),
    )(params, x)
    want = dense_switch_moe(router, stacked, x, num_slices=E)
    np.testing.assert_allclose(
        np.asarray(piped), np.asarray(want), atol=1e-5, rtol=1e-5
    )
    # Routing actually moved tokens off the passthrough path.
    assert not np.allclose(np.asarray(piped), np.asarray(x))


def test_trainer_dp_x_expert_trains_and_matches_dense_grads():
    """dp=2 x expert=2: the elastic step trains the MoE, and the first
    step's gradients (router AND experts) match a pure-DP run of the
    dense-equivalent model."""
    rng = np.random.default_rng(1)
    local_e = 2  # expert axis size in this test
    router = jnp.asarray(
        rng.normal(size=(D, local_e)).astype(np.float32)
    )
    per_expert = [
        {
            "w_up": jnp.asarray(
                rng.normal(size=(D, F)).astype(np.float32) * 0.3
            ),
            "w_down": jnp.asarray(
                rng.normal(size=(F, D)).astype(np.float32) * 0.3
            ),
        }
        for _ in range(local_e)
    ]
    stacked = stack_expert_params(per_expert)
    params = {"router": router, **stacked}
    data = {
        "x": rng.normal(size=(64, D)).astype(np.float32),
        "y": rng.normal(size=64).astype(np.float32),
    }

    def moe_loss(p, batch, rng_):
        out = switch_moe(p, batch["x"])
        return jnp.mean((out.sum(axis=-1) - batch["y"]) ** 2)

    def sharding_fn(path, leaf):
        name = str(path[0].key if hasattr(path[0], "key") else path[0])
        return P() if name == "router" else P(EXPERT_AXIS)

    from adaptdl_tpu.trainer import ElasticTrainer

    ep_trainer = ElasticTrainer(
        moe_loss,
        params,
        optax.sgd(0.05),
        16,
        mesh=create_mesh(
            {"data": 2, EXPERT_AXIS: local_e},
            devices=jax.devices()[:4],
        ),
        param_sharding_fn=sharding_fn,
    )
    ep_state = ep_trainer.init_state()
    ep_step = ep_trainer.train_step(8, 0)

    def dp_loss(p, batch, rng_):
        out = dense_switch_moe(
            p["router"],
            {"w_up": p["w_up"], "w_down": p["w_down"]},
            batch["x"],
            num_slices=local_e,
        )
        return jnp.mean((out.sum(axis=-1) - batch["y"]) ** 2)

    dp_trainer = ElasticTrainer(
        dp_loss,
        params,
        optax.sgd(0.05),
        16,
        mesh=create_mesh({"data": 2}, devices=jax.devices()[:2]),
    )
    dp_state = dp_trainer.init_state()
    dp_step = dp_trainer.train_step(8, 0)

    for step_idx in range(3):
        idx = rng.integers(0, 64, size=16)
        batch = {k: v[idx] for k, v in data.items()}
        ep_state, ep_m = ep_step(ep_state, ep_trainer.shard_batch(batch))
        dp_state, dp_m = dp_step(dp_state, dp_trainer.shard_batch(batch))
        assert float(ep_m["loss"]) == pytest.approx(
            float(dp_m["loss"]), rel=1e-4
        ), step_idx
        assert float(ep_m["grad_sqr"]) == pytest.approx(
            float(dp_m["grad_sqr"]), rel=1e-3, abs=1e-8
        )
    # Both the replicated router and the sharded experts evolved
    # identically to the dense run.
    for key in ("router", "w_up", "w_down"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(ep_state.params[key])),
            np.asarray(jax.device_get(dp_state.params[key])),
            atol=1e-5,
            err_msg=key,
        )
    assert "expert" in str(ep_state.params["w_up"].sharding.spec)
    assert str(ep_state.params["router"].sharding.spec) == (
        "PartitionSpec()"
    )


def test_top2_routing_matches_dense_and_uses_two_experts():
    rng = np.random.default_rng(3)
    router, stacked = _params(rng)
    x = jnp.asarray(rng.normal(size=(32, D)).astype(np.float32))
    mesh = create_mesh({EXPERT_AXIS: E}, devices=jax.devices()[:E])
    params = {"router": router, **stacked}
    piped, aux = shard_map(
        lambda p, xx: switch_moe(
            p, xx, top_k=2, return_aux=True
        ),
        mesh=mesh,
        in_specs=(
            {
                "router": P(),
                "w_up": P(EXPERT_AXIS),
                "w_down": P(EXPERT_AXIS),
            },
            P(),
        ),
        out_specs=(P(), P()),
    )(params, x)
    want, want_aux = dense_switch_moe(
        router, stacked, x, num_slices=E, top_k=2, return_aux=True
    )
    np.testing.assert_allclose(
        np.asarray(piped), np.asarray(want), atol=1e-5, rtol=1e-5
    )
    assert float(aux) == pytest.approx(float(want_aux), rel=1e-5)
    # top-2 output differs from top-1 (the second expert contributes).
    top1 = dense_switch_moe(router, stacked, x, num_slices=E)
    assert not np.allclose(np.asarray(want), np.asarray(top1))


def test_multi_expert_per_device_matches_dense():
    """E=4 experts over ep=2 devices (2 experts per device)."""
    rng = np.random.default_rng(4)
    router, stacked = _params(rng)
    x = jnp.asarray(rng.normal(size=(32, D)).astype(np.float32))
    mesh = create_mesh({EXPERT_AXIS: 2}, devices=jax.devices()[:2])
    params = {"router": router, **stacked}
    piped = shard_map(
        lambda p, xx: switch_moe(p, xx),
        mesh=mesh,
        in_specs=(
            {
                "router": P(),
                "w_up": P(EXPERT_AXIS),
                "w_down": P(EXPERT_AXIS),
            },
            P(),
        ),
        out_specs=P(),
    )(params, x)
    want = dense_switch_moe(router, stacked, x, num_slices=2)
    np.testing.assert_allclose(
        np.asarray(piped), np.asarray(want), atol=1e-5, rtol=1e-5
    )


def test_aux_loss_balances_uniform_and_collapsed_routers():
    """The Switch aux loss is ~1 for a uniform router and larger for a
    collapsed one — the signal that keeps experts alive."""
    rng = np.random.default_rng(5)
    # Positive inputs so a dominant router column wins for EVERY token.
    x = jnp.asarray(
        np.abs(rng.normal(size=(64, D))).astype(np.float32)
    )
    stacked = _params(rng)[1]
    uniform_router = jnp.zeros((D, E), jnp.float32)
    _, aux_uniform = dense_switch_moe(
        uniform_router, stacked, x, num_slices=1, return_aux=True
    )
    collapsed_router = (
        jnp.zeros((D, E), jnp.float32).at[:, 0].set(50.0)
    )
    _, aux_collapsed = dense_switch_moe(
        collapsed_router, stacked, x, num_slices=1, return_aux=True
    )
    # Collapse: f_0 = P_0 = 1 -> aux = E; uniform: f·P = 1/E each -> 1.
    assert float(aux_collapsed) == pytest.approx(E, rel=1e-3)
    assert float(aux_uniform) == pytest.approx(1.0, rel=1e-3)


def test_moe_transformer_expert_parallel_matches_dense():
    """A MoE *transformer* (every 2nd block Switch-MoE) trains under
    dp x expert with the same loss as the dense-equivalent model —
    the VERDICT r2 'dryrun a MoE transformer' integration, test-sized.
    """
    import dataclasses

    import optax

    from adaptdl_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
        lm_loss_fn,
        moe_param_sharding_fn,
    )
    from adaptdl_tpu.trainer import ElasticTrainer

    cfg = TransformerConfig(
        vocab_size=64,
        num_layers=2,
        num_heads=2,
        d_model=16,
        d_ff=32,
        max_seq_len=16,
        dtype=jnp.float32,
        remat=False,
        moe_every_n=2,
        moe_num_experts=2,
        moe_axis=EXPERT_AXIS,
        moe_dense_slices=2,
    )
    model, params = init_transformer(cfg, seq_len=16)
    assert "moe" in params["layer_1"], list(params["layer_1"])
    rng = np.random.default_rng(6)
    tokens = rng.integers(0, 64, size=(32, 17)).astype(np.int32)

    ep_trainer = ElasticTrainer(
        lm_loss_fn(model),
        params,
        optax.sgd(0.1),
        8,
        mesh=create_mesh(
            {"data": 2, EXPERT_AXIS: 2}, devices=jax.devices()[:4]
        ),
        param_sharding_fn=moe_param_sharding_fn,
    )
    ep_state = ep_trainer.init_state()
    ep_step = ep_trainer.train_step(4, 0)

    dense_model = type(model)(
        dataclasses.replace(cfg, moe_axis=None)
    )
    dp_trainer = ElasticTrainer(
        lm_loss_fn(dense_model),
        params,
        optax.sgd(0.1),
        8,
        mesh=create_mesh({"data": 2}, devices=jax.devices()[:2]),
    )
    dp_state = dp_trainer.init_state()
    dp_step = dp_trainer.train_step(4, 0)

    losses = []
    for step_idx in range(3):
        batch = {"tokens": tokens[rng.integers(0, 32, size=8)]}
        ep_state, ep_m = ep_step(ep_state, ep_trainer.shard_batch(batch))
        dp_state, dp_m = dp_step(dp_state, dp_trainer.shard_batch(batch))
        assert float(ep_m["loss"]) == pytest.approx(
            float(dp_m["loss"]), rel=1e-4
        ), step_idx
        losses.append(float(ep_m["loss"]))
    # Expert weights sharded, router replicated, and training moves.
    moe_params = ep_state.params["layer_1"]["moe"]
    assert "expert" in str(moe_params["w_up"].sharding.spec)
    assert str(moe_params["router"].sharding.spec) == "PartitionSpec()"
    assert losses[-1] < losses[0]


# ---- expert-choice routing ----------------------------------------------


def test_expert_choice_parallel_matches_dense():
    """Expert-choice routing: the all_to_all sharded path reproduces
    the dense reference bit-for-bit (same per-slice top-C binning)."""
    rng = np.random.default_rng(5)
    router, stacked = _params(rng)
    x = jnp.asarray(rng.normal(size=(32, D)).astype(np.float32))
    mesh = create_mesh({EXPERT_AXIS: E}, devices=jax.devices()[:E])
    params = {"router": router, **stacked}

    piped = shard_map(
        lambda p, xx: switch_moe(p, xx, router_type="experts"),
        mesh=mesh,
        in_specs=(
            {
                "router": P(),
                "w_up": P(EXPERT_AXIS),
                "w_down": P(EXPERT_AXIS),
            },
            P(),
        ),
        out_specs=P(),
    )(params, x)
    want = dense_switch_moe(
        router, stacked, x, num_slices=E, router_type="experts"
    )
    np.testing.assert_allclose(
        np.asarray(piped), np.asarray(want), atol=1e-5, rtol=1e-5
    )


def test_expert_choice_balance_is_structural():
    """Every expert processes exactly its capacity of tokens — no
    router collapse is possible, and the aux loss is identically 0."""
    from adaptdl_tpu.models.moe import _expert_choice_routing

    rng = np.random.default_rng(6)
    # A router heavily biased toward expert 0: token-choice would
    # collapse; expert-choice cannot.
    router = jnp.asarray(
        rng.normal(size=(D, E)).astype(np.float32)
    ) + jnp.array([5.0, 0, 0, 0])[None, :]
    x = jnp.asarray(rng.normal(size=(16, D)).astype(np.float32))
    capacity = 3
    dispatch, combine, aux = _expert_choice_routing(
        x, router, E, capacity
    )
    per_expert_tokens = np.asarray(
        jnp.einsum("sec->e", dispatch)
    )
    np.testing.assert_array_equal(
        per_expert_tokens, np.full(E, capacity)
    )
    assert float(aux) == 0.0
    # Gates carry the router affinity of the chosen (expert, slot).
    assert float(jnp.max(combine)) <= 1.0


def test_expert_choice_transformer_trains():
    """A dp x expert MoE transformer with expert-choice routing runs
    a full elastic step with finite loss and zero aux contribution."""
    from adaptdl_tpu.models import (
        TransformerConfig,
        init_transformer,
        lm_loss_fn,
    )
    from adaptdl_tpu.models.transformer import moe_param_sharding_fn
    from adaptdl_tpu.trainer import ElasticTrainer

    cfg = TransformerConfig(
        vocab_size=64,
        num_layers=2,
        num_heads=2,
        d_model=16,
        d_ff=32,
        max_seq_len=8,
        dtype=jnp.float32,
        remat=False,
        moe_every_n=2,
        moe_num_experts=2,
        moe_axis=EXPERT_AXIS,
        moe_top_k=1,
        moe_router="experts",
        causal=False,  # expert-choice is encoder/MLM-only (non-causal)
    )
    model, params = init_transformer(cfg, seq_len=8)
    trainer = ElasticTrainer(
        lm_loss_fn(model),
        params,
        optax.adam(1e-3),
        4,
        mesh=create_mesh(
            {"data": 2, EXPERT_AXIS: 2}, devices=jax.devices()[:4]
        ),
        param_sharding_fn=moe_param_sharding_fn,
    )
    state = trainer.init_state()
    step = trainer.train_step(2, 0)
    rng = np.random.default_rng(8)
    tokens = rng.integers(0, 64, size=(4, 9), dtype=np.int32)
    state, m = step(state, trainer.shard_batch({"tokens": tokens}))
    assert np.isfinite(float(m["loss"]))


def test_expert_choice_trainer_matches_dense_trajectory():
    """dp x expert with expert-choice routing: losses, GNS statistics,
    and the router AND expert parameter trajectories match the
    dense-equivalent pure-DP run (gradient flow through lax.top_k and
    the all_to_all exchange is regression-protected, not just the
    forward)."""
    rng = np.random.default_rng(9)
    local_e = 2
    router = jnp.asarray(
        rng.normal(size=(D, local_e)).astype(np.float32)
    )
    per_expert = [
        {
            "w_up": jnp.asarray(
                rng.normal(size=(D, F)).astype(np.float32) * 0.3
            ),
            "w_down": jnp.asarray(
                rng.normal(size=(F, D)).astype(np.float32) * 0.3
            ),
        }
        for _ in range(local_e)
    ]
    stacked = stack_expert_params(per_expert)
    params = {"router": router, **stacked}
    data = {
        "x": rng.normal(size=(64, D)).astype(np.float32),
        "y": rng.normal(size=64).astype(np.float32),
    }

    def moe_loss(p, batch, rng_):
        out = switch_moe(p, batch["x"], router_type="experts")
        return jnp.mean((out.sum(axis=-1) - batch["y"]) ** 2)

    def sharding_fn(path, leaf):
        name = str(path[0].key if hasattr(path[0], "key") else path[0])
        return P() if name == "router" else P(EXPERT_AXIS)

    from adaptdl_tpu.trainer import ElasticTrainer

    ep_trainer = ElasticTrainer(
        moe_loss,
        params,
        optax.sgd(0.05),
        16,
        mesh=create_mesh(
            {"data": 2, EXPERT_AXIS: local_e},
            devices=jax.devices()[:4],
        ),
        param_sharding_fn=sharding_fn,
    )
    ep_state = ep_trainer.init_state()
    ep_step = ep_trainer.train_step(8, 0)

    def dp_loss(p, batch, rng_):
        out = dense_switch_moe(
            p["router"],
            {"w_up": p["w_up"], "w_down": p["w_down"]},
            batch["x"],
            num_slices=local_e,
            router_type="experts",
        )
        return jnp.mean((out.sum(axis=-1) - batch["y"]) ** 2)

    dp_trainer = ElasticTrainer(
        dp_loss,
        params,
        optax.sgd(0.05),
        16,
        mesh=create_mesh({"data": 2}, devices=jax.devices()[:2]),
    )
    dp_state = dp_trainer.init_state()
    dp_step = dp_trainer.train_step(8, 0)

    for step_idx in range(3):
        idx = rng.integers(0, 64, size=16)
        batch = {k: v[idx] for k, v in data.items()}
        ep_state, ep_m = ep_step(ep_state, ep_trainer.shard_batch(batch))
        dp_state, dp_m = dp_step(dp_state, dp_trainer.shard_batch(batch))
        assert float(ep_m["loss"]) == pytest.approx(
            float(dp_m["loss"]), rel=1e-4
        ), step_idx
        assert float(ep_m["grad_sqr"]) == pytest.approx(
            float(dp_m["grad_sqr"]), rel=1e-3, abs=1e-8
        )
    for key in ("router", "w_up", "w_down"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(ep_state.params[key])),
            np.asarray(jax.device_get(dp_state.params[key])),
            atol=1e-5,
            err_msg=key,
        )


def test_expert_choice_capacity_ignores_topk_and_clamps():
    """Flipping a GShard config (top_k=2, cf=2) to expert-choice must
    not crash lax.top_k: capacity ignores top_k and clamps to the
    token-slice length."""
    rng = np.random.default_rng(10)
    router, stacked = _params(rng)
    x = jnp.asarray(rng.normal(size=(8, D)).astype(np.float32))
    # slice_len=8, E=4, cf=8 -> unclamped capacity 16 > slice; with
    # top_k=2 token-choice would ask for 32. Must still trace.
    out = dense_switch_moe(
        router, stacked, x, num_slices=1, capacity_factor=8.0,
        top_k=2, router_type="experts",
    )
    assert np.isfinite(np.asarray(out)).all()


def test_unknown_router_type_raises():
    rng = np.random.default_rng(11)
    router, stacked = _params(rng)
    x = jnp.asarray(rng.normal(size=(8, D)).astype(np.float32))
    with pytest.raises(ValueError, match="router_type"):
        dense_switch_moe(
            router, stacked, x, num_slices=1,
            router_type="expert-choice",
        )


def test_expert_choice_rejects_causal_lm():
    from adaptdl_tpu.models import TransformerConfig, init_transformer

    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=16,
        d_ff=32, max_seq_len=8, moe_every_n=2, moe_num_experts=2,
        moe_router="experts",  # causal defaults True
    )
    with pytest.raises(ValueError, match="causal"):
        init_transformer(cfg, seq_len=8)


def test_expert_choice_guard_ignores_disabled_moe():
    """moe_router='experts' on a config with MoE DISABLED builds a
    plain causal LM — the causal guard must not fire."""
    from adaptdl_tpu.models import TransformerConfig, init_transformer

    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=16,
        d_ff=32, max_seq_len=8, moe_router="experts",  # moe off
    )
    model, params = init_transformer(cfg, seq_len=8)
    assert "layer_0" in params
