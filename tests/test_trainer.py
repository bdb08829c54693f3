"""ElasticTrainer tests on the virtual 8-device CPU mesh.

Mirrors the reference's coverage (reference:
adaptdl/adaptdl/torch/parallel_test.py — linear-regression convergence
through restarts; gradient_noise_scale_test.py — estimator values).
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from adaptdl_tpu import gns
from adaptdl_tpu.parallel import create_mesh
from adaptdl_tpu.scaling_rules import AdaScale
from adaptdl_tpu.trainer import ElasticTrainer, TrainState

TRUE_W = np.array([2.0, -3.0, 0.5, 1.5], np.float32)


def _make_data(n, seed=0, noise=0.1):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = x @ TRUE_W + noise * rng.normal(size=n).astype(np.float32)
    return {"x": x, "y": y}


def _loss_fn(params, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _make_trainer(num_devices, **kwargs):
    mesh = create_mesh(devices=jax.devices()[:num_devices])
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    defaults = dict(
        loss_fn=_loss_fn,
        params=params,
        optimizer=optax.sgd(0.05),
        init_batch_size=16,
        scaling_rule=AdaScale(),
        mesh=mesh,
    )
    defaults.update(kwargs)
    return ElasticTrainer(**defaults)


def _run_steps(trainer, state, data, atomic_bsz, accum_steps, steps, seed=1):
    rng = np.random.default_rng(seed)
    step_fn = trainer.train_step(atomic_bsz, accum_steps)
    global_bsz = trainer.num_replicas * (accum_steps + 1) * atomic_bsz
    metrics = None
    for _ in range(steps):
        idx = rng.integers(0, len(data["y"]), size=global_bsz)
        batch = trainer.shard_batch(
            {"x": data["x"][idx], "y": data["y"][idx]}
        )
        state, metrics = step_fn(state, batch)
    return state, metrics


def test_converges_multi_replica():
    trainer = _make_trainer(8)
    state = trainer.init_state()
    data = _make_data(2048)
    state, metrics = _run_steps(
        trainer, state, data, atomic_bsz=16, accum_steps=0, steps=60
    )
    w = np.asarray(state.params["w"])
    assert np.allclose(w, TRUE_W, atol=0.15), w
    assert float(metrics["loss"]) < 0.05


def test_gain_between_one_and_scale():
    trainer = _make_trainer(8)
    state = trainer.init_state()
    data = _make_data(2048)
    state, metrics = _run_steps(
        trainer, state, data, atomic_bsz=16, accum_steps=1, steps=20
    )
    scale = float(metrics["scale"])
    assert scale == pytest.approx(8 * 2 * 16 / 16)
    gain = float(metrics["gain"])
    assert 1.0 <= gain <= scale + 1e-6
    # Noisy regression at batch 256 is far from the critical batch
    # size, so the gain should be clearly sublinear.
    assert gain < scale


def test_progress_advances_by_gain():
    trainer = _make_trainer(4)
    state = trainer.init_state()
    data = _make_data(512)
    state, m = _run_steps(
        trainer, state, data, atomic_bsz=16, accum_steps=0, steps=5
    )
    assert 0 < float(state.progress) <= 5 * float(m["scale"]) + 1e-6
    assert int(state.step) == 5


def test_single_replica_differenced_estimator():
    trainer = _make_trainer(1)
    state = trainer.init_state()
    data = _make_data(512)
    state, metrics = _run_steps(
        trainer, state, data, atomic_bsz=16, accum_steps=0, steps=10
    )
    assert bool(state.gns.ema_is_biased)
    assert bool(state.gns.prev_grad_valid)
    assert float(metrics["grad_var"]) > 0
    # Scaling up with accumulation switches to unbiased estimates and
    # resets the EMAs.
    state, metrics = _run_steps(
        trainer, state, data, atomic_bsz=16, accum_steps=1, steps=5
    )
    assert not bool(state.gns.ema_is_biased)


def test_estimator_consistency_across_replica_counts():
    """GNS estimates from 8x1 and 1x(accum 8) agree in expectation."""
    data = _make_data(4096, noise=0.5)
    t8 = _make_trainer(8, init_batch_size=8)
    s8, _ = _run_steps(t8, t8.init_state(), data, 8, 0, 40)
    t1 = _make_trainer(1, init_batch_size=8)
    s1, _ = _run_steps(t1, t1.init_state(), data, 8, 7, 40)
    var8 = float(gns.var_avg(s8.gns))
    var1 = float(gns.var_avg(s1.gns))
    assert var8 == pytest.approx(var1, rel=0.5), (var8, var1)


def test_checkpoint_restores_onto_different_mesh(tmp_path, monkeypatch):
    """Save on a 2-device mesh, restore onto 8 devices, keep training."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    data = _make_data(1024)

    t2 = _make_trainer(2)
    holder = {"state": t2.init_state()}
    ck = t2.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
    )
    holder["state"], _ = _run_steps(t2, holder["state"], data, 16, 0, 20)
    from adaptdl_tpu import checkpoint as ckpt_mod

    ckpt_mod.save_all_states()
    progress_before = float(holder["state"].progress)
    ck.unregister()

    t8 = _make_trainer(8)
    holder8 = {"state": t8.init_state()}
    ck8 = t8.make_checkpoint_state(
        lambda: holder8["state"],
        lambda s: holder8.__setitem__("state", s),
    )
    assert ckpt_mod.load_state(ck8)
    restored = holder8["state"]
    assert float(restored.progress) == pytest.approx(progress_before)
    assert np.allclose(
        np.asarray(restored.params["w"]),
        np.asarray(holder["state"].params["w"]),
    )
    # Training continues on the new mesh.
    state, metrics = _run_steps(t8, restored, data, 16, 0, 10)
    assert int(state.step) == 30
    assert float(metrics["loss"]) < 1.0
    ck8.unregister()


def test_adam_preconditioned_gns():
    trainer = _make_trainer(
        4,
        optimizer=optax.adam(1e-2),
        precondition="adam",
    )
    state = trainer.init_state()
    data = _make_data(512)
    state, metrics = _run_steps(trainer, state, data, 16, 0, 10)
    assert np.isfinite(float(metrics["grad_sqr"]))
    assert np.isfinite(float(metrics["grad_var"]))
    assert float(metrics["loss"]) < 20.0


# ---- per-param-group gradient noise scale ---------------------------


def test_per_group_gns_distinct_gains():
    """VERDICT r1 item 7's bar: two param groups with different noise
    levels get DISTINCT per-group gains (reference keeps per-group
    arrays, gradient_noise_scale.py:66-73, and AdaScale applies one
    factor per group, scaling_rules.py:119-125)."""
    import optax

    from adaptdl_tpu import gns as gns_mod
    from adaptdl_tpu.scaling_rules import AdaScale, RuleContext

    rng = np.random.default_rng(0)
    # Group "clean": targets follow a fixed linear map (low gradient
    # noise). Group "noisy": targets are independent noise (gradient
    # variance dominates).
    w_true = rng.normal(size=4).astype(np.float32)
    data = {
        "x": rng.normal(size=(512, 4)).astype(np.float32),
        "z": rng.normal(size=(512, 4)).astype(np.float32),
    }
    data["y_clean"] = (data["x"] @ w_true).astype(np.float32)
    data["y_noisy"] = rng.normal(size=512).astype(np.float32)

    def loss_fn(params, batch, _rng):
        clean = jnp.mean(
            (batch["x"] @ params["w_clean"] - batch["y_clean"]) ** 2
        )
        noisy = jnp.mean(
            (batch["z"] @ params["w_noisy"] - batch["y_noisy"]) ** 2
        )
        return clean + noisy

    def group_fn(path, leaf):
        return 0 if "clean" in str(path[-1]) else 1

    trainer = ElasticTrainer(
        loss_fn,
        {"w_clean": jnp.zeros(4), "w_noisy": jnp.zeros(4)},
        optax.sgd(0.05),
        16,
        scaling_rule=AdaScale(),
        mesh=create_mesh(devices=jax.devices()[:2]),
        param_group_fn=group_fn,
    )
    assert trainer.num_param_groups == 2
    state = trainer.init_state()
    step = trainer.train_step(8, 1)  # 2 replicas x 2 micro = count 4
    for _ in range(30):
        idx = rng.integers(0, 512, size=32)
        state, m = step(
            state,
            trainer.shard_batch({k: v[idx] for k, v in data.items()}),
        )
    raw_var = np.asarray(gns_mod.raw_var_avg(state.gns))
    raw_sqr = np.asarray(gns_mod.raw_sqr_avg(state.gns))
    assert raw_var.shape == (2,)
    # The noisy group's noise/signal ratio dwarfs the clean group's.
    ratio = raw_var / np.maximum(raw_sqr, 1e-12)
    assert ratio[1] > 5 * ratio[0], (raw_sqr, raw_var)
    # ...so scaling the batch benefits it more: the noisy group's
    # AdaScale gain approaches `scale` while the clean (signal-
    # dominated) group's stays near 1.
    ctx = RuleContext(
        scale=8.0,
        batch_size=128,
        init_batch_size=16,
        gns_state=state.gns,
        progress=state.progress,
    )
    factors = np.asarray(AdaScale().lr_factor_groups(ctx))
    assert factors.shape == (2,)
    assert factors[1] > 1.5 * factors[0], factors
    assert factors[0] < 4.0 < factors[1] <= 8.0 + 1e-5, factors
    # Totals still feed the global gain/progress metric.
    assert float(m["gain"]) >= 1.0


def test_single_group_checkpoint_restores_into_grouped_trainer(
    tmp_path, monkeypatch
):
    """Old checkpoints carry scalar GNS stats; they broadcast into a
    per-group trainer instead of failing shape checks."""
    from adaptdl_tpu import gns as gns_mod

    state = gns_mod.init({"w": jnp.zeros(2)}, num_groups=1)
    legacy = state._replace(
        sqr_biased=np.float32(0.5),
        sqr_unbias=np.float32(1.0),
        var_biased=np.float32(0.25),
        var_unbias=np.float32(1.0),
    )
    fixed = gns_mod.normalize_groups(legacy, 3)
    assert fixed.sqr_biased.shape == (3,)
    np.testing.assert_allclose(fixed.sqr_biased, [0.5] * 3)
    with pytest.raises(ValueError):
        gns_mod.normalize_groups(fixed, 2)


# ---- the last micro-batch outside the accumulation scan (PR 43) -------


TRACED = []  # one entry a trace of a ``_counting`` loss


def _counting(loss):
    """``loss`` scaled by a draw from the micro-batch's rng, with the
    draw and the rows seen among its counters."""

    def counted(params, batch, rng):
        TRACED.append(1)
        draw = jax.random.uniform(rng)
        seen = {"rows": jnp.float32(len(batch["x"])), "draw": draw}
        return loss(params, batch, rng) * (1 + draw), {"test.seen": seen}

    counted.has_counters = True
    return counted


def _two_steps(layout, dp, accum_steps, lower_only=False):
    from tests import test_storage as cases

    trainer = cases._trainer(layout, dp, wrap=_counting)
    rng = np.random.default_rng(1)
    batch = trainer.shard_batch({
        "x": rng.normal(size=(cases.ROWS, cases.D)).astype(np.float32),
        "y": rng.normal(size=(cases.ROWS, 7)).astype(np.float32),
    })
    state = trainer.init_state(cases._params())
    step = trainer.train_step(
        cases.ROWS // dp // (accum_steps + 1), accum_steps
    )
    if lower_only:
        return step._jitted.lower(state, batch, ()).as_text()
    for _ in range(2):
        state, metrics = step(state, batch)
    return cases._stored(state), metrics


def _assert_same_bits(tailed, in_scan):
    assert jax.tree.structure(tailed) == jax.tree.structure(in_scan)
    for x, y in zip(jax.tree.leaves(tailed), jax.tree.leaves(in_scan)):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


@pytest.mark.parametrize("accum_steps", [0, 1, 3])
@pytest.mark.parametrize(
    "layout", ["replicated", "zero1", "zero3-lite", "zero3-blocks"]
)
def test_last_micro_batch_outside_the_scan_is_the_scans_step(
    layout, accum_steps, monkeypatch
):
    """With more than one data replica the step's last micro-batch
    runs behind the accumulation scan, not in it (so that the reduce
    of a leaf waits for that leaf alone), the model traced once for
    both (``TRACED``). Against the all-in-scan step: the state after
    two steps, the metrics and the counters (sums of draws from the
    micro-batches' rngs) to the bit. zero3-blocks reduces its rows
    inside AD and keeps the all-in-scan step."""
    from adaptdl_tpu import trainer as trainer_mod

    events = []
    monkeypatch.setattr(
        trainer_mod.trace, "event",
        lambda name, **attrs: events.append((name, attrs)),
    )

    def overlaps():
        # (By name: with jax's persistent compile cache on, its
        # ``jit.cache_*`` events follow the step's own.)
        return [a for n, a in events if n == "step.reduce_overlap"]

    del TRACED[:]
    tailed = _two_steps(layout, 4, accum_steps)
    assert len(TRACED) == 1
    has_tail = layout != "zero3-blocks"
    assert overlaps() == [{
        "replicas": 4, "num_micro": accum_steps + 1,
        "scanned": accum_steps + (not has_tail), "tail": has_tail,
        "groups": 7 if has_tail else 0,  # one all-reduce a leaf
    }]
    monkeypatch.setattr(
        ElasticTrainer, "_reduce_has_tail", lambda self: False
    )
    in_scan = _two_steps(layout, 4, accum_steps)
    assert len(overlaps()) == 2 and overlaps()[-1]["tail"] is False
    _assert_same_bits(tailed, in_scan)
    seen = tailed[1]["counters"]["test.seen"]
    assert float(seen["rows"]) == 16.0
    assert float(seen["draw"]) not in (0.0, float(seen["rows"]))


def test_another_order_of_the_sums_is_not_the_scans_step(monkeypatch):
    """What the comparison above can see: the three micro-batches in
    the loop summed last first — every sum within 2e-5 of the all-in-
    scan step's — are not the same bits."""
    scan = jax.lax.scan

    def last_first(body, init, xs, **kwargs):
        return scan(
            body, init, xs, reverse=body.__name__ == "micro_step", **kwargs
        )

    with monkeypatch.context() as patched:
        patched.setattr(jax.lax, "scan", last_first)
        faulty = _two_steps("replicated", 4, 3)
    monkeypatch.setattr(
        ElasticTrainer, "_reduce_has_tail", lambda self: False
    )
    in_scan = _two_steps("replicated", 4, 3)
    for x, y in zip(jax.tree.leaves(faulty), jax.tree.leaves(in_scan)):
        np.testing.assert_allclose(x, y, rtol=2e-5, atol=1e-7)
    with pytest.raises(AssertionError):
        _assert_same_bits(faulty, in_scan)


def test_only_plain_data_parallel_jobs_get_the_tail():
    """The tail (and the compiler options that go with it) where it
    was measured: the data axis the mesh's only one, more than one
    replica, a layout that all-reduces the gradient."""
    from tests import test_storage as cases

    def has_tail(axes, layout="replicated"):
        size = int(np.prod(list(axes.values())))
        return _make_trainer(
            size, mesh=create_mesh(axes, devices=jax.devices()[:size]),
            **cases.LAYOUTS[layout],
        )._reduce_has_tail()

    assert has_tail({"data": 4})
    assert has_tail({"data": 2}, "zero1")
    assert not has_tail({"data": 1})
    assert not has_tail({"data": 2, "seq": 2})
    assert not has_tail({"data": 2, "model": 2})
    assert not has_tail({"data": 2, "stage": 2})
    assert _make_trainer(4)._reduce_overlap_options() is None  # no TPU


def test_one_replica_keeps_the_all_in_scan_step(monkeypatch):
    """One replica has nothing to overlap: its step program is the
    all-in-scan one, text for text — the program four replicas lower
    with the tail switched off is the form compared with."""
    from adaptdl_tpu import trainer as trainer_mod

    events = []
    monkeypatch.setattr(
        trainer_mod.trace, "event",
        lambda name, **attrs: events.append((name, attrs)),
    )
    shipped = _two_steps("replicated", 1, 1, lower_only=True)
    assert events[-1] == ("step.reduce_overlap", {
        "replicas": 1, "num_micro": 2, "scanned": 2, "tail": False,
        "groups": 7,
    })
    tailed = _two_steps("replicated", 4, 1, lower_only=True)
    monkeypatch.setattr(
        ElasticTrainer, "_reduce_has_tail", lambda self: False
    )
    assert _two_steps("replicated", 1, 1, lower_only=True) == shipped
    in_scan = _two_steps("replicated", 4, 1, lower_only=True)
    # (The accumulation is one ``while`` more where the last
    # micro-batch has a scan of its own; the model's are the rest.)
    whiles = [
        text.count("stablehlo.while") for text in (shipped, in_scan, tailed)
    ]
    assert whiles[0] == whiles[1] == whiles[2] - 1
    assert "optimization_barrier" in tailed
    assert "optimization_barrier" not in shipped + in_scan
