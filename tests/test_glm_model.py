"""The whole glm-4.7-flash model at a small size against its plain
reference (PR 56): both loss terms, both streams' logits, the gradient
of every leaf (the two shared tables' among them), the cell's own
``reference_check``, and a job that trains the tiny preset through
``ElasticTrainer``, checkpoints, restores and steps on with the same
loss. (The mixer, the module, the loss and the share:
``tests/test_glm.py``.)"""

import re

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from configurations import loader_stub

from adaptdl_tpu import trace

NAME = "glm-4.7-flash"


def test_loss_logits_and_gradients_equal_the_reference(monkeypatch):
    """Every kind of layer of the cell (latent attention + the dense
    FFN, two routed layers, the module's routed block), remat on, the
    flash kernels, a share of 4 of 16 experts, the untied head streamed
    over both streams: the loss (both terms), both streams' logits and
    the gradient of every leaf. (The cell's own ``reference_check`` on
    such a build: ``benchmark/tests/test_glm_cell.py``.)"""
    config = configurations.module(NAME)
    sizes = configurations.sizes(NAME, num_hidden_layers=3)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    data = config.make_dataset(sizes, 5, 4)
    batch = {k: jnp.asarray(v[:2]) for k, v in data.items()}

    def system(params):
        loss, counters = built["loss_fn"](params, batch, jax.random.key(0))
        return loss, counters["mtp.loss"]

    def reference(params):
        loss, parts = config.reference_loss(
            config.reference_weights(params, sizes),
            batch["inputs"], batch["targets"], sizes,
        )
        return loss, parts

    (loss, terms), grads = jax.jit(
        jax.value_and_grad(system, has_aux=True)
    )(params)
    (want, parts), want_grads = jax.jit(
        jax.value_and_grad(reference, has_aux=True)
    )(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert float(terms["main"]) == pytest.approx(float(parts["main"]), rel=1e-5)
    assert float(terms["mtp"]) == pytest.approx(float(parts["mtp"]), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    kinds = set()
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            continue  # a buffer: no gradient reaches it on either side
        kinds.add(re.sub(r"layer_\d+", "layer", name))
        scale = max(float(jnp.abs(ref).max()), 1e-6)
        assert float(jnp.abs(got - ref).max()) / scale < 5e-4, name
    # Every kind of leaf, the trunk's and the module's: a block's two
    # norms, the mixer's seven, the dense FFN's three or the router,
    # the experts' three and the shared expert's three (17 + 16 under
    # ``mtp``), the module's three norms and projection, the final
    # norm and the two tables.
    assert len(kinds) == 19 + 16 + 4 + 3, sorted(kinds)
    model_logits = jax.jit(
        lambda p, b: jnp.stack(
            built["model"].apply(
                {"params": p}, b["inputs"], next_tokens=b["targets"]
            )
        )
    )(params, batch)
    want_logits = jax.jit(
        lambda p, b: config.reference_logits(
            config.reference_weights(p, sizes), b["inputs"], b["targets"],
            sizes,
        )
    )(params, batch)
    assert model_logits.shape == (2, 2, 64, 97)
    np.testing.assert_allclose(
        model_logits, want_logits, rtol=2e-4, atol=2e-4
    )


def test_a_job_trains_checkpoints_restores_and_continues(
    tmp_path, monkeypatch
):
    """``ElasticTrainer.run_step`` on the donated step from the
    configuration alone: ``moe.load`` journalled with the module's
    router as its last layer, ``mtp.loss`` beside it, a save through
    ``checkpoint.py``, and a restore into a fresh trainer that steps on
    bit-equal."""
    from adaptdl_tpu import checkpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    config = configurations.module(NAME)
    sizes = configurations.sizes(NAME, num_hidden_layers=2)
    data = config.make_dataset(sizes, 5, 8)
    batch = {k: v[:4] for k, v in data.items()}
    built = configurations.built(monkeypatch, NAME, sizes)
    trainer = built["trainer"]
    holder = {"state": trainer.init_state()}
    ck = trainer.make_checkpoint_state(
        lambda: holder["state"], lambda s: holder.__setitem__("state", s)
    )
    trainer._calibrated.add(2)
    since = len(trace.snapshot_spans())
    holder["state"], metrics = trainer.run_step(
        holder["state"], batch, loader_stub(2, 1)
    )
    assert np.isfinite(float(metrics["loss"]))
    load = metrics["counters"]["moe.load"]
    # One trunk routed layer and the module's: every token, twice.
    np.testing.assert_array_equal(load["shared_rows"], [4 * 64] * 2)
    terms = metrics["counters"]["mtp.loss"]
    assert int(terms["micro_batches"]) == 2
    assert float(metrics["loss"]) == pytest.approx(
        float(terms["main"] + 0.1 * terms["mtp"]) / 2, rel=1e-5
    )
    journalled = {
        r["name"]: r["attrs"] for r in trace.snapshot_spans()[since:]
        if r["name"] in ("moe.load", "mtp.loss", "mtp.schedule")
    }
    assert len(journalled["moe.load"]["held_rows"]) == 2
    assert journalled["mtp.loss"]["micro_batches"] == 2
    assert journalled["mtp.schedule"]["head_rows"] == 2 * 2 * 64
    checkpoint.save_all_states()
    saved = jax.tree.map(np.asarray, trainer.params_tree(holder["state"]))
    holder["state"], after = trainer.run_step(
        holder["state"], batch, loader_stub(2, 1)
    )
    ck.unregister()

    again = configurations.built(monkeypatch, NAME, sizes, seed=11)["trainer"]
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
    again._calibrated.add(2)
    holder2["state"], resumed = again.run_step(
        holder2["state"], batch, loader_stub(2, 1)
    )
    assert float(resumed["loss"]) == float(after["loss"])
    ck2.unregister()
