"""The storage seam's own contract (adaptdl_tpu.storage): for each of
the four layouts, at dp in (1, 2, 4) on the CPU mesh,

- ``from_canonical(to_canonical(state))`` is ``state`` bit for bit,
- the canonical form is dp-independent,
- the host-numpy and the jitted execution of the same transform give
  equal bytes,
- the layout's spec tree is the placement of the state it builds;

and a checkpoint written BEFORE the module existed (tests/data/
storage_pr27/, written by this file's ``__main__`` at the parent
commit) is what the layouts write today, and restores.
"""

import io
import os
import pickle
import sys

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from adaptdl_tpu.parallel import create_mesh
from adaptdl_tpu.parallel import zero3 as z3
from adaptdl_tpu.scaling_rules import AdamScale
from adaptdl_tpu.trainer import ElasticTrainer

LAYOUTS = {
    "replicated": {},
    "zero1": {"zero1": True},
    "zero3-lite": {"zero3": True},
    "zero3-blocks": {"zero3_blocks": "blocks"},
}
FIXTURES = os.path.join(os.path.dirname(__file__), "data", "storage_pr27")
L, D, H, ROWS = 3, 8, 15, 16


def _params():
    rng = np.random.default_rng(0)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)

    return {
        "inp": normal(D, D),
        "blocks": {
            "w1": normal(L, D, H),
            "b1": jnp.zeros((L, H), jnp.float32),
            "w2": normal(L, H, D),
            "b2": jnp.zeros((L, D), jnp.float32),
        },
        "out": normal(D, 7),
        # Odd sizes on purpose: 263 per block, 129 outside, 918 in
        # all, so dp = 2 and dp = 4 pad differently in every layout.
        "gain": jnp.ones((2,), jnp.float32),
    }


def _block(p, hid):
    return hid + jnp.tanh(hid @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


def _dense_loss(p, batch, rng):
    hid, _ = jax.lax.scan(
        lambda h, pb: (_block(pb, h), None),
        batch["x"] @ p["inp"],
        p["blocks"],
    )
    out = hid @ p["out"] * p["gain"][0] + p["gain"][1]
    return jnp.mean((out - batch["y"]) ** 2)


def _rows_loss(spec):
    def loss(view, batch, rng):
        hid = z3.scan_blocks(
            _block, view.blocks, batch["x"] @ view.other["inp"], spec
        )
        gain = view.other["gain"]
        out = hid @ view.other["out"] * gain[0] + gain[1]
        return jnp.mean((out - batch["y"]) ** 2)

    return loss


def _trainer(layout, dp, wrap=lambda loss: loss):
    params = _params()
    loss = (
        _rows_loss(z3.block_spec(params, "blocks"))
        if layout == "zero3-blocks"
        else _dense_loss
    )
    return ElasticTrainer(
        wrap(loss), params, optax.adamw(1e-2), ROWS,
        scaling_rule=AdamScale(), precondition="adam",
        mesh=create_mesh({"data": dp}, devices=jax.devices()[:dp]),
        **LAYOUTS[layout],
    )


def _trained(trainer, steps=2):
    rng = np.random.default_rng(1)
    batch = trainer.shard_batch({
        "x": rng.normal(size=(ROWS, D)).astype(np.float32),
        "y": rng.normal(size=(ROWS, 7)).astype(np.float32),
    })
    # (A trainer lets go of its initial parameters with its first
    # fresh state: every further one is handed them.)
    state = trainer.init_state(_params())
    step = trainer.train_step(ROWS // trainer.num_replicas, 0)
    for _ in range(steps):
        state, _ = step(state, batch)
    return state


def _stored(state):
    """The three fields a layout transforms (the rng key is opaque to
    numpy and no layout's business)."""
    return state._replace(rng=jax.random.key_data(state.rng))


def _assert_same(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("dp", [1, 2, 4])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_layout_contract(layout, dp):
    from adaptdl_tpu import storage

    trainer = _trainer(layout, dp)
    lay = trainer.storage
    assert lay.name.split(":")[0] == layout
    on_mesh = storage.on_mesh(trainer.mesh)

    # The spec tree is the placement of the state the layout builds,
    # fresh and after a step, and of its abstract skeleton.
    fresh = trainer.init_state(_params())
    state = _trained(trainer)
    abstract = trainer._abstract_state()
    for built in (fresh, state):
        specs = trainer.state_spec_tree(built)
        jax.tree.map(
            lambda leaf, spec, skel: (
                leaf.sharding.is_equivalent_to(
                    NamedSharding(trainer.mesh, spec), leaf.ndim
                )
                and leaf.shape == skel.shape
                and leaf.dtype == skel.dtype
            )
            or pytest.fail(f"{leaf.shape} {leaf.sharding} vs {spec}"),
            built, specs, abstract,
        )

    # One transform, two places of execution, equal bytes.
    device = _stored(state)
    host = jax.tree.map(np.asarray, device)
    canon = lay.to_canonical(host, storage.on_host)
    assert all(
        isinstance(x, np.ndarray) for x in jax.tree.leaves(canon)
    )
    _assert_same(canon, lay.to_canonical(device, on_mesh))
    back = lay.from_canonical(canon, storage.on_host)
    back_device = lay.from_canonical(
        # (as orbax restores it: every canonical leaf replicated)
        jax.device_put(canon, NamedSharding(trainer.mesh, P())), on_mesh
    )
    _assert_same(back, back_device)
    jax.tree.map(
        lambda leaf, spec: leaf.sharding.is_equivalent_to(
            NamedSharding(trainer.mesh, spec), leaf.ndim
        ) or pytest.fail(f"restored {leaf.sharding} vs {spec}"),
        back_device.params, lay.param_specs(),
    )

    # The round trip is the state, bit for bit. The one exception is
    # stated by the layout: zero1 / zero3-lite write the GNS carry as
    # a placeholder, so their dp == 1 reader (the only one that has a
    # carry) re-primes.
    if lay.name in ("zero1", "zero3-lite") and dp == 1:
        assert not back.gns.prev_grad_valid
        assert not any(np.any(x) for x in jax.tree.leaves(back.gns.prev_grad))
        back = back._replace(gns=host.gns)
    _assert_same(back, host)

    # The canonical form is dp-independent: read at another dp and
    # written again, it is the same bytes; its params are the tree.
    _assert_same(canon.params, trainer.params_tree(state))
    _assert_same(
        jax.tree.map(np.shape, canon.params),
        jax.tree.map(np.shape, _params()),
    )
    other = _trainer(layout, {1: 2, 2: 4, 4: 2}[dp]).storage
    again = other.to_canonical(
        other.from_canonical(canon, storage.on_host), storage.on_host
    )
    _assert_same(again, canon)


def _snapshot_bytes(trainer, state):
    buf = io.BytesIO()
    ckpt = trainer.make_checkpoint_state(
        lambda: state, lambda s: None, name="storage-fixture"
    )
    ckpt.save(buf)
    ckpt.unregister()
    return buf.getvalue()


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_checkpoint_written_before_the_module_restores(layout):
    """The on-disk format did not move: the same two steps snapshot to
    the leaves PR 27's tree wrote (dp=4), and that file restores onto
    dp=2 and trains on."""
    with open(os.path.join(FIXTURES, f"{layout}.pkl"), "rb") as f:
        old = f.read()
    trainer = _trainer(layout, 4)
    new = pickle.loads(_snapshot_bytes(trainer, _trained(trainer)))
    _assert_same(new, pickle.loads(old))

    small = _trainer(layout, 2)
    holder = {}
    small.make_checkpoint_state(
        small.init_state, lambda s: holder.update(state=s),
        name="storage-fixture-restore",
    ).load(io.BytesIO(old))
    restored = holder["state"]
    assert int(restored.step) == 2
    _assert_same(
        small.params_tree(restored), pickle.loads(old).params
    )
    step = small.train_step(ROWS // 2, 0)
    rng = np.random.default_rng(1)
    _, metrics = step(restored, small.shard_batch({
        "x": rng.normal(size=(ROWS, D)).astype(np.float32),
        "y": rng.normal(size=(ROWS, 7)).astype(np.float32),
    }))
    assert np.isfinite(float(metrics["loss"]))


if __name__ == "__main__":
    # python tests/test_storage.py <dir>: write the fixtures with
    # whatever tree is on sys.path (public API only).
    os.makedirs(sys.argv[1], exist_ok=True)
    for name in LAYOUTS:
        tr = _trainer(name, 4)
        with open(os.path.join(sys.argv[1], f"{name}.pkl"), "wb") as f:
            f.write(_snapshot_bytes(tr, _trained(tr)))
