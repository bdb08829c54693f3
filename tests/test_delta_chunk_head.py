"""The delta rule's chunk kernels for ONE decay a head (PR 50):
``delta_chunk_head_fwd`` / ``delta_chunk_head_bwd``
(``ops/kda.py:_head_work``), which ``kda`` runs where ``g`` comes
``[batch, seq, heads]``, against the XLA ``_prepare`` and the
per-channel kernel pair on the decay broadcast over the channels,
against the recurrence token by token, at a decay no float32 inverse
holds, and that a decay a channel still runs the pair it ran. Every
shape tiny: the kernels run interpreted here."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from configurations import rel

from adaptdl_tpu import trace
from adaptdl_tpu.ops import kda as kda_op

SCALE = 0.3


def _inputs(seed, batch=1, seq=32, key_heads=1, heads=2, dk=8, dv=8,
            dtype=jnp.float32, decay=0.5):
    keys = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(keys[0], (batch, seq, key_heads, dk))
    k = jax.random.normal(keys[1], (batch, seq, key_heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, seq, heads, dv))
    g = -decay * jnp.exp(jax.random.normal(keys[3], (batch, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, heads)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _blocks(args, chunk):
    """``kda``'s operands as the chunk kernels take them: q, k (each
    key head on its value heads), v ``[b * h, chunks, C, w]``, g and
    beta ``[b * h, chunks, 1, C]``, the row padded with tokens that
    leave the state as it is."""
    q, k, v, g, beta = args
    batch, seq, heads, _ = v.shape
    chunks = -(-seq // chunk)
    q, k = (jnp.repeat(x, heads // x.shape[2], axis=2) for x in (q, k))

    def rows(x):
        x = jnp.swapaxes(x, 1, 2)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, chunks * chunk - seq), (0, 0)))
        return x.reshape(batch * heads, chunks, chunk, x.shape[-1])

    def across(x):
        return rows(x[..., None]).reshape(batch * heads, chunks, 1, chunk)

    return rows(q), rows(k), rows(v), across(g), across(beta)


def _a_channel(g, dk):  # [bh, chunks, 1, C] -> [bh, chunks, C, dk]
    return jnp.broadcast_to(
        jnp.swapaxes(g, 2, 3), g.shape[:2] + (g.shape[3], dk)
    )


def _head_pair(q, k, v, g, beta):
    return kda_op._head_work(SCALE, q, k, v, g, beta)


def _channel_pair(q, k, v, g, beta):
    return kda_op._own_work(SCALE, q, k, v, _a_channel(g, q.shape[-1]), beta)


def _prepare(q, k, v, g, beta):
    bh, chunks, chunk, dk = q.shape
    outs = kda_op._prepare(
        *(x.reshape((bh * chunks,) + x.shape[2:])
          for x in (q, k, v, _a_channel(g, dk))),
        beta.reshape(bh * chunks, chunk), chunk, SCALE,
    )
    return tuple(
        x.reshape((bh, chunks) + x.shape[1:]) for x in outs[:5]
    ) + (outs[5].reshape(bh, chunks, 1, dk),)


def _results_and_grads(fn, operands, cotangents):
    """-> (the six results, the five operands' gradients under
    ``cotangents`` of all six) as one compiled program."""
    def loss(*operands):
        return sum(
            jnp.sum(x.astype(jnp.float32) * c)
            for x, c in zip(fn(*operands), cotangents)
        )

    return jax.jit(
        lambda *operands: (
            fn(*operands), jax.grad(loss, tuple(range(5)))(*operands)
        )
    )(*operands)


def _cotangents(operands):
    """Random, and numbers the results' own dtype holds."""
    return [
        jax.random.normal(jax.random.key(30 + i), x.shape)
        .astype(x.dtype).astype(jnp.float32)
        for i, x in enumerate(jax.eval_shape(_head_pair, *operands))
    ]


def _rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(
        np.sqrt(np.mean((got - want) ** 2))
        / max(np.sqrt(np.mean(want ** 2)), 1e-30)
    )


@pytest.mark.parametrize(
    "dtype,chunk,seq",
    [
        ("float32", 16, 32),  # the chunk divides the row
        ("float32", 64, 100),  # the last chunk padded
        ("bfloat16", 16, 40),  # padded
        ("bfloat16", 64, 128),
    ],
)
def test_head_kernels_equal_the_own_work_on_a_broadcast_decay(
    dtype, chunk, seq
):
    """``delta_chunk_head_fwd`` / ``_bwd`` (interpret mode), one key
    head for two value heads: the six results and the five gradients
    (g's ``[bh, chunks, 1, C]``, a head's) are ``_prepare``'s and its
    autodiff's on the decay broadcast over the channels (g's gradient
    there summed over them) and the per-channel pair's within float32
    rounding. In bfloat16 every result and gradient is no farther from
    a float32 run of the same numbers than the per-channel pair's: the
    forward takes ``[K; Q] K^T`` of the operands as they came (no
    decayed k is rounded), the backward its cotangent in two bf16
    parts."""
    operands = _blocks(_inputs(5, seq=seq, dtype=jnp.dtype(dtype)), chunk)
    cotangents = _cotangents(operands)
    run = functools.partial(_results_and_grads, cotangents=cotangents)
    head, channel = (run(fn, operands) for fn in (_head_pair, _channel_pair))
    # (A float32 run of the same numbers: what ``_prepare`` gives.)
    xla = run(_prepare, tuple(x.astype(jnp.float32) for x in operands))
    for got, other, want in zip(*(jax.tree.leaves(x) for x in
                                  (head, channel, xla))):
        assert got.dtype == other.dtype and got.shape == want.shape
        if dtype == "float32":
            assert rel(got, want) < 2e-5 and rel(got, other) < 2e-5
        else:
            assert _rms(got, want) <= 1.02 * _rms(other, want) + 1e-6
            assert rel(got, want) < 2e-2
    assert head[1][3].shape == operands[3].shape  # dg: a head's


def test_kda_with_a_decay_a_head_runs_the_head_kernels(monkeypatch):
    """``kda(g [b, s, h])``, 2 key heads for 4 value heads on a padded
    row: the recurrence token by token, value and every gradient
    (``dg`` ``[b, s, h]``), through the head's kernel pair and neither
    the per-channel one nor the XLA ``_prepare``; the event says so."""
    def refuse(*_):
        raise AssertionError("a decay a head in the per-channel body")

    monkeypatch.setattr(kda_op, "_own_work", refuse)
    monkeypatch.setattr(kda_op, "_prepare", refuse)
    args = _inputs(7, batch=2, seq=40, key_heads=2, heads=4)
    run = functools.partial(kda_op.kda, chunk=16)

    def weighted(fn):
        def loss(*a):
            out = fn(*a).astype(jnp.float32)
            return jnp.sum(
                out * jnp.cos(jnp.arange(out.size)).reshape(out.shape)
            )

        return jax.jit(jax.value_and_grad(loss, tuple(range(5))))(*args)

    since = len(trace.snapshot_spans())
    got, want = weighted(run), weighted(kda_op.kda_recurrent)
    assert got[1][3].shape == args[3].shape
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and rel(a, b) < 2e-5
    attrs = [
        r for r in trace.snapshot_spans()[since:]
        if r["name"] == "kda.schedule"
    ][0]["attrs"]
    assert (attrs["decay"], attrs["path"]) == ("head", "kernel")
    assert attrs["own_work"] == (
        "pallas:delta_chunk_head_fwd,delta_chunk_head_bwd"
    )
    assert "delta_chunk_head_bwd" in attrs["backward"]
    assert attrs["sub_block"] == attrs["chunk"] == 16  # no level left


def test_head_kernels_survive_a_decay_no_float32_inverse_holds():
    """A chunk whose decay sum is far below -100 (e^-40 a token:
    ``e^{-G}`` overflows float32): every result and gradient finite,
    ``e^{G_C}`` exactly 0, what ``_prepare`` gives — no exponent of a
    positive number is taken (the upper triangle's are clamped before
    the exponential, then masked)."""
    operands = _blocks(_inputs(6, seq=64, decay=40.0), 32)
    assert float(operands[3].sum(axis=-1).max()) < -100.0
    cotangents = _cotangents(operands)
    got, want = (
        _results_and_grads(fn, operands, cotangents)
        for fn in (_head_pair, _prepare)
    )
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(got))
    assert float(jnp.abs(got[0][5]).max()) == 0.0
    # (At e^-40 a token what is left of a product is differences of
    # terms many times its size, in either program.)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel(a, b) < 1e-3


def _kernel_names(fn, *args):
    """The names of the ``pallas_call``s in ``fn``'s jaxpr, jitted
    functions and custom rules opened."""
    found = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.add(eqn.params["name"])
            for value in eqn.params.values():
                for sub in jax.tree.leaves(
                    value, is_leaf=lambda x: hasattr(x, "eqns")
                    or hasattr(x, "jaxpr")
                ):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("rank", [3, 4])
def test_the_rank_of_g_chooses_the_chunk_kernels(rank):
    """A decay a channel (``g`` of rank 4) still traces
    ``delta_chunk_fwd`` / ``delta_chunk_bwd`` and no ``_head_`` kernel;
    one a head the head's pair and not the per-channel one. The state
    kernels are the same two either way."""
    q, k, v, g, beta = _inputs(8, seq=32, heads=1)
    if rank == 4:
        g = jnp.broadcast_to(g[..., None], v.shape[:3] + (q.shape[-1],))

    def loss(*a):
        return kda_op.kda(*a, chunk=16).astype(jnp.float32).sum()

    names = _kernel_names(
        jax.grad(loss, tuple(range(5))), q, k, v, g, beta
    )
    own = {"delta_chunk_head_fwd", "delta_chunk_head_bwd"} if rank == 3 else {
        "delta_chunk_fwd", "delta_chunk_bwd"
    }
    assert names == own | {"kda_fwd", "kda_bwd"}
