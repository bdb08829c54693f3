"""Streamed (row-chunked) cross-entropy correctness tests."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from adaptdl_tpu.ops.chunked_xent import (
    chunked_lm_loss_fn,
    chunked_softmax_xent,
    weighted_xent_sum,
)


def _dense_xent(x, embedding, targets):
    logits = x.astype(jnp.float32) @ embedding.astype(jnp.float32).T
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, targets
    )


def _inputs(tokens=24, d=16, vocab=50, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(tokens, d)).astype(np.float32))
    emb = jnp.asarray(rng.normal(size=(vocab, d)).astype(np.float32))
    tgt = jnp.asarray(rng.integers(0, vocab, size=tokens), jnp.int32)
    return x, emb, tgt


@pytest.mark.parametrize("chunk", [8, 16, 50, 64, 4096])
def test_matches_dense_xent(chunk):
    """Every chunking of the 120 rows (dividing at 8; a padded last
    chunk at 16, 50 and 64; one chunk) reproduces the dense loss."""
    x, emb, tgt = _inputs(tokens=120)
    got = chunked_softmax_xent(x, emb, tgt, chunk)
    want = _dense_xent(x, emb, tgt)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("chunk", [16, 50, 64])
def test_gradients_match_dense(chunk):
    x, emb, tgt = _inputs(tokens=120)

    def chunked_loss(x, emb):
        return chunked_softmax_xent(x, emb, tgt, chunk).mean()

    def dense_loss(x, emb):
        return _dense_xent(x, emb, tgt).mean()

    gx_c, ge_c = jax.jit(jax.grad(chunked_loss, argnums=(0, 1)))(x, emb)
    gx_d, ge_d = jax.jit(jax.grad(dense_loss, argnums=(0, 1)))(x, emb)
    np.testing.assert_allclose(
        np.asarray(gx_c), np.asarray(gx_d), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(ge_c), np.asarray(ge_d), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("cotangent", [1.0, 3.0])
@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [8, 9, 16, 4096])
def test_weighted_sum_matches_dense(chunk, x_dtype, cotangent):
    """The scalar entry point against ``sum(w * dense_xent)``: value,
    per-row losses, and the gradients by ``x``, the table AND the
    weights, under a cotangent that is not 1, at chunks that divide
    the 24 rows and that do not (9, 16: a padded last chunk)."""
    x, emb, tgt = _inputs()
    x = x.astype(x_dtype)
    w = jnp.asarray(
        np.random.default_rng(3).uniform(0.1, 1.0, size=x.shape[0]),
        jnp.float32,
    )

    def streamed(x, emb, w):
        return cotangent * weighted_xent_sum(x, emb, tgt, w, chunk)[0]

    def dense(x, emb, w):
        return cotangent * jnp.sum(w * _dense_xent(x, emb, tgt))

    got, xent = weighted_xent_sum(x, emb, tgt, w, chunk)
    tol = 1e-5 if x_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(xent), np.asarray(_dense_xent(x, emb, tgt)),
        rtol=tol, atol=tol,
    )
    np.testing.assert_allclose(
        float(got), float(dense(x, emb, w)) / cotangent, rtol=tol
    )
    grads = jax.jit(jax.grad(streamed, argnums=(0, 1, 2)))(x, emb, w)
    wants = jax.jit(jax.grad(dense, argnums=(0, 1, 2)))(x, emb, w)
    assert [g.dtype for g in grads] == [x_dtype, jnp.float32, jnp.float32]
    for g, want in zip(grads, wants):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(want, np.float32),
            rtol=10 * tol, atol=tol,
        )


def test_weighted_sum_per_row_output_carries_no_gradient():
    """The second output is for counters: differentiating through it
    gives zeros, not a second head."""
    x, emb, tgt = _inputs()
    w = jnp.full(x.shape[:1], 0.5, jnp.float32)
    gx = jax.grad(
        lambda x: weighted_xent_sum(x, emb, tgt, w, 8)[1].sum()
    )(x)
    assert not np.asarray(gx).any()


def _vocab_products(jaxpr, vocab):
    """``(in the top-level jaxpr, inside scans)`` counts of the
    ``dot_general`` equations with an operand or a result of a
    ``vocab``-sized dimension, sub-jaxprs walked."""
    outside = inside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
            vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars)
        ):
            outside += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            below = sum(_vocab_products(sub, vocab))
            if eqn.primitive.name == "scan":
                inside += below
            else:
                outside += below
    return outside, inside


@pytest.mark.parametrize("loss", ["chunked_lm", "looped_lm"])
def test_gradient_of_the_loss_holds_three_products_against_the_table(loss):
    """The mechanism engages: in the gradient of a loss that knows its
    rows' weights the table meets a ``dot_general`` exactly three
    times — logits, dx, dE, all inside the ONE scan over the row
    chunks — and no product of the vocabulary's size stands outside
    it, where the column stream's backward held a fourth."""
    from adaptdl_tpu.models import TransformerConfig, init_transformer
    from adaptdl_tpu.models.transformer import looped_lm_loss_fn

    vocab = 96  # no other dimension of the model
    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=1, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=16, dtype=jnp.float32, remat=False,
        tie_embeddings=loss == "chunked_lm",
        loop_passes=4 if loss == "looped_lm" else 1,
    )
    model, params = init_transformer(cfg, seq_len=8)
    tokens = jnp.zeros((4, 9), jnp.int32)
    if loss == "chunked_lm":
        loss_fn = chunked_lm_loss_fn(model, chunk_size=8)
        batch = {"tokens": tokens}
    else:
        looped = looped_lm_loss_fn(model, chunk_size=8)
        loss_fn = lambda *a: looped(*a)[0]  # noqa: E731
        batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    jaxpr = jax.make_jaxpr(jax.grad(loss_fn))(
        params, batch, jax.random.key(0)
    )
    assert _vocab_products(jaxpr.jaxpr, vocab) == (0, 3)
    # The per-row entry point, for the contrast: its backward streams
    # the rows again, the logits' product with them.
    x, emb, tgt = _inputs(vocab=vocab)
    per_row = jax.make_jaxpr(
        jax.grad(lambda x: chunked_softmax_xent(x, emb, tgt, 8).mean())
    )(x)
    assert _vocab_products(per_row.jaxpr, vocab) == (0, 4)


def test_bf16_hidden_states():
    """bf16 activations (the TPU training dtype) accumulate in f32;
    gradients come back in the input dtypes."""
    x, emb, tgt = _inputs()
    x16 = x.astype(jnp.bfloat16)

    def loss(x, emb):
        return chunked_softmax_xent(x, emb, tgt, 16).mean()

    val = loss(x16, emb)
    ref = _dense_xent(x16, emb, tgt).mean()
    assert float(abs(val - ref)) < 1e-2
    gx, ge = jax.grad(loss, argnums=(0, 1))(x16, emb)
    assert gx.dtype == jnp.bfloat16
    assert ge.dtype == jnp.float32


def test_chunked_lm_loss_matches_dense_lm_loss():
    """The drop-in loss factory reproduces models.lm_loss_fn on the
    flagship transformer — loss value AND parameter gradients."""
    from adaptdl_tpu.models import (
        TransformerConfig,
        init_transformer,
        lm_loss_fn,
    )

    cfg = TransformerConfig(
        vocab_size=96, num_layers=2, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=32, dtype=jnp.float32, remat=False,
    )
    model, params = init_transformer(cfg, seq_len=16)
    rng = np.random.default_rng(1)
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, 96, size=(4, 17)), jnp.int32
        )
    }
    key = jax.random.key(0)
    dense = lm_loss_fn(model)
    chunked = chunked_lm_loss_fn(model, chunk_size=32)
    l_dense, g_dense = jax.value_and_grad(dense)(params, batch, key)
    l_chunk, g_chunk = jax.value_and_grad(chunked)(params, batch, key)
    assert float(l_chunk) == pytest.approx(float(l_dense), rel=1e-5)
    for pd, pc in zip(
        jax.tree.leaves(g_dense), jax.tree.leaves(g_chunk)
    ):
        np.testing.assert_allclose(
            np.asarray(pc), np.asarray(pd), rtol=1e-4, atol=1e-5
        )


def test_chunked_loss_trains_under_elastic_trainer():
    """End-to-end: the chunked loss drives the fused elastic step on a
    data-parallel mesh and the loss decreases."""
    from adaptdl_tpu.models import TransformerConfig, init_transformer
    from adaptdl_tpu.parallel import create_mesh
    from adaptdl_tpu.trainer import ElasticTrainer

    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=16, dtype=jnp.float32, remat=False,
    )
    model, params = init_transformer(cfg, seq_len=8)
    mesh = create_mesh({"data": 2}, devices=jax.devices()[:2])
    trainer = ElasticTrainer(
        chunked_lm_loss_fn(model, chunk_size=32),
        params,
        optax.adam(1e-2),
        4,
        mesh=mesh,
    )
    state = trainer.init_state()
    step = trainer.train_step(2, 0)
    rng = np.random.default_rng(2)
    batch = trainer.shard_batch(
        {
            "tokens": rng.integers(
                0, 64, size=(4, 9), dtype=np.int32
            )
        }
    )
    state, m0 = step(state, batch)
    for _ in range(20):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])
