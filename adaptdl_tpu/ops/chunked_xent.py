"""Chunked softmax cross-entropy: LM loss without the logits tensor.

The output head of a tied-embedding LM computes
``logits = x @ E^T`` with ``x: [tokens, d]`` and ``E: [vocab, d]``,
then a softmax cross-entropy over the vocab axis. Materializing
``[tokens, vocab]`` logits is routinely the single largest HBM
allocation of the whole training step (8x1024 tokens x 32k vocab in
f32 = 1 GiB), and XLA cannot elide it through ``optax``'s reduction.

This op streams the vocab axis in chunks through an online
logsumexp — ``O(tokens x chunk)`` live memory instead of
``O(tokens x vocab)`` — with each chunk's ``x @ E_c^T`` still a
full-width MXU matmul. The backward pass (``jax.custom_vjp``)
recomputes each chunk's probabilities from the saved per-row
logsumexp and accumulates ``dx`` / ``dE`` chunkwise, so backward
memory is bounded the same way. The classic trade: ~2x head FLOPs
for a vocab-factor memory reduction — on TPU the freed HBM buys a
larger batch, which buys MFU.

The reference has no equivalent (its loss layer is
``torch.nn.CrossEntropyLoss`` over materialized logits, e.g.
reference examples/transformer/ — SURVEY.md §2.6); this is a
TPU-native capability extension in the same spirit as the flash
attention kernel: keep the hot op's working set inside the fast
memory tier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _pad_chunks(embedding: jnp.ndarray, chunk_size: int):
    """[vocab, d] -> ([num_chunks, chunk, d], padded_rows)."""
    vocab, d = embedding.shape
    chunk_size = min(chunk_size, vocab)
    pad = (-vocab) % chunk_size
    if pad:
        embedding = jnp.concatenate(
            [embedding, jnp.zeros((pad, d), embedding.dtype)], axis=0
        )
    return (
        embedding.reshape(-1, chunk_size, embedding.shape[-1]),
        pad,
    )


def _chunk_mask(chunk_idx, chunk_size, vocab, rows):
    """[rows, chunk] True where the chunk column is a real vocab id."""
    cols = chunk_idx * chunk_size + jnp.arange(chunk_size)
    return jnp.broadcast_to(cols[None, :] < vocab, (rows, chunk_size))


def _is_target(chunk_idx, chunk_size, targets):
    """[rows, chunk] True at each row's target column, where this chunk
    holds it: an elementwise compare that fuses into the chunk's
    softmax pass, where a gather of ``embedding[targets]`` and a
    scatter into the table's gradient are a row at a time on a TPU."""
    cols = chunk_idx * chunk_size + jnp.arange(chunk_size)
    return cols[None, :] == targets[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_softmax_xent(
    x: jnp.ndarray,
    embedding: jnp.ndarray,
    targets: jnp.ndarray,
    chunk_size: int = 4096,
) -> jnp.ndarray:
    """Per-token cross-entropy of ``softmax(x @ embedding^T)``.

    Args:
      x: ``[tokens, d]`` final hidden states (any float dtype;
        accumulated in f32).
      embedding: ``[vocab, d]`` tied output embedding table.
      targets: ``[tokens]`` int32 target ids.
      chunk_size: vocab rows per streamed chunk (the live-memory
        knob; keep it a multiple of 128 for MXU-aligned matmuls).

    Returns:
      ``[tokens]`` f32 losses: ``logsumexp_v(x@E^T) - (x@E^T)[target]``.
    """
    loss, _ = _xent_fwd_impl(x, embedding, targets, chunk_size)
    return loss


def _xent_fwd_impl(x, embedding, targets, chunk_size):
    tokens, d = x.shape
    vocab = embedding.shape[0]
    # Operands in x's dtype — a table chunk is rounded to it as it is
    # read (bf16 on TPU keeps the MXU at full rate, and no O(vocab x
    # d) copy of a float32 table is ever made); every dot ACCUMULATES
    # in f32 via preferred_element_type, and the softmax arithmetic
    # runs on the f32 products.
    chunks, _ = _pad_chunks(embedding, chunk_size)
    chunk_size = chunks.shape[1]

    def fold(carry, inp):
        m, s, target_logit = carry
        idx, e_chunk = inp
        logits = jnp.einsum(
            "td,kd->tk", x, e_chunk.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )  # [tokens, chunk] — the live buffer
        target_logit = target_logit + jnp.sum(
            jnp.where(_is_target(idx, chunk_size, targets), logits, 0.0),
            axis=-1,
        )
        logits = jnp.where(
            _chunk_mask(idx, chunk_size, vocab, tokens), logits, NEG_INF
        )
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1
        )
        return (m_new, s, target_logit), None

    # Derive the accumulator init arithmetically from x so it inherits
    # x's varying-axis type under shard_map (the trainer's data/seq
    # axes) — a literal zeros array would be typed unvarying and fail
    # the scan's carry check (same pattern as ring_attention.py).
    zero_rows = jnp.sum(x * 0.0, axis=-1).astype(jnp.float32)
    init = (zero_rows + NEG_INF, zero_rows, zero_rows)
    (m, s, target_logit), _ = lax.scan(
        fold, init, (jnp.arange(chunks.shape[0]), chunks)
    )
    lse = m + jnp.log(jnp.maximum(s, 1e-30))
    return lse - target_logit, lse


def _xent_vjp_fwd(x, embedding, targets, chunk_size):
    loss, lse = _xent_fwd_impl(x, embedding, targets, chunk_size)
    return loss, (x, embedding, targets, lse)


def _xent_vjp_bwd(chunk_size, residuals, g):
    """dL/dx = diag(g) (P - onehot) @ E;  dL/dE = (P - onehot)^T
    diag(g) x — all accumulated chunkwise from recomputed
    probabilities P_c = exp(x E_c^T - lse), the one-hot of the targets
    taken off inside the chunk that holds each.
    """
    x, embedding, targets, lse = residuals
    tokens, d = x.shape
    vocab = embedding.shape[0]
    g32 = g.astype(jnp.float32)
    # Same mixed-precision policy as forward: operands in x's dtype,
    # dots accumulate in f32; dE leaves in the TABLE's dtype (float32
    # for a float32 table: the gradient is not rounded on the way).
    chunks, pad = _pad_chunks(embedding, chunk_size)
    chunk_size = chunks.shape[1]

    def chunk_grads(dx_acc, inp):
        idx, e_chunk = inp
        e_chunk = e_chunk.astype(x.dtype)
        logits = jnp.einsum(
            "td,kd->tk", x, e_chunk,
            preferred_element_type=jnp.float32,
        )
        logits = jnp.where(
            _chunk_mask(idx, chunk_size, vocab, tokens), logits, NEG_INF
        )
        p = jnp.exp(logits - lse[:, None])  # [tokens, chunk] f32
        gp = g32[:, None] * (
            p - _is_target(idx, chunk_size, targets).astype(jnp.float32)
        )
        dx_acc = dx_acc + jnp.einsum(
            "tk,kd->td", gp, e_chunk,
            preferred_element_type=jnp.float32,
        )
        de_chunk = jnp.einsum(
            "tk,td->kd", gp, x,
            preferred_element_type=jnp.float32,
        )  # [chunk, d]
        return dx_acc, de_chunk

    dx, de_chunks = lax.scan(
        chunk_grads,
        # varying-typed zeros (see forward scan note), f32 accumulator
        (x * 0.0).astype(jnp.float32),
        (jnp.arange(chunks.shape[0]), chunks),
    )
    de = de_chunks.reshape(-1, d)
    if pad:
        de = de[:vocab]
    return dx.astype(x.dtype), de.astype(embedding.dtype), None


chunked_softmax_xent.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)


def chunked_lm_loss_fn(model, chunk_size: int = 4096):
    """Next-token LM loss streaming the vocab axis — a drop-in
    alternative to ``adaptdl_tpu.models.lm_loss_fn`` for large-vocab
    models. The model runs with ``return_hidden=True`` (no logits
    tensor exists anywhere in the step); the tied embedding table is
    read from the params tree. batch = {"tokens": [b, s+1] int32}.
    """

    def loss_fn(params, batch, rng):
        from adaptdl_tpu.models.transformer import apply_with_moe_aux

        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        hidden, aux = apply_with_moe_aux(
            model, params, inputs, rng, return_hidden=True
        )
        flat = hidden.reshape(-1, hidden.shape[-1])
        losses = chunked_softmax_xent(
            flat,
            params["embed"]["embedding"],
            targets.reshape(-1),
            chunk_size,
        )
        return losses.mean() + aux

    return loss_fn
