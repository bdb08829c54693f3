"""Streamed softmax cross-entropy: LM loss without the logits tensor.

The output head of an LM computes ``logits = x @ E^T`` with
``x: [rows, d]`` and ``E: [vocab, d]``, then a softmax cross-entropy
over the vocab axis. Materializing ``[rows, vocab]`` logits is
routinely the single largest HBM allocation of the whole training step
(8x1024 tokens x 32k vocab in f32 = 1 GiB), and XLA cannot elide it
through ``optax``'s reduction.

This op streams the ROWS: a chunk of ``chunk_size`` rows against the
WHOLE table, so the live buffer is ``[chunk_size, vocab]`` float32
and a row's log-sum-exp is final inside its chunk. That is what lets
the gradients be formed in the same pass: with a row's weight ``w``
in the scalar ``sum_i w_i xent_i`` known,
``gp = w (softmax(logits) - onehot)``, ``dx = gp @ E`` and
``dE += gp^T @ x`` need nothing a later chunk brings. Three
full-width MXU products a chunk, which is the count of a materialized
head; none is run twice.

Two entry points over the one core (``_stream_rows``):

- ``weighted_xent_sum(x, table, targets, weights, chunk_size)`` for a
  caller that HAS the rows' weights before the head runs (a mean:
  ``1 / rows``; a looped model: its exit distribution). Its forward
  rule forms ``dx`` and ``dE``; its backward is a multiplication by
  the scalar cotangent.
- ``chunked_softmax_xent(x, table, targets, chunk_size)`` returns the
  per-row losses for a caller that weights them afterwards. The rows'
  cotangent only exists in the backward, so its VJP runs the core a
  second time with ``weights = g``: the logits' product twice.

The reference has no equivalent (its loss layer is
``torch.nn.CrossEntropyLoss`` over materialized logits, e.g.
reference examples/transformer/ — SURVEY.md §2.6); this is a
TPU-native capability extension in the same spirit as the flash
attention kernel: keep the hot op's working set bounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def rows_per_chunk(rows: int, chunk_size: int) -> int:
    """How many rows a chunk of the stream holds: ``chunk_size`` of
    them, or all where there are fewer."""
    return min(chunk_size, rows)


def _vary_together(*arrays):
    """The arrays, each cast to vary over every mesh axis any of them
    varies over under a ``shard_map`` (the trainer's: the table may be
    replicated over the data axis where ``x`` is not). A scan's carry
    has to enter with the type its body gives back, where a literal
    zeros array is typed unvarying; and a ``custom_vjp``'s cotangents
    have to have their primals' types, where a gradient formed from
    ``x`` varies as ``x`` does. Outside a ``shard_map``: as they
    were."""
    varying = frozenset().union(*(jax.typeof(a).vma for a in arrays))
    return tuple(
        lax.pcast(a, tuple(missing), to="varying")
        if (missing := varying - jax.typeof(a).vma) else a
        for a in arrays
    )


def _stream_rows(x, table, targets, weights, chunk_size):
    """The one core. ``chunk_size`` rows at a time against the whole
    table: per-row ``xent = logsumexp_v(x @ E^T) - (x @ E^T)[target]``
    in float32 and, where ``weights`` [rows] is given, the gradients
    of ``sum(weights * xent)``: ``dx`` in ``x``'s dtype and ``dE`` in
    float32 (else ``None``, ``None``).

    Operands in ``x``'s dtype — the table is rounded to it once (bf16
    on TPU keeps the MXU at full rate) — every dot ACCUMULATES in f32
    via ``preferred_element_type``, and the softmax arithmetic runs on
    the f32 products. The target's logit comes off an elementwise
    compare that fuses into the chunk's softmax pass, where a gather
    of ``table[targets]`` and a scatter into the table's gradient are
    a row at a time on a TPU.
    """
    rows, d = x.shape
    vocab = table.shape[0]
    chunk = rows_per_chunk(rows, chunk_size)
    pad = (-rows) % chunk
    with_grads = weights is not None
    e = table.astype(x.dtype)
    cols = jnp.arange(vocab)

    def chunks_of(a):
        # Rows that do not fill the last chunk are zeros: weight 0.
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape(-1, chunk, *a.shape[1:])

    def body(de, inp):
        x_c, t_c = inp[:2]
        logits = jnp.einsum(
            "td,kd->tk", x_c, e, preferred_element_type=jnp.float32
        )  # [chunk, vocab] — the live buffer
        is_target = cols[None, :] == t_c[:, None]
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        xent = lse - jnp.sum(jnp.where(is_target, logits, 0.0), axis=-1)
        if not with_grads:
            return de, (xent,)
        w_c = inp[2].astype(jnp.float32)
        gp = w_c[:, None] * (
            jnp.exp(logits - lse[:, None]) - is_target.astype(jnp.float32)
        )
        # Written once, in the operands' dtype, for both products: left
        # to itself XLA fuses this expression into each product as a
        # prologue and re-reads the float32 logits for every tile of
        # the output's width (on a v5e, 2048 rows x 49152: dE 3.65 ms
        # a chunk that way, 2.51 + 0.87 for this pass so).
        gp = lax.optimization_barrier(gp.astype(x.dtype))
        dx_c = jnp.einsum(
            "tk,kd->td", gp, e, preferred_element_type=jnp.float32
        )
        de = de + jnp.einsum(
            "tk,td->kd", gp, x_c, preferred_element_type=jnp.float32
        )
        return de, (xent, dx_c.astype(x.dtype))

    if not with_grads:
        _, (xent,) = lax.scan(body, None, (chunks_of(x), chunks_of(targets)))
        return xent.reshape(-1)[:rows], None, None
    de0 = _vary_together(
        jnp.zeros((vocab, d), jnp.float32), x, table, targets, weights
    )[0]
    de, (xent, dx) = lax.scan(
        body, de0, (chunks_of(x), chunks_of(targets), chunks_of(weights))
    )
    return xent.reshape(-1)[:rows], dx.reshape(-1, d)[:rows], de


def weighted_xent_sum(
    x: jnp.ndarray,
    table: jnp.ndarray,
    targets: jnp.ndarray,
    weights: jnp.ndarray,
    chunk_size: int = 2048,
):
    """``sum_i weights_i * xent_i`` of ``softmax(x @ table^T)``, with
    its gradients formed in the forward pass.

    Args:
      x: ``[rows, d]`` final hidden states (any float dtype;
        accumulated in f32).
      table: ``[vocab, d]`` output table.
      targets: ``[rows]`` int32 target ids.
      weights: ``[rows]`` float weight of each row's loss in the sum
        (``1 / rows`` for a mean). Differentiable: its cotangent is
        the row's loss.
      chunk_size: ROWS per streamed chunk (the live-memory knob:
        ``chunk_size x vocab`` float32 logits; keep it a multiple of
        128 for MXU-aligned matmuls).

    Returns:
      ``(sum, xent)``: the float32 scalar, and the ``[rows]`` f32
      per-row losses for counters only — ``xent`` carries NO gradient
      (differentiate the sum, or ``chunked_softmax_xent``).
    """
    x, table, weights = _vary_together(x, table, weights)
    return _xent_sum(x, table, targets, weights, chunk_size)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _xent_sum(x, table, targets, weights, chunk_size):
    xent, _, _ = _stream_rows(x, table, targets, None, chunk_size)
    return jnp.sum(weights * xent), lax.stop_gradient(xent)


def _sum_vjp_fwd(x, table, targets, weights, chunk_size):
    xent, dx, de = _stream_rows(x, table, targets, weights, chunk_size)
    # dE leaves in the TABLE's dtype (float32 for a float32 table: the
    # gradient is not rounded on the way).
    return (
        (jnp.sum(weights * xent), xent),
        (dx, de.astype(table.dtype), xent.astype(weights.dtype)),
    )


def _sum_vjp_bwd(chunk_size, residuals, cotangents):
    dx, de, xent = residuals
    g, _ = cotangents
    return (
        (g * dx).astype(dx.dtype), (g * de).astype(de.dtype), None, g * xent
    )


_xent_sum.defvjp(_sum_vjp_fwd, _sum_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_softmax_xent(
    x: jnp.ndarray,
    table: jnp.ndarray,
    targets: jnp.ndarray,
    chunk_size: int = 2048,
) -> jnp.ndarray:
    """Per-row cross-entropy of ``softmax(x @ table^T)``: ``[rows]``
    f32 losses, ``logsumexp_v(x@E^T) - (x@E^T)[target]``. Arguments as
    ``weighted_xent_sum``, without the weights: the rows' cotangent
    arrives in the backward, which therefore streams the rows again
    (the logits' product a second time). A caller that knows the rows'
    weights beforehand takes ``weighted_xent_sum``.
    """
    return _stream_rows(x, table, targets, None, chunk_size)[0]


def _xent_vjp_fwd(x, table, targets, chunk_size):
    xent, _, _ = _stream_rows(x, table, targets, None, chunk_size)
    return xent, (x, table, targets)


def _xent_vjp_bwd(chunk_size, residuals, g):
    x, table, targets = residuals
    _, dx, de = _stream_rows(x, table, targets, g, chunk_size)
    return dx, de.astype(table.dtype), None


chunked_softmax_xent.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)


def chunked_lm_loss_fn(model, chunk_size: int = 2048):
    """Next-token LM loss streaming the head ``chunk_size`` rows at a
    time — a drop-in alternative to ``adaptdl_tpu.models.lm_loss_fn``
    for large-vocab models. The model runs with ``return_hidden=True``
    (no ``[tokens, vocab]`` tensor exists anywhere in the step); the
    tied embedding table is read from the params tree; the mean's
    weights are known, so the head's gradients are formed in the
    forward pass. batch = {"tokens": [b, s+1] int32}.
    """

    def loss_fn(params, batch, rng):
        from adaptdl_tpu.models.transformer import apply_with_moe_aux

        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        hidden, aux = apply_with_moe_aux(
            model, params, inputs, rng, return_hidden=True
        )
        flat = hidden.reshape(-1, hidden.shape[-1])
        mean, _ = weighted_xent_sum(
            flat,
            params["embed"]["embedding"],
            targets.reshape(-1),
            jnp.full(flat.shape[:1], 1.0 / flat.shape[0], jnp.float32),
            chunk_size,
        )
        return mean + aux

    return loss_fn
