"""Ulysses-style all-to-all sequence parallelism (head-scatter).

The second sequence-parallel mode, complementing ring attention
(``adaptdl_tpu.parallel.ring_attention``). Both run over the same
``"seq"`` mesh axis and are drop-in values for
``TransformerConfig.attention_fn``; they differ in communication
pattern:

- **ring**: K/V blocks rotate with ``lax.ppermute`` — ``seq_shards``
  neighbor hops per attention, memory O(seq/shards) everywhere, works
  for any head count. Best at very long sequences where even one
  device's full-sequence K/V would not fit.
- **ulysses**: two ``lax.all_to_all`` exchanges swap the sharded axis
  from sequence to heads around a *local* full-sequence attention
  (pattern from the DeepSpeed-Ulysses literature; implementation
  original). Each device then attends over the whole sequence for
  ``heads/shards`` heads: one fused attention matmul per step instead
  of a ``shards``-step scan, which keeps the MXU busier and lets the
  within-chip flash kernel (``adaptdl_tpu.ops.flash_attention``)
  handle the full sequence. Requires ``num_heads % seq_shards == 0``
  and O(seq) K/V memory per device for its head slice.

On TPU the all_to_all rides ICI as a single fused collective, so for
moderate sequence lengths (fits-in-HBM per head slice) ulysses is
usually the faster mode; ring wins when sequence length per device is
the binding constraint. The scheduler prices both through the same
fitted ``seq_shards`` network term (adaptdl_tpu/goodput.py) — the fit
observes whichever mode the job runs.

The reference has no sequence parallelism at all (SURVEY.md §5: its
only sequence handling is BPTT-window data parallelism,
adaptdl/adaptdl/torch/iterator.py:87-97); like ring attention this is
a TPU-native capability extension.
"""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp
from jax import lax

from adaptdl_tpu.parallel.mesh import SEQ_AXIS


def ulysses_attention(
    q,
    k,
    v,
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
    inner_attention=None,
):
    """Exact attention across a sequence-sharded axis via all_to_all.

    Args:
      q, k, v: local blocks ``[batch, heads, seq_local, head_dim]``
        with the FULL head count (parameters are replicated over the
        seq axis) and ``seq_local = seq / axis_size``.
      axis_name: the mesh axis the sequence is sharded over.
      causal: apply a causal mask in global positions.
      inner_attention: optional ``fn(q, k, v, causal=...)`` computing
        full-sequence attention on the gathered blocks — e.g. a flash
        kernel; defaults to plain softmax attention.

    Returns:
      ``[batch, heads, seq_local, head_dim]`` local attention output.
    """
    shards = lax.axis_size(axis_name)
    heads = q.shape[1]
    if heads % shards != 0:
        raise ValueError(
            f"ulysses attention needs num_heads ({heads}) divisible "
            f"by seq shards ({shards}); use ring attention otherwise"
        )

    def to_heads(x):
        # [b, h, s/n, d] -> [b, h/n, s, d]: head chunk j of every
        # device's block lands on device j; blocks concatenate along
        # the sequence axis in source-device order, which IS global
        # sequence order (device i holds contiguous block i).
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    q, k, v = to_heads(q), to_heads(k), to_heads(v)
    if inner_attention is None:
        from adaptdl_tpu.models.transformer import causal_attention

        inner_attention = causal_attention
    out = inner_attention(q, k, v, causal=causal)
    out = out.astype(q.dtype)
    # [b, h/n, s, d] -> [b, h, s/n, d]: the transpose exchange.
    return lax.all_to_all(
        out, axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def make_ulysses_attention(
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
    inner_attention=None,
):
    """Partial suitable for ``TransformerConfig.attention_fn``."""
    return partial(
        ulysses_attention,
        axis_name=axis_name,
        causal=causal,
        inner_attention=inner_attention,
    )
