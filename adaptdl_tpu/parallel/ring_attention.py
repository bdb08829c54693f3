"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context training shards the sequence dimension across a "seq"
mesh axis. Each device holds one block of Q/K/V; K/V blocks rotate
around the ring with ``lax.ppermute`` (neighbor hops that ride ICI)
while an online-softmax accumulator folds in one block per step —
exact attention with O(seq/devices) memory per chip and communication
overlapped with the block matmuls by XLA.

The reference has no sequence parallelism at all (SURVEY.md section 5:
its only sequence handling is BPTT-window data parallelism,
adaptdl/adaptdl/torch/iterator.py); this module is the TPU-native
capability extension that makes long-context first-class. The
computation pattern follows the ring-attention literature (Liu et al.,
blockwise parallel transformers); implementation is original.

Use inside any ``shard_map`` whose mesh has the sequence axis, e.g. by
setting ``TransformerConfig.attention_fn = ring_attention`` and
training with ``ElasticTrainer(seq_shards=k)``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from adaptdl_tpu.parallel.mesh import SEQ_AXIS

NEG_INF = -1e30


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS, causal: bool = True):
    """Exact (causal) attention across a sequence-sharded axis.

    Args:
      q, k, v: local blocks ``[batch, heads, seq_local, head_dim]``.
      axis_name: the mesh axis the sequence is sharded over.
      causal: apply a causal mask in *global* positions.

    Returns:
      ``[batch, heads, seq_local, head_dim]`` local attention output.
    """
    ring_size = lax.axis_size(axis_name)
    my_block = lax.axis_index(axis_name)
    seq_local = q.shape[2]
    scale = q.shape[-1] ** -0.5
    q32 = q.astype(jnp.float32) * scale

    q_pos = my_block * seq_local + jnp.arange(seq_local)

    def fold_block(carry, step):
        out, row_max, row_sum, k_blk, v_blk = carry
        src_block = (my_block - step) % ring_size
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk",
            q32,
            k_blk.astype(jnp.float32),
        )
        if causal:
            k_pos = src_block * seq_local + jnp.arange(seq_local)
            visible = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(visible[None, None], logits, NEG_INF)
        block_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(row_max, block_max)
        # Rows with nothing visible yet keep NEG_INF; exp() of the
        # shifted logits stays exactly 0 for them.
        probs = jnp.exp(logits - new_max[..., None])
        rescale = jnp.exp(row_max - new_max)
        new_sum = row_sum * rescale + jnp.sum(probs, axis=-1)
        new_out = out * rescale[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", probs, v_blk.astype(jnp.float32)
        )
        # Pass our current K/V block to the next device; after r hops
        # device i holds block (i - r) mod ring_size.
        perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (new_out, new_max, new_sum, k_next, v_next), None

    # Derive the accumulator init arithmetically from q so it inherits
    # exactly q's varying-axis type (the ring axis here, plus any outer
    # mapped axes such as "data" when nested in the trainer's
    # shard_map) — a literal zeros array would be typed unvarying and
    # fail the scan's carry check.
    zero_rows = q32[..., 0] * 0.0
    init = (q32 * 0.0, zero_rows + NEG_INF, zero_rows, k, v)
    (out, _, row_sum, _, _), _ = lax.scan(
        fold_block, init, jnp.arange(ring_size)
    )
    # Every causal query row sees at least its own diagonal block, so
    # row_sum > 0; the guard covers degenerate non-causal edge cases.
    out = out / jnp.maximum(row_sum[..., None], 1e-30)
    return out.astype(q.dtype)


def make_ring_attention(axis_name: str = SEQ_AXIS, causal: bool = True):
    """Partial suitable for ``TransformerConfig.attention_fn``."""
    return partial(ring_attention, axis_name=axis_name, causal=causal)
