"""Flash attention: a Pallas TPU kernel for the attention hot loop.

``adaptdl_tpu.models.transformer.causal_attention`` materializes the
full [seq, seq] logits matrix — fine at tutorial sizes, HBM-bound at
real sequence lengths. This kernel is the classic blockwise
online-softmax formulation: Q blocks stream through VMEM, K/V blocks
stream past them, and the running (max, sum, accumulator) triple is
kept in VMEM scratch — O(block²) memory instead of O(seq²), with both
matmuls per block landing on the MXU. (The reference framework has no
kernel layer to compare against — it rides torch's prebuilt CUDA
attention; this is the TPU-native equivalent of that native layer.)

Differentiation: ``pallas_call`` is not autodiff-transparent, so
:func:`flash_attention` is a ``jax.custom_vjp``. The backward pass
recomputes attention blockwise in plain JAX (a ``lax.scan`` over K
blocks using the saved per-row log-sum-exp) — the standard
recompute-instead-of-store trade, keeping backward memory O(seq·block)
too. XLA fuses the backward scan well; the forward is where a custom
kernel beats the default lowering (no [seq, seq] intermediate).

On CPU the kernel runs in interpret mode (bit-accurate semantics,
Python speed) so the whole path is testable without hardware — and a
program lowered that way carries no ``MOSAIC_CALL``, which is what the
chip-path checks look for. The mesh-sharded long-context path still
uses
``adaptdl_tpu.parallel.ring_attention`` — this kernel is the
*within-chip* block engine.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# How the Mosaic-compiled kernel appears in a lowered or compiled
# program's text. Interpret mode leaves no such call, so code that
# measures or proves the chip path (chip_smoke.py, bench.py, the chip
# compile tests) asserts this string is present.
MOSAIC_CALL = "tpu_custom_call"


def _use_interpret() -> bool:
    """Interpret mode (Python-speed reference semantics) off the TPU,
    so the CPU tests can run the kernel; the compiled kernel on it."""
    return jax.default_backend() != "tpu"


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_scratch,
    l_scratch,
    acc_scratch,
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    # A fully-masked block (whole K block strictly above the causal
    # diagonal) contributes nothing: skip its matmuls.
    if causal:
        diag_visible = ki * block_k <= qi * block_q + block_q - 1
    else:
        diag_visible = ki >= 0  # always, as a traced predicate

    @pl.when(diag_visible)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scratch[:, 0:1]  # [bq, 1] (lanes replicated)
        l_prev = l_scratch[:, 0:1]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        p = jnp.exp(s - m_next)
        rescale = jnp.exp(m_prev - m_next)
        l_next = l_prev * rescale + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * rescale + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scratch[...] = jnp.broadcast_to(m_next, m_scratch.shape)
        l_scratch[...] = jnp.broadcast_to(l_next, l_scratch.shape)

    @pl.when(ki == num_k - 1)
    def _finalize():
        l_final = l_scratch[:, 0:1]
        safe_l = jnp.maximum(l_final, 1e-30)
        o_ref[0] = (acc_scratch[...] / safe_l).astype(o_ref.dtype)
        lse = m_scratch[:, 0:1] + jnp.log(safe_l)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:]).astype(
            jnp.float32
        )


def _fwd_pallas(q, k, v, causal, scale, block_q, block_k):
    """q/k/v: [bh, seq, d] -> (out [bh, seq, d], lse [bh, seq, 128])."""
    bh, seq_len, head_dim = q.shape
    block_q = min(block_q, seq_len)
    block_k = min(block_k, seq_len)
    assert seq_len % block_q == 0 and seq_len % block_k == 0, (
        f"seq_len {seq_len} must divide into blocks "
        f"({block_q}, {block_k})"
    )
    grid = (bh, seq_len // block_q, seq_len // block_k)
    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
    )
    # Inside a shard_map (the trainer's data/seq axes) pallas outputs
    # must declare how they vary: the same way q does.
    vma = jax.typeof(q).vma
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0)
            ),
            pl.BlockSpec(
                (1, block_k, head_dim), lambda b, qi, ki: (b, ki, 0)
            ),
            pl.BlockSpec(
                (1, block_k, head_dim), lambda b, qi, ki: (b, ki, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0)
            ),
            pl.BlockSpec(
                (1, block_q, 128), lambda b, qi, ki: (b, qi, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            jax.ShapeDtypeStruct(
                (bh, seq_len, 128), jnp.float32, vma=vma
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((block_q, head_dim), jnp.float32),  # accumulator
        ],
        interpret=_use_interpret(),
    )(q, k, v)
    return out, lse[..., 0]


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
):
    """Blockwise exact attention.

    Args:
      q, k, v: ``[batch, heads, seq, head_dim]``.
      causal: apply the causal mask.
      scale: logit scale; default ``head_dim ** -0.5``.
      block_q / block_k: VMEM tile sizes (must divide seq).

    Returns:
      ``[batch, heads, seq, head_dim]``, dtype of ``q``.
    """
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    batch, heads, seq_len, head_dim = q.shape
    resolved_scale = (
        head_dim**-0.5 if scale is None else float(scale)
    )
    flat = lambda x: x.reshape(batch * heads, seq_len, head_dim)  # noqa: E731
    out, lse = _fwd_pallas(
        flat(q), flat(k), flat(v), causal, resolved_scale,
        block_q, block_k,
    )
    out = out.reshape(q.shape)
    lse = lse.reshape(batch, heads, seq_len)
    return out, lse


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, residuals, g):
    """Blockwise backward: scan over K blocks recomputing P from the
    saved log-sum-exp (the flash-attention backward identities):

        dV = P^T dO
        dP = dO V^T
        dS = P * (dP - rowsum(dO * O))
        dQ = dS K * scale ;  dK = dS^T Q * scale
    """
    q, k, v, out, lse = residuals
    batch, heads, seq_len, head_dim = q.shape
    resolved_scale = head_dim**-0.5 if scale is None else float(scale)
    block = min(block_k, seq_len)
    num_blocks = seq_len // block

    q32 = q.astype(jnp.float32) * resolved_scale
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    # delta_i = sum_d dO_id * O_id  (the softmax-jacobian row term)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)

    q_pos = jnp.arange(seq_len)

    def kv_block(carry, block_idx):
        dq_acc = carry
        start = block_idx * block
        k_blk = lax.dynamic_slice_in_dim(k32, start, block, axis=2)
        v_blk = lax.dynamic_slice_in_dim(v32, start, block, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_blk)
        if causal:
            k_pos = start + jnp.arange(block)
            visible = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(visible[None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])
        dp = jnp.einsum("bhqd,bhkd->bhqk", g32, v_blk)
        ds = p * (dp - delta[..., None])
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
        dk_blk = jnp.einsum(
            "bhqk,bhqd->bhkd", ds, q32
        )  # scale folded into q32
        dq_acc = dq_acc + jnp.einsum(
            "bhqk,bhkd->bhqd", ds, k_blk
        ) * resolved_scale
        return dq_acc, (dk_blk, dv_blk)

    dq, (dk_blocks, dv_blocks) = lax.scan(
        kv_block,
        # Derive the accumulator init from q so it inherits q's
        # varying-axis type under shard_map (a literal zeros array is
        # typed unvarying and fails the scan carry check).
        q32 * 0.0,
        jnp.arange(num_blocks),
    )
    # blocks: [num_blocks, batch, heads, block, d] -> [b, h, seq, d]
    merge = lambda blocks: jnp.moveaxis(blocks, 0, 2).reshape(  # noqa: E731
        batch, heads, seq_len, head_dim
    )
    dk = merge(dk_blocks)
    dv = merge(dv_blocks)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def make_flash_attention(
    causal: bool = True, block_q: int = 128, block_k: int = 128
):
    """Partial suitable for ``TransformerConfig.attention_fn``
    (signature ``attn(q, k, v) -> out``)."""

    def attn(q, k, v):
        return flash_attention(
            q, k, v, causal, None, block_q, block_k
        )

    return attn
