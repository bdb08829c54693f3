"""Gated delta-rule linear attention with a per-channel decay ("KDA"),
chunk by chunk.

Per head, with a state ``S`` in R^{dk x dv} that is zero at a row's
start, ``alpha_t = exp(g_t)`` in (0, 1)^dk and a step ``beta_t`` in
(0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Token by token that is a scan of rank-one updates the MXU cannot fill.
Here a row is cut into chunks of ``chunk`` tokens and the recurrence
is carried from chunk to chunk only. With ``G_t`` the running sum of
``g`` inside a chunk (float32) and ``S`` the state at the chunk's
start,

    u_t  = beta_t (v_t - S^T (k_t e^{G_t}) - sum_{i<t} A_ti u_i)
    A_ti = sum_c k_t[c] k_i[c] e^{G_t[c] - G_i[c]}          (i < t)
    o_t  = S^T (q_t e^{G_t}) + sum_{i<=t} B_ti u_i
    B_ti = sum_c q_t[c] k_i[c] e^{G_t[c] - G_i[c]}          (i <= t)
    S'   = Diag(e^{G_C}) S + sum_i (k_i e^{G_C - G_i}) u_i^T

so ``U = T (V - K+ S)`` with ``T = (I + Diag(beta) A)^{-1} Diag(beta)``
a unit lower triangular solve a chunk. Two stages:

- **The chunk's own work** (``_prepare``), for all chunks at once, in
  XLA and differentiated by autodiff: the decay sums, ``A`` and ``B``,
  the triangular inverse, ``W_k = T K+`` and ``W_v = T V``. No
  exponent is ever positive: inside a sub-block of ``_SUB`` (16)
  tokens ``e^{G_t - G_i}`` is taken exactly, pair by pair and channel
  by channel; between sub-blocks the product is split at the later
  sub-block's first token, ``e^{G_t - R} e^{R - G_i}``, both factors
  at most 1. The inverse is block forward substitution from single
  rows up (``_unit_lower_inverse``), which does not cancel as a
  Neumann series does. Products take operands in the input dtype and accumulate in
  float32; sums of ``g``, the exponents and the inverse are float32.
- **The recurrence over chunks**, two Pallas kernels (``kda_fwd``,
  ``kda_bwd``; interpret mode off the TPU): one grid step a (batch,
  head) and chunk, the float32 state (its gradient, walking the chunks
  backwards) in VMEM scratch, four (nine) products a chunk on the MXU.
  The forward writes each chunk's starting state for the backward,
  which recomputes ``U`` from it. Where a head's widths are not whole
  lane tiles on the chip the same arithmetic runs as a ``lax.scan``
  (``path`` = fallback in the ``kda.schedule`` event).

Both stages run a group of heads at a time (``head_groups``), one
group after another, each group's work (``kda_fwd`` included) done
again in its backward: the float32 arrays of the chunks' own work are
then a group's, not the layer's.

``kda`` names its output (``SAVED_OUT``): a remat'd block keeps it
(``models.transformer.block_remat``), so the block's recomputation
does not run the rule a second time before its backward does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adaptdl_tpu import trace

FWD_KERNEL_NAME = "kda_fwd"
BWD_KERNEL_NAME = "kda_bwd"
# What ``kda`` names (``jax.ad_checkpoint.checkpoint_name``) of what it
# produces: its output. A remat'd block keeps it by this name
# (``models.transformer.block_remat``), so the block's recomputation
# does not run the rule again only to hand the output gate what it
# already had; the rule's own backward does its group's work again.
SAVED_OUT = "kda_out"
# Tokens a chunk: how the rule is computed, not part of the
# mathematics, and one value in use: a constant, no field of a model.
CHUNK = 64
_SUB = 16  # tokens of a sub-block: exponents taken pair by pair inside
_LANES = 128
_HIGHEST = lax.Precision.HIGHEST


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _sub_block(chunk: int) -> int:
    return _SUB if chunk % _SUB == 0 else chunk


def _unit_lower_inverse(lower):
    """``(I + lower)^{-1}`` for strictly lower triangular ``lower``
    [..., C, C], C a power of two, by block forward substitution: the
    inverse of ``[[A, 0], [B, D]]`` is ``[[A', 0], [-D' B A', D']]``,
    from blocks of one row up, every block of a level in one product.
    No term is larger than the inverse's own entries, where a Neumann
    series of a chunk's ``lower`` cancels binomially large ones."""
    size = lower.shape[-1]
    lead = lower.shape[:-2]
    inv = jnp.ones(lead + (size, 1, 1), lower.dtype)  # blocks of 1 x 1
    half = 1
    while half < size:
        pairs = size // (2 * half)
        shaped = lower.reshape(lead + (pairs, 2, half, pairs, 2, half))
        # Block (1, 0) of every diagonal pair: [..., pairs, half, half].
        below = jnp.moveaxis(
            jnp.diagonal(shaped, axis1=-6, axis2=-3)[..., 1, :, 0, :, :],
            -1, -3,
        )
        inv = inv.reshape(lead + (pairs, 2, half, half))
        upper, lower_inv = inv[..., 0, :, :], inv[..., 1, :, :]
        corner = -jnp.matmul(
            lower_inv, jnp.matmul(below, upper, precision=_HIGHEST),
            precision=_HIGHEST,
        )
        inv = jnp.concatenate(
            [
                jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
                jnp.concatenate([corner, lower_inv], axis=-1),
            ],
            axis=-2,
        )  # [..., pairs, 2 * half, 2 * half]
        half *= 2
    return inv[..., 0, :, :]


def _prepare(q, k, v, g, beta, chunk: int, scale: float):
    """The chunks' own work. q, k: [N, C, dk] (``scale`` multiplies q
    in float32), v: [N, C, dv], g: [N, C, dk] float32 log-decay, beta: [N, C] float32; N =
    batch x heads x chunks. Returns what the recurrence takes:
    ``(q e^G, k e^{G_C - G}, W_k, W_v, B, e^{G_C})``, the first five in
    the input dtype."""
    dtype = q.dtype
    sub = _sub_block(chunk)
    blocks = chunk // sub
    f32 = jnp.float32
    big_g = jnp.cumsum(g.astype(f32), axis=1)  # [N, C, dk]
    k32, q32 = k.astype(f32), q.astype(f32) * scale
    n, _, dk = k.shape

    def by_sub(x):
        return x.reshape(n, blocks, sub, x.shape[-1])

    g_sub, k_sub, q_sub = by_sub(big_g), by_sub(k32), by_sub(q32)
    # Inside a sub-block: every pair's exponent by itself.
    gap = jnp.minimum(
        g_sub[:, :, :, None, :] - g_sub[:, :, None, :, :], 0.0
    )  # [N, blocks, t, i, dk]
    decayed = jnp.exp(gap) * k_sub[:, :, None, :, :]
    a_diag = jnp.sum(k_sub[:, :, :, None, :] * decayed, axis=-1)
    b_diag = jnp.sum(q_sub[:, :, :, None, :] * decayed, axis=-1)
    same = jnp.eye(blocks, dtype=f32)[None, :, None, :, None]
    a_full = a_diag[:, :, :, None, :] * same  # [N, blocks, t, blocks, i]
    b_full = b_diag[:, :, :, None, :] * same
    if blocks > 1:
        # Between sub-blocks: split at the later one's first token.
        ref = g_sub[:, :, :1, :]  # [N, blocks, 1, dk]
        rel = jnp.exp(g_sub - ref)  # e^{G_t - R_I}, t in I
        earlier = (
            jnp.arange(chunk)[None, :] < (jnp.arange(blocks) * sub)[:, None]
        )  # [blocks I, i]: token i lies before sub-block I
        back = jnp.exp(
            jnp.minimum(ref - big_g[:, None, :, :], 0.0)
        ) * k32[:, None, :, :]  # [N, I, i, dk]: k_i e^{R_I - G_i}
        back = jnp.where(earlier[None, :, :, None], back, 0.0).astype(dtype)
        pair = functools.partial(
            jnp.einsum, "nItc,nIic->nIti", preferred_element_type=f32
        )
        a_off = pair((k_sub * rel).astype(dtype), back)
        b_off = pair((q_sub * rel).astype(dtype), back)
        a_full = a_full + a_off.reshape(n, blocks, sub, blocks, sub)
        b_full = b_full + b_off.reshape(n, blocks, sub, blocks, sub)
    a_full = a_full.reshape(n, chunk, chunk)
    b_full = b_full.reshape(n, chunk, chunk)
    at = jnp.arange(chunk)
    a_full = jnp.where(at[:, None] > at[None, :], a_full, 0.0)
    b_full = jnp.where(at[:, None] >= at[None, :], b_full, 0.0)
    beta = beta.astype(f32)
    solve = _unit_lower_inverse(beta[:, :, None] * a_full)
    solve = (solve * beta[:, None, :]).astype(dtype)  # T
    k_plus = (k32 * jnp.exp(big_g)).astype(dtype)
    w_k = jnp.matmul(solve, k_plus, preferred_element_type=f32)
    w_v = jnp.matmul(solve, v, preferred_element_type=f32)
    last = big_g[:, -1:, :]  # [N, 1, dk]
    return (
        (q32 * jnp.exp(big_g)).astype(dtype),
        (k32 * jnp.exp(last - big_g)).astype(dtype),
        w_k.astype(dtype),
        w_v.astype(dtype),
        b_full.astype(dtype),
        jnp.exp(last),
    )


_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _chunk_forward(state, qp, kd, wk, wv, b, dl):
    """One chunk of the recurrence on values: ``state`` [dv, dk]
    float32 (the state transposed: the decay runs along lanes), the
    rest one chunk's blocks, ``dl`` [1, dk]. Returns (o float32 [C,
    dv], the next state)."""
    low = qp.dtype
    s_low = state.astype(low)
    u = wv.astype(jnp.float32) - _dot(wk, s_low, _NT)
    u_low = u.astype(low)
    o = _dot(qp, s_low, _NT) + _dot(b, u_low)
    return o, dl * state + _dot(u_low, kd, _TN)


def _chunk_backward(d_state, state, qp, kd, wk, wv, b, dl, d_o):
    """The chunk's gradients from ``d_o`` [C, dv] and the gradient of
    its END state ``d_state`` [dv, dk] float32. Returns ((d_qp, d_kd,
    d_wk, d_wv, d_b, d_dl), the gradient of its starting state)."""
    low = qp.dtype
    s_low, ds_low = state.astype(low), d_state.astype(low)
    u_low = (wv.astype(jnp.float32) - _dot(wk, s_low, _NT)).astype(low)
    d_u = _dot(b, d_o, _TN) + _dot(kd, ds_low, _NT)
    du_low = d_u.astype(low)
    grads = (
        _dot(d_o, s_low),
        _dot(u_low, ds_low),
        -_dot(du_low, s_low),
        d_u,
        _dot(d_o, u_low, _NT),
        jnp.sum(state * d_state, axis=0, keepdims=True),
    )
    d_start = _dot(d_o, qp, _TN) - _dot(du_low, wk, _TN) + dl * d_state
    return grads, d_start


def _fwd_kernel(qp, kd, wk, wv, b, dl, o_ref, st_ref, state):
    ci = pl.program_id(1)

    # (Every block access inside a ``when``: interpret mode under a
    # shard_map needs it, as the flash kernels note.)
    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    @pl.when(ci >= 0)
    def _chunk():
        start = state[...]
        st_ref[0, 0] = start
        o, state[...] = _chunk_forward(
            start, qp[0, 0], kd[0, 0], wk[0, 0], wv[0, 0], b[0, 0],
            dl[0, 0],
        )
        o_ref[0, 0] = o.astype(o_ref.dtype)


def _bwd_kernel(
    qp, kd, wk, wv, b, dl, st, d_o,
    d_qp, d_kd, d_wk, d_wv, d_b, d_dl, d_state,
):
    ci = pl.program_id(1)  # the index maps walk the chunks backwards

    @pl.when(ci == 0)
    def _init():
        d_state[...] = jnp.zeros_like(d_state)

    @pl.when(ci >= 0)
    def _chunk():
        grads, d_state[...] = _chunk_backward(
            d_state[...], st[0, 0], qp[0, 0], kd[0, 0], wk[0, 0],
            wv[0, 0], b[0, 0], dl[0, 0], d_o[0, 0],
        )
        for ref, value in zip((d_qp, d_kd, d_wk, d_wv, d_b), grads):
            ref[0, 0] = value.astype(ref.dtype)
        d_dl[0, 0] = grads[5]


def _specs(chunk: int, dk: int, dv: int, chunks: int, backwards: bool):
    """Block specs of (a [bh, chunks, C, dk] operand, a [.., C, dv] one,
    B [.., C, C], the decay [bh, chunks, 1, dk], the states [bh,
    chunks, dv, dk]): a chunk's rows as the chunks' own work leaves
    them, so that no operand is laid out again on its way in."""

    def at(ci):
        return chunks - 1 - ci if backwards else ci

    def rows(width):
        return pl.BlockSpec(
            (1, 1, chunk, width), lambda bh, ci: (bh, at(ci), 0, 0)
        )

    return (
        rows(dk), rows(dv), rows(chunk),
        pl.BlockSpec((1, 1, 1, dk), lambda bh, ci: (bh, at(ci), 0, 0)),
        pl.BlockSpec((1, 1, dv, dk), lambda bh, ci: (bh, at(ci), 0, 0)),
    )


_PARAMS = dict(dimension_semantics=("parallel", "arbitrary"))


def _fwd_pallas(qp, kd, wk, wv, b, dl):
    bh, chunks, chunk, dk = qp.shape
    dv = wv.shape[3]
    wide, tall, square, decay, states = _specs(chunk, dk, dv, chunks, False)
    vma = jax.typeof(qp).vma
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bh, chunks),
        in_specs=[wide, wide, wide, tall, square, decay],
        out_specs=[tall, states],
        out_shape=[
            jax.ShapeDtypeStruct(wv.shape, qp.dtype, vma=vma),
            jax.ShapeDtypeStruct(
                (bh, chunks, dv, dk), jnp.float32, vma=vma
            ),
        ],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=_use_interpret(),
        name=FWD_KERNEL_NAME,
    )(qp, kd, wk, wv, b, dl)


def _bwd_pallas(qp, kd, wk, wv, b, dl, st, d_o):
    bh, chunks, chunk, dk = qp.shape
    dv = wv.shape[3]
    wide, tall, square, decay, states = _specs(chunk, dk, dv, chunks, True)
    vma = jax.typeof(qp).vma

    def like(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, vma=vma)

    return pl.pallas_call(
        _bwd_kernel,
        grid=(bh, chunks),
        in_specs=[wide, wide, wide, tall, square, decay, states, tall],
        out_specs=[wide, wide, wide, tall, square, decay],
        out_shape=[
            like(qp), like(kd), like(wk), like(wv), like(b), like(dl),
        ],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=_use_interpret(),
        name=BWD_KERNEL_NAME,
    )(qp, kd, wk, wv, b, dl, st, d_o)


def _by_chunk(x):
    """[bh, chunks, C, w] -> [chunks, bh, C, w], a scan's axis first."""
    return jnp.moveaxis(x, 1, 0)


def _fwd_scan(qp, kd, wk, wv, b, dl):
    """``_fwd_pallas`` as a ``lax.scan`` over the chunks."""
    bh, _, _, dk = qp.shape
    dv = wv.shape[3]
    step = jax.vmap(_chunk_forward)

    def body(state, blocks):
        o, after = step(state, *blocks)
        return after, (o.astype(qp.dtype), state)

    _, (o, states) = lax.scan(
        body, jnp.zeros((bh, dv, dk), jnp.float32),
        tuple(_by_chunk(x) for x in (qp, kd, wk, wv, b, dl)),
    )
    return jnp.moveaxis(o, 0, 1), jnp.moveaxis(states, 0, 1)


def _bwd_scan(qp, kd, wk, wv, b, dl, st, d_o):
    """``_bwd_pallas`` as a reversed ``lax.scan``."""
    bh, _, _, dk = qp.shape
    dv = wv.shape[3]
    step = jax.vmap(_chunk_backward)

    def body(d_state, blocks):
        state, d_out, *rest = blocks
        grads, d_start = step(d_state, state, *rest, d_out)
        return d_start, grads

    _, grads = lax.scan(
        body, jnp.zeros((bh, dv, dk), jnp.float32),
        tuple(_by_chunk(x) for x in (st, d_o, qp, kd, wk, wv, b, dl)),
        reverse=True,
    )
    return tuple(
        jnp.moveaxis(x, 0, 1).astype(like.dtype)
        for x, like in zip(grads, (qp, kd, wk, wv, b, dl))
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _recurrence(kernel: bool, qp, kd, wk, wv, b, dl):
    """The chunks' recurrence: o [bh, chunks, C, dv]."""
    return (_fwd_pallas if kernel else _fwd_scan)(qp, kd, wk, wv, b, dl)[0]


def _recurrence_fwd(kernel, *operands):
    out, states = (_fwd_pallas if kernel else _fwd_scan)(*operands)
    return out, (*operands, states)


def _recurrence_bwd(kernel, saved, d_o):
    return (_bwd_pallas if kernel else _bwd_scan)(*saved, d_o)


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


# A group of heads whose k is at most this many elements is prepared
# at once (32 MiB in float32: at 16 384 tokens and heads of 128, four).
_GROUP_ELEMENTS = 2**23


def head_groups(tokens: int, heads: int, dk: int) -> int:
    """In how many groups of heads, one after another, a call runs."""
    at_once = max(1, _GROUP_ELEMENTS // (tokens * dk))
    return next(
        heads // held for held in range(min(at_once, heads), 0, -1)
        if heads % held == 0
    )


def kernel_fits(dk: int, dv: int, chunk: int) -> bool:
    """Whether the Pallas kernels take these widths: anything in
    interpret mode; on the chip whole lane tiles a head and whole
    sublane tiles a chunk."""
    if _use_interpret():
        return True
    return dk % _LANES == 0 and dv % _LANES == 0 and chunk % 16 == 0


def kda(q, k, v, g, beta, *, chunk: int | None = None,
        scale: float | None = None, use_kernel: bool | None = None,
        prepare=None, per_head=()):
    """Chunked gated delta rule.

    q, k: ``[batch, seq, heads, dk]`` (the caller L2-normalises them),
    v: ``[batch, seq, heads, dv]``, g: ``[batch, seq, heads, dk]`` the
    log-decay (<= 0; summed in float32), beta: ``[batch, seq, heads]``.
    ``scale`` multiplies q (default ``dk ** -0.5``). A row whose
    length ``chunk`` does not divide is padded with tokens that leave
    the state as it is (``chunk``: ``CHUNK`` unless a test asks for
    another). Returns ``[batch, seq, heads, dv]`` in ``v.dtype``.

    ``prepare``: where the caller's own work on a head (convolutions,
    norms, the decay's activation) is as large as the rule's, it hands
    over what it has BEFORE that work and ``prepare(q, k, v, g, beta,
    *per_head)`` is called on one group of heads at a time (the five
    arrays sliced to the group's heads on axis 2, g then as the caller
    pleases, ``None`` where it brings the decay itself; ``per_head``
    arrays, heads leading, sliced on axis 0) and
    returns the five operands described above. Its work is then a
    group's too, and is done again in the group's backward."""
    batch, seq_len, heads, dk = q.shape
    dv = v.shape[-1]
    scale = dk**-0.5 if scale is None else float(scale)
    # A power of two (the solve halves a chunk down to single rows),
    # no longer than the row.
    chunk = 1 << (min(chunk or CHUNK, seq_len).bit_length() - 1)
    chunks = -(-seq_len // chunk)
    kernel = kernel_fits(dk, dv, chunk) if use_kernel is None else use_kernel
    groups = head_groups(batch * seq_len, heads, dk)
    trace.event(
        "kda.schedule",
        heads=heads,
        head_dim=dk,
        v_dim=dv,
        seq_len=seq_len,
        chunk=chunk,
        chunks=chunks,
        sub_block=_sub_block(chunk),
        head_groups=groups,
        padded=chunks * chunk - seq_len,
        dtype=q.dtype.name,
        path="kernel" if kernel else "fallback",
        product="pallas:" + FWD_KERNEL_NAME + "," + BWD_KERNEL_NAME
        if kernel else "scan",
        backward="kernel over the forward's chunk states; the chunks' own "
        "work by autodiff" + (", a group of heads at a time, done again "
                              "in its backward" if groups > 1 else ""),
        saved_names=SAVED_OUT,
    )
    pad = chunks * chunk - seq_len

    def some_heads(per_token, per_head):
        if prepare is not None:
            per_token = prepare(*per_token, *per_head)
        q, k, v, g, beta = per_token  # [b, s, heads of the group, w]
        held = q.shape[2]

        def rows(x):  # [b, s, h, w] -> [b * h * chunks, C, w]
            x = jnp.swapaxes(x, 1, 2)
            if pad:
                x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
            return x.reshape(batch * held * chunks, chunk, x.shape[-1])

        qp, kd, wk, wv, b, dl = _prepare(
            rows(q), rows(k), rows(v), rows(g.astype(jnp.float32)),
            rows(beta.astype(jnp.float32)[..., None])[..., 0], chunk, scale,
        )
        bh = batch * held
        out = _recurrence(
            kernel,
            *(x.reshape(bh, chunks, chunk, x.shape[-1])
              for x in (qp, kd, wk, wv, b)),
            dl.reshape(bh, chunks, 1, dk),
        )
        out = out.reshape(batch, held, chunks * chunk, dv)[:, :, :seq_len]
        return jnp.swapaxes(out, 1, 2).astype(v.dtype)

    per_token = (q, k, v, g, beta)
    if groups == 1:
        return checkpoint_name(some_heads(per_token, per_head), SAVED_OUT)

    # The chunks' own work holds a dozen float32 arrays as large as k
    # (and its gradient as many again): a group of heads at a time, in
    # turn, each group's work done again in its backward, so that what
    # is alive at once is a group's and not the layer's.
    def token_groups(x):  # [b, s, h, ...] -> [groups, b, s, h / groups, ...]
        shape = x.shape[:2] + (groups, heads // groups) + x.shape[3:]
        return jnp.moveaxis(x.reshape(shape), 2, 0)

    def head_groups_of(x):  # [h, ...] -> [groups, h / groups, ...]
        return x.reshape((groups, heads // groups) + x.shape[1:])

    out = lax.map(
        jax.checkpoint(lambda operands: some_heads(*operands)),
        (
            jax.tree.map(token_groups, per_token),  # (a None stays)
            tuple(head_groups_of(x) for x in per_head),
        ),
    )  # [groups, b, s, heads / groups, dv]
    return checkpoint_name(
        jnp.moveaxis(out, 0, 2).reshape(batch, seq_len, heads, dv), SAVED_OUT
    )


def kda_recurrent(q, k, v, g, beta, scale: float | None = None):
    """The recurrence token by token, in float32: what ``kda``
    computes chunk-wise. Same arguments and result."""
    dk = q.shape[-1]
    scale = dk**-0.5 if scale is None else float(scale)
    f32 = jnp.float32

    def step(state, token):  # state [b, h, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = token
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=_HIGHEST)
        u_t = beta_t[..., None] * (v_t - seen)
        state = state + k_t[..., None] * u_t[..., None, :]
        return state, jnp.einsum(
            "bhk,bhkv->bhv", q_t * scale, state, precision=_HIGHEST
        )

    batch, _, heads, _ = q.shape
    tokens = tuple(
        jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta)
    )
    _, out = lax.scan(
        step, jnp.zeros((batch, heads, dk, v.shape[-1]), f32), tokens
    )
    return jnp.moveaxis(out, 0, 1)
