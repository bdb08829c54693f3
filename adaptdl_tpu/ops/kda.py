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

- **The chunk's own work**: the decay sums, ``A`` and ``B``, the
  triangular inverse, ``W_k = T K+`` and ``W_v = T V``. No exponent
  is ever positive: inside a sub-block of ``_SUB`` (16) tokens
  ``e^{G_t - G_i}`` is taken exactly, pair by pair and channel by
  channel; between sub-blocks the product is split at the later
  sub-block's first token, ``e^{G_t - R} e^{R - G_i}``, both factors
  at most 1. The inverse ``X = (I + Diag(beta) A)^{-1}`` is block
  forward substitution from single rows up, which does not cancel as
  a Neumann series does. Products take operands in the input dtype
  and accumulate in float32; sums of ``g``, the exponents and the
  inverse (its products at ``Precision.HIGHEST``) are float32. Two
  programs of that one arithmetic, the same rounding points:

  - a Pallas kernel pair (``delta_chunk_fwd``, ``delta_chunk_bwd``;
    ``_own_work``, a ``jax.custom_vjp``), a few chunks a grid step
    (the backward walks two of them abreast in one basic block), every
    float32 array of a chunk in VMEM: the forward reads q, k, v, g,
    beta and writes what the recurrence takes (``q e^G``, ``k e^{G_C -
    G}``, ``W_k``, ``W_v``, ``B`` in the input dtype, ``e^{G_C}``).
    Tokens lie on
    sublanes and channels on lanes; a sub-block's pairs are walked one
    earlier token at a time against the later rows, and each column of
    ``A`` so formed is at once one step of the forward substitution
    inside the sub-block; above a sub-block the block substitution is
    two whole-chunk products a level. The inverse is formed ONCE for a
    group's backward: the forward RULE's kernel writes ``X`` and ``A``
    out beside the six results (float32 [bh, chunks, C, C] each, 32
    KiB a chunk, alive from a group's forward rule to its backward),
    the primal's (the forward pass's run) writes neither, and the
    backward takes both, forms only the decay sums and the scaled
    operands again (``_own_shared``, the one function both bodies
    trace: handed ``X`` and ``A`` it walks no pair and solves nothing)
    and then every step's transpose by hand, float32 where autodiff
    would round a cotangent to the operand's dtype;
  - ``_prepare``, for all chunks at once in XLA and differentiated by
    autodiff (``_unit_lower_inverse``): where ``kernel_fits`` says no,
    and what the kernels are tested against.
- **The recurrence over chunks**, two Pallas kernels (``kda_fwd``,
  ``kda_bwd``; interpret mode off the TPU): a grid step takes a
  group's heads abreast and several chunks of each in turn
  (``_state_how``: what fits the kernels' VMEM, from the shapes
  alone), a head's float32 state (its gradient, walking the chunks
  backwards) in VMEM scratch, four (nine) products a chunk and head on
  the MXU.
  The forward writes each chunk's starting state for the backward,
  which recomputes ``U`` from it. Where a head's widths are not whole
  lane tiles on the chip the same arithmetic runs as a ``lax.scan``
  over ``_prepare``'s results (``path`` = fallback, ``own_work`` = xla
  in the ``kda.schedule`` event).

Both stages run a group of heads at a time (``head_groups``), one
group after another, each group's work (both forward kernels) done
again in its backward: what is alive in HBM at once (a group's
operands, its chunk states and their cotangents) is then a group's,
not the layer's.

A rule with ONE decay a head (``g`` [batch, seq, heads]) and fewer
key heads than value heads is this rule with every channel's decay
equal and q, k repeated (a group of heads at a time). The decay then
leaves the sums over channels,

    A_ti = e^{G_t - G_i} (k_t . k_i)     (i < t)
    B_ti = e^{G_t - G_i} (q_t . k_i)     (i <= t)

``A = tril(K K^T, -1) * D`` and ``B = tril(Q K^T) * D`` with ``D`` ONE
[C, C] float32 matrix a head and chunk: C^2 exponents, none positive,
and ``[K; Q] K^T`` one product of the operands as they came, where the
per-channel body walks a sub-block's pairs channel by channel. The
chunks' own work of such a rule has a kernel pair of its own
(``delta_chunk_head_fwd`` / ``delta_chunk_head_bwd``; ``_head_work``),
chosen by the RANK of ``g`` and by nothing else: it takes ``g`` as the
head's ``[.., 1, C]`` row, never broadcast to the channels, writes
exactly what ``_own_work`` writes (``e^{G_C}`` as the ``[.., 1, dk]``
row, constant along it), so the state kernels are the same, and hands
g's gradient back a head's. The solve is the same float32 arithmetic
(forward substitution, which no pair walk pins to a sub-block of 16
here: over the whole chunk, so no level is left), written out by the
forward rule for the backward as the per-channel pair's is (``A`` is
one cheap product here and is formed again). Where ``kernel_fits``
says no, the
decay is broadcast into ``_prepare`` (``decay`` = ``head_as_channel``
in the ``kda.schedule`` event; ``head`` on the kernel path).

``kda`` names its output (``SAVED_OUT``): a remat'd block keeps it
(``models.transformer.block_remat``), so the block's recomputation
does not run the rule a second time before its backward does.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adaptdl_tpu import trace

FWD_KERNEL_NAME = "kda_fwd"
BWD_KERNEL_NAME = "kda_bwd"
# The chunks' own work (no ``kda_``: a device trace's ``kda_`` events
# are the state kernels alone).
OWN_FWD_KERNEL_NAME = "delta_chunk_fwd"
OWN_BWD_KERNEL_NAME = "delta_chunk_bwd"
# The same work where a head has ONE decay (``g`` [batch, seq, heads]):
# names that still hold ``delta_chunk``, which a device trace's readers
# of the chunks' own work match.
OWN_HEAD_FWD_KERNEL_NAME = "delta_chunk_head_fwd"
OWN_HEAD_BWD_KERNEL_NAME = "delta_chunk_head_bwd"
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
_NN = (((1,), (0,)), ((), ()))


def _dot(a, b, dims=None, precision=None):
    return lax.dot_general(
        a, b, dims or _NN, precision=precision,
        preferred_element_type=jnp.float32,
    )


# ---- the chunk's own work as a kernel pair ----------------------------
#
# ``_prepare``'s arithmetic on ONE chunk's [C, w] blocks in VMEM, the
# same rounding points, written for what Mosaic lowers: rows (tokens) on
# sublanes and channels on lanes; a sub-block's pair decays one earlier
# token ``i`` at a time against the sub-block's later rows; the inverse
# by forward substitution, a column at a time inside a sub-block (as the
# pair decays form the columns) and by ``_unit_lower_inverse``'s block
# products above it, each two whole-chunk products whose other blocks
# are zero.


def _running_sum(x, reverse: bool = False):
    """Inclusive running sum down the rows of ``x`` [C, w] (up them
    with ``reverse``), float32 adds in log2(C) doubling steps."""
    rows = x.shape[0]
    at = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    shift = 1
    while shift < rows:
        if reverse:
            moved = pltpu.roll(x, rows - shift, 0)
            x = x + jnp.where(at < rows - shift, moved, 0.0)
        else:
            x = x + jnp.where(at >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _level_masks(size: int, sub: int):
    """-> (row and column numbers of a [C, C] matrix, for each level
    of the block forward substitution above a sub-block (blocks of
    ``half`` = sub, 2 sub, .. rows) the mask of block (1, 0) of every
    diagonal pair of blocks)."""
    row = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    col = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    masks, bits = [], sub.bit_length() - 1
    while (1 << bits) < size:
        masks.append(
            ((row >> (bits + 1)) == (col >> (bits + 1)))
            & (((row >> bits) & 1) == 1) & (((col >> bits) & 1) == 0)
        )
        bits += 1
    return row, col, masks


def _unit_lower_inverse_chunk(inv, lower, masks):
    """``_unit_lower_inverse``'s levels from a sub-block up on one
    [C, C] matrix: with ``X`` (``inv`` to begin with) the inverse of
    the diagonal blocks of ``half`` rows and ``B`` the blocks (1, 0) of
    every pair, ``X - X (B X)`` is the inverse of the blocks of ``2
    half``: the same ``-D' (B A')`` a corner, the other blocks of both
    products exactly zero. (Inside a sub-block the same forward
    substitution runs a column at a time where the columns are
    formed: ``_own_shared``.)"""
    for below in masks:
        inv = inv - _dot(
            inv, _dot(jnp.where(below, lower, 0.0), inv, precision=_HIGHEST),
            precision=_HIGHEST,
        )
    return inv


def _unrolled() -> bool:
    """Whether a sub-block's tokens are walked by unrolled code: where
    the kernel is compiled (static rows and lanes, a straight line the
    scheduler interleaves). Interpreted, the same body runs as a loop
    and the CPU's compiler is spared the copies."""
    return not _use_interpret()


def _each(lo: int, hi: int, body, carry, unrolled: bool):
    """``carry = body(i, carry)`` for i in [lo, hi)."""
    if not unrolled:
        return lax.fori_loop(lo, hi, body, carry)
    for i in range(lo, hi):
        carry = body(i, carry)
    return carry


def _one(x, at, axis: int):
    """Row (axis 0) or column (axis 1) ``at`` of a value, kept 2-D."""
    if isinstance(at, int):
        return x[at:at + 1] if axis == 0 else x[:, at:at + 1]
    return lax.dynamic_slice_in_dim(x, at, 1, axis)


def _octet(sub: int, unrolled: bool) -> int:
    """In runs of how many tokens a sub-block is walked, each run
    against the rows from its first on: a float32 sublane tile where
    the walk is unrolled (the whole tiles of rows before token ``i``
    are skipped); as a loop, the whole sub-block (the rows before
    token i carry zeros: both triangles are masked)."""
    return 8 if sub % 8 == 0 and unrolled else sub


def _own_shared(q, k, g, beta, scale, unrolled, g_rows, k_rows, kept=None):
    """One chunk's own work up to ``T``, on values: q, k [C, dk] in
    the input dtype, g [C, dk] float32, beta [1, C] float32.
    ``g_rows``, ``k_rows``: float32 [C, dk] scratch that holds ``G``
    and k, from which single rows are read. ``kept``: ``(X, A)``, the
    inverse and the matrix it was formed from as the forward rule wrote
    them out — the backward's: no pair is then walked here, nothing is
    solved and ``B`` is not formed. Returns what the forward forms its
    results from and the backward its gradients."""
    low, f32 = q.dtype, jnp.float32
    chunk, dk = k.shape
    sub = _sub_block(chunk)
    octet = _octet(sub, unrolled)
    big_g = _running_sum(g)
    k32, q32 = k.astype(f32), q.astype(f32) * scale
    g_rows[...] = big_g
    k_rows[...] = k32
    lane = lax.broadcasted_iota(jnp.int32, (octet, chunk), 1)
    down = lax.broadcasted_iota(jnp.int32, (octet, chunk), 0)
    row, col, masks = _level_masks(chunk, sub)
    beta_col = jnp.sum(jnp.where(row == col, beta, 0.0), axis=1, keepdims=True)
    a_parts, b_parts, x_parts, between = [], [], [], {}
    for at in range(0, chunk, sub):
        g_sub, k_sub, q_sub = (x[at:at + sub] for x in (big_g, k32, q32))
        if at:
            # Between sub-blocks: split at this one's first token.
            first = g_sub[:1]
            rel = jnp.exp(g_sub - first)  # e^{G_t - R}
            shrunk = jnp.exp(jnp.minimum(first - big_g[:at], 0.0))
            back = jnp.concatenate(
                [(shrunk * k32[:at]).astype(low),
                 jnp.zeros((chunk - at, dk), low)], axis=0,
            )  # k_i e^{R - G_i}, i before the sub-block
            both = jnp.concatenate(
                [(k_sub * rel).astype(low), (q_sub * rel).astype(low)], axis=0
            )
            between[at] = (rel, shrunk, back, both)
        if kept is not None:
            continue
        if at:
            off = _dot(both, back, _NT)  # [2 sub, C]
            a_rows, b_rows = off[:sub], off[sub:]
        else:
            a_rows = b_rows = jnp.zeros((sub, chunk), f32)
        octets = range(0, sub, octet)
        a_sub = [a_rows[o:o + octet] for o in octets]
        b_sub = [b_rows[o:o + octet] for o in octets]
        # The sub-block's rows of the inverse of its own diagonal
        # block, the identity to begin with.
        x_sub = [jnp.where(lane == down + (at + o), 1.0, 0.0) for o in octets]
        beta_sub = beta_col[at:at + sub]
        below = lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
        # Inside the sub-block: every pair's exponent by itself, one
        # earlier token i against the rows from its tile on; and with
        # column i of A formed, forward substitution's step i.
        for lo in octets:

            def pair(i, carry, at=at, lo=lo, g_sub=g_sub, k_sub=k_sub,
                     q_sub=q_sub, beta_sub=beta_sub):
                a_sub, b_sub, x_sub = (list(x) for x in carry)
                decayed = jnp.exp(
                    jnp.minimum(g_sub[lo:] - g_rows[pl.ds(at + i, 1), :], 0.0)
                ) * k_rows[pl.ds(at + i, 1), :]
                a_col = jnp.sum(k_sub[lo:] * decayed, axis=1, keepdims=True)
                b_col = jnp.sum(q_sub[lo:] * decayed, axis=1, keepdims=True)
                step = jnp.where(below[lo:] > i, beta_sub[lo:] * a_col, 0.0)
                solved = _one(x_sub[lo // octet], i - lo, 0)  # row i, final
                for o in range(lo // octet, sub // octet):
                    rows = slice(o * octet - lo, (o + 1) * octet - lo)
                    here = lane == at + i
                    a_sub[o] = jnp.where(here, a_col[rows], a_sub[o])
                    b_sub[o] = jnp.where(here, b_col[rows], b_sub[o])
                    x_sub[o] = x_sub[o] - step[rows] * solved
                return tuple(a_sub), tuple(b_sub), tuple(x_sub)

            a_sub, b_sub, x_sub = _each(
                lo, lo + octet, pair,
                (tuple(a_sub), tuple(b_sub), tuple(x_sub)), unrolled,
            )
        a_parts += a_sub
        b_parts += b_sub
        x_parts += x_sub
    if kept is None:
        a_full = jnp.where(row > col, jnp.concatenate(a_parts, axis=0), 0.0)
        b_full = jnp.where(row >= col, jnp.concatenate(b_parts, axis=0), 0.0)
        inv = _unit_lower_inverse_chunk(
            jnp.concatenate(x_parts, axis=0), beta_col * a_full, masks
        )
    else:
        (inv, a_full), b_full = kept, None
    grown = jnp.exp(big_g)
    last = g_rows[pl.ds(chunk - 1, 1), :]
    return dict(
        big_g=big_g, k32=k32, q32=q32, between=between, a_full=a_full,
        b_full=b_full, beta_col=beta_col, inv=inv, row=row, col=col,
        solve=(inv * beta).astype(low),  # T
        grown=grown, k_plus=(k32 * grown).astype(low),
        last=last, left=jnp.exp(last - big_g),  # e^{G_C - G}
    )


def _own_fwd_kernel(q, k, v, g, beta, qp, kd, wk, wv, b, dl, *rest, scale,
                    unrolled, keep):
    # ``rest``: with ``keep`` the blocks of the inverse and of A, for
    # the backward; then the two scratch rows.
    kept, rows = rest[:2 * keep], rest[2 * keep:]

    # (Every block access inside a ``when``, as in the state kernels.)
    @pl.when(pl.program_id(1) >= 0)
    def _chunks():
        def one(j, carry):
            low = q.dtype
            at = _own_shared(
                q[0, j], k[0, j], g[0, j], beta[0, j], scale, unrolled, *rows
            )
            qp[0, j] = (at["q32"] * at["grown"]).astype(low)
            kd[0, j] = (at["k32"] * at["left"]).astype(low)
            wk[0, j] = _dot(at["solve"], at["k_plus"]).astype(low)
            wv[0, j] = _dot(at["solve"], v[0, j]).astype(low)
            b[0, j] = at["b_full"].astype(low)
            dl[0, j] = jnp.exp(at["last"])
            for ref, name in zip(kept, ("inv", "a_full")):
                ref[0, j] = at[name]
            return carry

        lax.fori_loop(0, q.shape[1], one, 0)


def _own_backward(q, k, v, g, beta, kept, cts, scale, unrolled, rows):
    """One chunk's gradients on values, from the six cotangents of
    what the forward wrote (the first five in the input dtype, the
    decay's [1, dk] float32) and ``kept``, the inverse and ``A`` as the
    forward rule wrote them out: the forward's other values are formed
    again, then every step's transpose by hand, float32 throughout
    except where the forward's products take the input dtype. ``rows``:
    four float32 [C, dk] scratch (G, k, and the sums of dG and dk that
    single rows are added to). Returns (dq, dk, dv, dg, dbeta)."""
    g_rows, k_rows, dg_rows, dk_rows = rows
    low, f32 = q.dtype, jnp.float32
    chunk, dk = k.shape
    sub = _sub_block(chunk)
    octet = _octet(sub, unrolled)
    at = _own_shared(q, k, g, beta, scale, unrolled, g_rows, k_rows, kept)
    big_g, k32, q32 = at["big_g"], at["k32"], at["q32"]
    grown, left, inv, solve = at["grown"], at["left"], at["inv"], at["solve"]
    row, col = at["row"], at["col"]
    d_qp, d_kd, d_wk, d_wv, d_b, d_dl = cts
    d_qp, d_kd = d_qp.astype(f32), d_kd.astype(f32)
    # W_k = T K+, W_v = T V, T = X Diag(beta), X = (I + Diag(beta) A)^-1.
    d_solve = _dot(d_wk, at["k_plus"], _NT) + _dot(d_wv, v, _NT)
    d_kplus = _dot(solve, d_wk, _TN)
    d_v = _dot(solve, d_wv, _TN)
    d_beta = jnp.sum(d_solve * inv, axis=0, keepdims=True)  # [1, C]
    d_lower = -_dot(
        inv, _dot(d_solve * beta, inv, _NT, _HIGHEST), _TN, _HIGHEST
    )  # -X^T dX X^T
    d_lower = jnp.where(row > col, d_lower, 0.0)
    d_beta += jnp.sum(
        jnp.where(
            row == col,
            jnp.sum(d_lower * at["a_full"], axis=1, keepdims=True), 0.0,
        ),
        axis=0, keepdims=True,
    )
    d_a = at["beta_col"] * d_lower
    d_b = jnp.where(row >= col, d_b.astype(f32), 0.0)
    # q e^G, k e^G (inside W_k), k e^{G_C - G}, e^{G_C}.
    to_last = d_kd * k32 * left
    dk_rows[...] = d_kplus * grown + d_kd * left
    dg_rows[...] = (d_qp * q32 + d_kplus * k32) * grown - to_last
    dg_rows[pl.ds(chunk - 1, 1), :] += (
        jnp.sum(to_last, axis=0, keepdims=True) + d_dl * jnp.exp(at["last"])
    )
    dq_parts = []
    for here in range(0, chunk, sub):
        g_sub, k_sub, q_sub = (x[here:here + sub] for x in (big_g, k32, q32))
        da_rows, db_rows = d_a[here:here + sub], d_b[here:here + sub]
        if here:
            rel, shrunk, back, both = at["between"][here]
            d_off = jnp.concatenate([da_rows, db_rows], axis=0).astype(low)
            d_both = _dot(d_off, back)  # [2 sub, dk]
            d_back = _dot(d_off, both, _TN)[:here]  # [tokens before, dk]
            d_kr, d_qr = d_both[:sub], d_both[sub:]
            dk_sub, dq_sub = d_kr * rel, d_qr * rel
            dg_sub = (d_kr * k_sub + d_qr * q_sub) * rel  # d(G_t - R)
            dk_rows[pl.ds(0, here), :] += d_back * shrunk
            from_first = d_back * k32[:here] * shrunk  # d(R - G_i)
            dg_rows[pl.ds(0, here), :] -= from_first
            dg_rows[pl.ds(here, 1), :] += (
                jnp.sum(from_first, axis=0, keepdims=True)
                - jnp.sum(dg_sub, axis=0, keepdims=True)
            )
        else:
            dk_sub = dq_sub = dg_sub = jnp.zeros((sub, dk), f32)
        octets = range(0, sub, octet)
        dk_sub = [dk_sub[o:o + octet] for o in octets]
        dq_sub = [dq_sub[o:o + octet] for o in octets]
        dg_sub = [dg_sub[o:o + octet] for o in octets]
        for lo in octets:

            def pair(i, carry, here=here, lo=lo, g_sub=g_sub, k_sub=k_sub,
                     q_sub=q_sub, da_rows=da_rows, db_rows=db_rows):
                dg_sub, dk_sub, dq_sub = (list(x) for x in carry)
                k_i = k_rows[pl.ds(here + i, 1), :]
                gone = jnp.exp(jnp.minimum(
                    g_sub[lo:] - g_rows[pl.ds(here + i, 1), :], 0.0
                ))  # e^{G_t - G_i}
                da_col = _one(da_rows[lo:], here + i, 1)
                db_col = _one(db_rows[lo:], here + i, 1)
                to_i = (da_col * k_sub[lo:] + db_col * q_sub[lo:]) * gone
                dk_rows[pl.ds(here + i, 1), :] += jnp.sum(
                    to_i, axis=0, keepdims=True
                )
                to_gap = to_i * k_i
                dg_rows[pl.ds(here + i, 1), :] -= jnp.sum(
                    to_gap, axis=0, keepdims=True
                )
                decayed = gone * k_i
                for o in range(lo // octet, sub // octet):
                    rows_o = slice(o * octet - lo, (o + 1) * octet - lo)
                    dg_sub[o] = dg_sub[o] + to_gap[rows_o]
                    dk_sub[o] = dk_sub[o] + da_col[rows_o] * decayed[rows_o]
                    dq_sub[o] = dq_sub[o] + db_col[rows_o] * decayed[rows_o]
                return tuple(dg_sub), tuple(dk_sub), tuple(dq_sub)

            dg_sub, dk_sub, dq_sub = _each(
                lo, lo + octet, pair,
                (tuple(dg_sub), tuple(dk_sub), tuple(dq_sub)), unrolled,
            )
        dk_rows[pl.ds(here, sub), :] += jnp.concatenate(dk_sub, axis=0)
        dg_rows[pl.ds(here, sub), :] += jnp.concatenate(dg_sub, axis=0)
        dq_parts += dq_sub
    d_q = jnp.concatenate(dq_parts, axis=0) + d_qp * grown
    return (
        (d_q * scale).astype(low),
        dk_rows[...].astype(low),
        d_v.astype(low),
        _running_sum(dg_rows[...], reverse=True),
        d_beta,
    )


def _own_bwd_kernel(q, k, v, g, beta, *rest, scale, unrolled):
    # ``rest``: the inverse and A as the forward rule wrote them out,
    # the six cotangents, the five gradients, and four scratch rows
    # for each chunk of a basic block.
    kept, cts, grads, rows = rest[:2], rest[2:8], rest[8:13], rest[13:]
    abreast = len(rows) // 4

    @pl.when(pl.program_id(1) >= 0)
    def _chunks():
        def one(j, rows):
            values = _own_backward(
                q[0, j], k[0, j], v[0, j], g[0, j], beta[0, j],
                tuple(x[0, j] for x in kept), tuple(x[0, j] for x in cts),
                scale, unrolled, rows,
            )
            for ref, value in zip(grads, values):
                ref[0, j] = value

        # ``_abreast``'s loop, each chunk with scratch rows of its own.
        def some(j, carry):
            for a in range(abreast):
                one(j * abreast + a, rows[4 * a:4 * a + 4])
            return carry

        lax.fori_loop(0, q.shape[1] // abreast, some, 0)


def _own_specs(held: int, chunk: int, dk: int, dv: int):
    """Block specs of the chunks' own work, ``held`` chunks a grid
    step: (a [bh, chunks, C, dk] operand, a [.., C, dv] one, a
    [.., C, C] one, a [.., 1, dk] one, beta's [.., 1, C])."""

    def rows(height, width):
        return pl.BlockSpec(
            (1, held, height, width), lambda bh, ci: (bh, ci, 0, 0)
        )

    return (rows(chunk, dk), rows(chunk, dv), rows(chunk, chunk),
            rows(1, dk), rows(1, chunk))


# Chunks a grid step of the chunks' own work: a step's fixed cost
# (~0.35 us) is then a small part of a chunk's ~1 us.
_OWN_HELD = 8


class _How(NamedTuple):
    """How the kernel pair is built: what is decided outside the
    traced functions, so that they can be traced once a shape."""

    interpret: bool  # off the TPU
    unrolled: bool  # a sub-block's tokens by unrolled code (``_unrolled``)
    held: int  # chunks a grid step


def _own_how(chunks: int) -> _How:
    held = next(n for n in range(min(_OWN_HELD, chunks), 0, -1)
                if chunks % n == 0)
    return _How(_use_interpret(), _unrolled(), held)


def _own_rows(count: int, chunk: int, dk: int):
    return [pltpu.VMEM((chunk, dk), jnp.float32)] * count


_OWN_PARAMS = dict(dimension_semantics=("parallel", "parallel"))


# Chunks in one basic block of the BACKWARD kernel's loop, where a grid
# step holds a multiple of it (``_abreast``'s note); the forward walks
# one at a time. Measured on a v5e (PR 53: a call of 4 heads x 256
# chunks of 64, heads of 128, bf16, device time of 16 calls chained in
# one program), ms a call forward / backward. One chunk at a time, the
# backward forming the forward again, inverse included: 1.204 / 2.356.
# The backward handed ``X`` (it solves nothing) 1.521, handed ``A`` too
# (it walks no pair for it either, only its own transpose's) 1.350,
# that two abreast **1.168**; the forward that writes both out (32 KiB
# a chunk) + 0.003. The forward two abreast 1.034 — one chunk's levels
# on the MXU beside the other's pair walk on the VPU —, four 0.916;
# with the inverse by ``_substituted_inverse`` over the whole chunk in
# place of the in-walk steps and the two levels 1.176 one chunk at a
# time and 1.071 two abreast, for a body two thirds longer: no gain,
# the levels stay. What a chunk abreast costs: one more copy of the
# body in every program that holds the kernel — ~1.7 ms of ``setup_s``
# an equation of the three bodies (primal forward, keeping forward,
# backward) in a cached run of the kimi cell, where eight programs hold
# them: both kernels two abreast 16 763 equations for the 10 957 of
# before, ``setup_s`` 128.9 -> 138.8 (+7.7% of a 10% bound) for 21.7 ms
# of a 1450 ms step; the backward alone 11 293 (it no longer holds the
# forward's walk), ``setup_s`` unmoved. So the forward stays at one.
_OWN_BWD_ABREAST = 2


def _abreast_of(held: int, most: int) -> int:
    return most if held % most == 0 else 1


# (Both calls are functions jitted by themselves: a kernel's body is
# some thousands of operations, unrolled, and a model traces it at
# every call site of every program. As jitted functions the bodies are
# traced once a process and shape, and lowered once a program.)
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _own_fwd_pallas(scale: float, how: _How, keep: bool, q, k, v, g, beta):
    """-> the six results and, with ``keep``, the inverse ``X`` and
    ``A`` [bh, chunks, C, C] float32 for the backward."""
    bh, chunks, chunk, dk = q.shape
    dv = v.shape[3]
    wide, tall, square, decay, steps = _own_specs(how.held, chunk, dk, dv)
    vma = jax.typeof(q).vma

    def out(shape, dtype=q.dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

    matrix = (bh, chunks, chunk, chunk)
    return pl.pallas_call(
        functools.partial(
            _own_fwd_kernel, scale=scale, unrolled=how.unrolled, keep=keep
        ),
        grid=(bh, chunks // how.held),
        in_specs=[wide, wide, tall, wide, steps],
        out_specs=[wide, wide, wide, tall, square, decay] + [square] * 2 * keep,
        out_shape=[
            out(q.shape), out(q.shape), out(q.shape), out(v.shape),
            out(matrix), out((bh, chunks, 1, dk), jnp.float32),
        ] + [out(matrix, jnp.float32)] * 2 * keep,
        scratch_shapes=_own_rows(2, chunk, dk),
        compiler_params=pltpu.CompilerParams(**_OWN_PARAMS),
        interpret=how.interpret,
        name=OWN_FWD_KERNEL_NAME,
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _own_bwd_pallas(scale: float, how: _How, q, k, v, g, beta, inv, a_full,
                    *cts):
    """``inv``, ``a_full``: what the forward rule kept."""
    bh, chunks, chunk, dk = q.shape
    dv = v.shape[3]
    wide, tall, square, decay, steps = _own_specs(how.held, chunk, dk, dv)
    vma = jax.typeof(q).vma
    return pl.pallas_call(
        functools.partial(
            _own_bwd_kernel, scale=scale, unrolled=how.unrolled
        ),
        grid=(bh, chunks // how.held),
        in_specs=[wide, wide, tall, wide, steps, square, square,
                  wide, wide, wide, tall, square, decay],
        out_specs=[wide, wide, tall, wide, steps],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
            for x in (q, k, v, g, beta)
        ],
        scratch_shapes=_own_rows(
            4 * _abreast_of(how.held, _OWN_BWD_ABREAST), chunk, dk
        ),
        compiler_params=pltpu.CompilerParams(**_OWN_PARAMS),
        interpret=how.interpret,
        name=OWN_BWD_KERNEL_NAME,
    )(q, k, v, g, beta, inv, a_full, *cts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _own_work(scale: float, q, k, v, g, beta):
    """``_prepare`` as a kernel pair: q, k, g [bh, chunks, C, dk], v
    [bh, chunks, C, dv], beta [bh, chunks, 1, C]; the six results as
    the state kernels' blocks take them."""
    how = _own_how(q.shape[1])
    return tuple(_own_fwd_pallas(scale, how, False, q, k, v, g, beta))


def _own_work_fwd(scale, *operands):
    how = _own_how(operands[0].shape[1])
    results = _own_fwd_pallas(scale, how, True, *operands)
    return tuple(results[:6]), (*operands, *results[6:])


def _own_work_bwd(scale, saved, cts):
    how = _own_how(saved[0].shape[1])
    return tuple(_own_bwd_pallas(scale, how, *saved, *cts))


_own_work.defvjp(_own_work_fwd, _own_work_bwd)


# ---- the chunk's own work with ONE decay a head -----------------------
#
# The same mathematics where every channel of a head decays alike:
# ``e^{G_t - G_i}`` no longer depends on the channel and leaves the sum
# over channels, so ``A = tril(K K^T, -1) * D`` and ``B = tril(Q K^T) *
# D`` with ``D_ti = e^{G_t - G_i}`` ONE [C, C] float32 matrix a chunk:
# C^2 exponents where the per-channel body takes C^2 dk / 2 (sub-block
# by sub-block), none positive (``i <= t`` and ``g <= 0``; the other
# triangle is clamped, then masked), and ``[K; Q] K^T`` one product of
# the operands as they came, accumulated in float32 — exact for bf16
# operands: nothing scaled by a decay is rounded on its way into ``A``
# or ``B``. ``q e^G``, ``k e^{G_C - G}`` and ``k e^G`` scale rows by a
# [C, 1] column. The inverse is the same float32 arithmetic, forward
# substitution a column at a time, now over a matrix that is already
# there and over the WHOLE chunk (``_substituted_inverse``): no pair
# walk pins a sub-block to 16 tokens here, and what the chip says is
# that ONE chunk at a time no level of ``_unit_lower_inverse_chunk`` is
# worth its two ``HIGHEST`` products. Measured on a v5e (a call of 4
# heads x 256 chunks of 64, heads of 128, bf16, device time of 16
# calls chained in one program; the per-channel pair one chunk at a
# time 1.229 ms forward): the blocks' DMAs alone 0.19 ms, everything
# but the inverse 0.29, and the inverse the rest — 0.42 ms a level, ~2
# ns a [8, C] tile and column of the substitution. (Two chunks abreast
# the per-channel body's levels run beside the other chunk's pair
# walk, MXU beside VPU, and the substitution is no faster there:
# ``_OWN_BWD_ABREAST``'s note.) Forward with substitution up to 16 / 32
# / 64 tokens, one chunk at a time: 1.32 / 1.01 / ~1.0; two abreast in one
# basic block (their chains interleave): 0.89 at 32, **0.78 at 64**
# (four abreast gain ~5% more and double the body); the inverse
# written out by the forward rule for the backward rather than formed
# again (16 KiB a chunk in float32, alive from a group's forward rule
# to its backward): the two together 1.48 where a backward that forms
# the forward again took 1.55 alone.

# Chunks in one basic block of the one-decay body's loop.
_HEAD_ABREAST = 2


def _substituted_inverse(lower, unrolled: bool):
    """``(I + lower)^{-1}`` for strictly lower triangular ``lower``
    [C, C] by forward substitution a column at a time: with row i
    final, every later row takes ``-lower[t, i]`` of it. Compiled, the
    rows are walked a float32 sublane tile at a time and past the
    tiles before row i (``_octet``)."""
    chunk = lower.shape[0]
    octet = _octet(chunk, unrolled)
    lane = lax.broadcasted_iota(jnp.int32, (octet, chunk), 1)
    down = lax.broadcasted_iota(jnp.int32, (octet, chunk), 0)
    octets = range(0, chunk, octet)
    l_rows = [lower[o:o + octet] for o in octets]
    x_rows = tuple(jnp.where(lane == down + o, 1.0, 0.0) for o in octets)
    for lo in octets:

        def column(i, x_rows, lo=lo):
            x_rows = list(x_rows)
            solved = _one(x_rows[lo // octet], i - lo, 0)  # row i, final
            for o in range(lo // octet, chunk // octet):
                # (Column i as a sum over lanes: 9% of a forward call
                # faster on the chip than a lane's slice broadcast.)
                step = jnp.sum(
                    jnp.where(lane == i, l_rows[o], 0.0),
                    axis=1, keepdims=True,
                )
                x_rows[o] = x_rows[o] - step * solved
            return tuple(x_rows)

        # (The last column holds nothing below the diagonal.)
        x_rows = _each(
            lo, min(lo + octet, chunk - 1), column, x_rows, unrolled
        )
    return jnp.concatenate(x_rows, axis=0)


def _in_parts(x, low):
    """float32 ``x`` as a sum of arrays in ``low``, the second what
    the first's rounding left: a cotangent that multiplies operands in
    ``low`` then loses nothing to their dtype (the per-channel body
    keeps a sub-block's pairs in float32 on the VPU)."""
    if low == x.dtype:
        return (x,)
    first = x.astype(low)
    return first, (x - first.astype(x.dtype)).astype(low)


def _head_shared(q, k, g, beta, scale, unrolled, inv=None):
    """One chunk's own work up to ``T`` where the head has one decay,
    on values: q, k [C, dk] in the input dtype, g and beta [1, C]
    float32; ``inv``: the inverse where the forward kept it. Returns
    what the forward forms its results from and the backward its
    gradients."""
    low, f32 = q.dtype, jnp.float32
    chunk = k.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    same = row == col

    def column(x):  # [1, C] -> [C, 1], the same numbers
        return jnp.sum(jnp.where(same, x, 0.0), axis=1, keepdims=True)

    def across(x):  # [C, 1] -> [1, C]
        return jnp.sum(jnp.where(same, x, 0.0), axis=0, keepdims=True)

    # G_t, the running sum of g: a column, and the same numbers a row.
    big_g = jnp.sum(jnp.where(col <= row, g, 0.0), axis=1, keepdims=True)
    decay = jnp.exp(jnp.minimum(big_g - across(big_g), 0.0))  # D
    both = jnp.concatenate([k, q], axis=0)
    pairs = _dot(both, k, _NT)  # [K; Q] K^T, [2 C, C]
    kk, qk = pairs[:chunk], pairs[chunk:]
    a_full = jnp.where(row > col, kk * decay, 0.0)
    b_full = jnp.where(row >= col, qk * (decay * scale), 0.0)
    beta_col = column(beta)
    if inv is None:
        inv = _substituted_inverse(beta_col * a_full, unrolled)
    k32, q32 = k.astype(f32), q.astype(f32) * scale
    grown = jnp.exp(big_g)  # [C, 1]
    last = big_g[chunk - 1:]  # [1, 1]
    return dict(
        k32=k32, q32=q32, both=both, kk=kk, qk=qk, decay=decay,
        a_full=a_full, b_full=b_full, beta_col=beta_col, inv=inv, row=row,
        col=col, column=column, across=across,
        solve=(inv * beta).astype(low),  # T
        grown=grown, k_plus=(k32 * grown).astype(low),
        last=last, left=jnp.exp(last - big_g),  # e^{G_C - G}
    )


def _abreast(held: int, one):
    """``one(j)`` for each of a grid step's ``held`` chunks,
    ``_HEAD_ABREAST`` of them in one basic block of the loop: their
    serial chains (a product, the substitution, the levels, a product)
    are independent and the scheduler interleaves them."""
    n = _HEAD_ABREAST if held % _HEAD_ABREAST == 0 else 1

    def some(j, carry):
        for a in range(n):
            one(j * n + a)
        return carry

    lax.fori_loop(0, held // n, some, 0)


def _head_fwd_kernel(q, k, v, g, beta, qp, kd, wk, wv, b, dl, *kept, scale,
                     unrolled):
    @pl.when(pl.program_id(1) >= 0)
    def _chunks():
        def one(j):
            low = q.dtype
            at = _head_shared(
                q[0, j], k[0, j], g[0, j], beta[0, j], scale, unrolled
            )
            qp[0, j] = (at["q32"] * at["grown"]).astype(low)
            kd[0, j] = (at["k32"] * at["left"]).astype(low)
            wk[0, j] = _dot(at["solve"], at["k_plus"]).astype(low)
            wv[0, j] = _dot(at["solve"], v[0, j]).astype(low)
            b[0, j] = at["b_full"].astype(low)
            dl[0, j] = jnp.broadcast_to(jnp.exp(at["last"]), dl.shape[2:])
            for ref in kept:  # the inverse, for the backward
                ref[0, j] = at["inv"]

        _abreast(q.shape[1], one)


def _head_backward(q, k, v, g, beta, inv, cts, scale, unrolled):
    """One chunk's gradients where the head has one decay, from the
    six cotangents of what the forward wrote and the inverse it kept:
    the forward's other values are formed again, then every step's
    transpose by hand as ``_own_backward`` does, float32 throughout
    except where the forward's products take the input dtype. With
    ``dS = dA * D`` and ``dR = dB * D`` (what reaches ``K K^T`` and
    ``Q K^T``), q's and k's gradients are ``[dS; dR] K`` and ``[dS;
    dR]^T [K; Q]``, and G's is a row sum less a column sum of ``dS * K
    K^T + dR * Q K^T``. Returns (dq, dk, dv, dg [1, C], dbeta [1, C])."""
    low, f32 = q.dtype, jnp.float32
    chunk = k.shape[0]
    at = _head_shared(q, k, g, beta, scale, unrolled, inv)
    k32, q32, grown, left = at["k32"], at["q32"], at["grown"], at["left"]
    solve, decay = at["solve"], at["decay"]
    row, col, column, across = at["row"], at["col"], at["column"], at["across"]
    d_qp, d_kd, d_wk, d_wv, d_b, d_dl = cts
    d_qp, d_kd = d_qp.astype(f32), d_kd.astype(f32)
    # W_k = T K+, W_v = T V, T = X Diag(beta), X = (I + Diag(beta) A)^-1.
    d_solve = _dot(d_wk, at["k_plus"], _NT) + _dot(d_wv, v, _NT)
    d_kplus = _dot(solve, d_wk, _TN)
    d_v = _dot(solve, d_wv, _TN)
    d_beta = jnp.sum(d_solve * inv, axis=0, keepdims=True)  # [1, C]
    d_lower = -_dot(
        inv, _dot(d_solve * beta, inv, _NT, _HIGHEST), _TN, _HIGHEST
    )  # -X^T dX X^T
    d_lower = jnp.where(row > col, d_lower, 0.0)
    d_beta += across(jnp.sum(d_lower * at["a_full"], axis=1, keepdims=True))
    # A = tril(K K^T, -1) * D, B = tril(Q K^T) * D * scale.
    d_s = at["beta_col"] * d_lower * decay
    d_r = jnp.where(row >= col, d_b.astype(f32), 0.0) * (decay * scale)
    later = earlier = 0.0
    for part in _in_parts(jnp.concatenate([d_s, d_r], axis=0), low):
        # [2 C, dk]: to k_t and q_t from A_t., B_t.
        later += _dot(part, k)
        # [C, dk]: to k_i from A_.i, B_.i
        earlier += _dot(part, at["both"], _TN)
    # G: through D (up at the later token, down at the earlier one),
    # q e^G, k e^G (inside W_k), k e^{G_C - G}, e^{G_C}.
    moved = d_s * at["kk"] + d_r * at["qk"]
    to_last = d_kd * k32 * left
    d_big = (
        jnp.sum(moved, axis=1, keepdims=True)
        - column(jnp.sum(moved, axis=0, keepdims=True))
        + jnp.sum(
            (d_qp * q32 + d_kplus * k32) * grown - to_last,
            axis=1, keepdims=True,
        )
    )  # [C, 1]
    at_last = jnp.sum(
        jnp.sum(to_last, axis=0, keepdims=True), axis=1, keepdims=True
    ) + jnp.sum(d_dl, axis=1, keepdims=True) * jnp.exp(at["last"])
    d_big = d_big + jnp.where(row[:, :1] == chunk - 1, at_last, 0.0)
    return (
        (d_qp * grown * scale + later[chunk:]).astype(low),
        (d_kplus * grown + d_kd * left + later[:chunk] + earlier).astype(low),
        d_v.astype(low),
        # g_j is in every G_t from t = j on.
        jnp.sum(jnp.where(row >= col, d_big, 0.0), axis=0, keepdims=True),
        d_beta,
    )


def _head_bwd_kernel(q, k, v, g, beta, inv, d_qp, d_kd, d_wk, d_wv, d_b,
                     d_dl, d_q, d_k, d_v, d_g, d_beta, *, scale, unrolled):
    @pl.when(pl.program_id(1) >= 0)
    def _chunks():
        def one(j):
            grads = _head_backward(
                q[0, j], k[0, j], v[0, j], g[0, j], beta[0, j], inv[0, j],
                tuple(x[0, j] for x in (d_qp, d_kd, d_wk, d_wv, d_b, d_dl)),
                scale, unrolled,
            )
            for ref, value in zip((d_q, d_k, d_v, d_g, d_beta), grads):
                ref[0, j] = value

        _abreast(q.shape[1], one)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _head_fwd_pallas(scale: float, how: _How, keep: bool, q, k, v, g, beta):
    """-> the six results and, with ``keep``, the inverse ``X`` [bh,
    chunks, C, C] float32 for the backward."""
    bh, chunks, chunk, dk = q.shape
    dv = v.shape[3]
    wide, tall, square, decay, steps = _own_specs(how.held, chunk, dk, dv)
    vma = jax.typeof(q).vma

    def out(shape, dtype=q.dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

    matrix = (bh, chunks, chunk, chunk)
    return pl.pallas_call(
        functools.partial(
            _head_fwd_kernel, scale=scale, unrolled=how.unrolled
        ),
        grid=(bh, chunks // how.held),
        in_specs=[wide, wide, tall, steps, steps],
        out_specs=[wide, wide, wide, tall, square, decay] + [square] * keep,
        out_shape=[
            out(q.shape), out(q.shape), out(q.shape), out(v.shape),
            out(matrix), out((bh, chunks, 1, dk), jnp.float32),
        ] + [out(matrix, jnp.float32)] * keep,
        compiler_params=pltpu.CompilerParams(**_OWN_PARAMS),
        interpret=how.interpret,
        name=OWN_HEAD_FWD_KERNEL_NAME,
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_bwd_pallas(scale: float, how: _How, q, k, v, g, beta, inv, *cts):
    bh, chunks, chunk, dk = q.shape
    dv = v.shape[3]
    wide, tall, square, decay, steps = _own_specs(how.held, chunk, dk, dv)
    vma = jax.typeof(q).vma
    return pl.pallas_call(
        functools.partial(
            _head_bwd_kernel, scale=scale, unrolled=how.unrolled
        ),
        grid=(bh, chunks // how.held),
        in_specs=[wide, wide, tall, steps, steps, square,
                  wide, wide, wide, tall, square, decay],
        out_specs=[wide, wide, tall, steps, steps],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
            for x in (q, k, v, g, beta)
        ],
        compiler_params=pltpu.CompilerParams(**_OWN_PARAMS),
        interpret=how.interpret,
        name=OWN_HEAD_BWD_KERNEL_NAME,
    )(q, k, v, g, beta, inv, *cts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _head_work(scale: float, q, k, v, g, beta):
    """``_own_work`` for ONE decay a head: g as beta comes, [bh,
    chunks, 1, C] float32, never a channel's; the same six results
    (``e^{G_C}`` the [.., 1, dk] row the state kernels take, constant
    along it) and g's gradient a head's."""
    how = _own_how(q.shape[1])
    return tuple(_head_fwd_pallas(scale, how, False, q, k, v, g, beta))


def _head_work_fwd(scale, *operands):
    how = _own_how(operands[0].shape[1])
    *results, inv = _head_fwd_pallas(scale, how, True, *operands)
    return tuple(results), (*operands, inv)


def _head_work_bwd(scale, saved, cts):
    how = _own_how(saved[0].shape[1])
    return tuple(_head_bwd_pallas(scale, how, *saved, *cts))


_head_work.defvjp(_head_work_fwd, _head_work_bwd)


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


def _fwd_kernel(qp, kd, wk, wv, b, dl, o_ref, st_ref, states):
    ci = pl.program_id(1)
    heads, held = qp.shape[:2]

    # (Every block access inside a ``when``: interpret mode under a
    # shard_map needs it, as the flash kernels note.)
    @pl.when(ci == 0)
    def _init():
        states[...] = jnp.zeros_like(states)

    @pl.when(ci >= 0)
    def _chunks():
        # The block's chunks in turn, its heads abreast: independent
        # recurrences in one straight line, which the scheduler
        # interleaves.
        def one(j, carry):
            for h in range(heads):
                start = states[h]
                st_ref[h, j] = start
                o, states[h] = _chunk_forward(
                    start, qp[h, j], kd[h, j], wk[h, j], wv[h, j], b[h, j],
                    dl[h, j],
                )
                o_ref[h, j] = o.astype(o_ref.dtype)
            return carry

        lax.fori_loop(0, held, one, 0)


def _bwd_kernel(
    qp, kd, wk, wv, b, dl, st, d_o,
    d_qp, d_kd, d_wk, d_wv, d_b, d_dl, d_states,
):
    # The index maps walk the blocks backwards, the loop a block's
    # chunks.
    ci = pl.program_id(1)
    heads, held = qp.shape[:2]

    @pl.when(ci == 0)
    def _init():
        d_states[...] = jnp.zeros_like(d_states)

    @pl.when(ci >= 0)
    def _chunks():
        def one(i, carry):
            j = held - 1 - i
            for h in range(heads):
                grads, d_states[h] = _chunk_backward(
                    d_states[h], st[h, j], qp[h, j], kd[h, j], wk[h, j],
                    wv[h, j], b[h, j], dl[h, j], d_o[h, j],
                )
                for ref, value in zip((d_qp, d_kd, d_wk, d_wv, d_b), grads):
                    ref[h, j] = value.astype(ref.dtype)
                d_dl[h, j] = grads[5]
            return carry

        lax.fori_loop(0, held, one, 0)


# What the state kernels ask of Mosaic for one call (the chip's
# default is 16 MiB of its 128), and the share of it that a grid
# step's blocks, double-buffered as the pipeline holds them, and the
# scratch states may take; the rest is the body's own values.
_VMEM_LIMIT = 32 * 2**20
_BLOCKS_SHARE = 0.5
# Heads of a block at most: each is one more copy of the chunk's
# products in the loop's body, which every program that holds the
# kernels lowers (``_OWN_HELD``'s note). Measured on a v5e at the
# kimi cell's shapes (bf16, 4 heads x 256 chunks of 64, heads of 128),
# ms a call forward / backward at (heads, chunks) a grid step: (1, 1)
# 0.613 / 0.770, (1, 8) 0.449 / 0.546, (4, 1) 0.321 / 0.437, (4, 8)
# 0.243 / 0.370, where the blocks' DMAs alone take 0.222 / 0.344;
# (4, 16) and (4, 32) are no faster.
_STATE_HEADS = 4


class _Held(NamedTuple):
    """What one grid step of a state kernel holds."""

    heads: int  # of the call's batch x heads, walked abreast
    chunks: int  # of a head, walked in turn


def _state_how(bh: int, chunks: int, chunk: int, dk: int, dv: int,
               itemsize: int, backward: bool) -> _Held:
    """The largest divisors of ``bh`` (up to ``_STATE_HEADS``) and of
    ``chunks`` whose blocks fit ``_BLOCKS_SHARE`` of ``_VMEM_LIMIT``:
    a grid step's fixed cost (~0.35 us) is then shared by all of them
    and the heads' serial chains of small products overlap. Where
    nothing larger divides or fits: (1, 1), a chunk of a head a step."""
    def block(rows, width, size):  # in VMEM: whole (32 B x 128) tiles
        sub = 32 // size
        return -(-rows // sub) * sub * -(-width // _LANES) * _LANES * size

    tall, state = block(chunk, dv, itemsize), block(dv, dk, 4)
    operands = (  # qp, kd, wk; wv; B; the decay
        3 * block(chunk, dk, itemsize) + tall
        + block(chunk, chunk, itemsize) + block(1, dk, 4)
    )
    # Forward: o and the chunk's starting state out; backward: d_o and
    # the state in, and a gradient an operand out.
    one = operands + tall + state + (operands if backward else 0)
    budget = _BLOCKS_SHARE * _VMEM_LIMIT

    def fits(heads, held):
        return (2 * held * one + state) * heads <= budget

    def largest(count, most, ok):
        return next(
            (n for n in range(min(most, count), 1, -1)
             if count % n == 0 and ok(n)), 1,
        )

    heads = largest(bh, _STATE_HEADS, lambda n: fits(n, 1))
    return _Held(heads, largest(chunks, chunks, lambda n: fits(heads, n)))


def _specs(held: _Held, chunk: int, dk: int, dv: int, chunks: int,
           backwards: bool):
    """Block specs of (a [bh, chunks, C, dk] operand, a [.., C, dv] one,
    B [.., C, C], the decay [bh, chunks, 1, dk], the states [bh,
    chunks, dv, dk]), ``held`` of each a grid step: a chunk's rows as
    the chunks' own work leaves them, so that no operand is laid out
    again on its way in."""
    blocks = chunks // held.chunks

    def rows(height, width):
        return pl.BlockSpec(
            (held.heads, held.chunks, height, width),
            lambda bh, ci: (bh, blocks - 1 - ci if backwards else ci, 0, 0),
        )

    return (rows(chunk, dk), rows(chunk, dv), rows(chunk, chunk),
            rows(1, dk), rows(dv, dk))


_PARAMS = dict(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT,
)


# (Functions jitted by themselves, as the chunks' own work is and for
# its reason: a model traces the kernels at every call site of every
# program, and four heads abreast are four times the body.)
@functools.partial(jax.jit, static_argnums=(0, 1))
def _fwd_call(held: _Held, interpret: bool, qp, kd, wk, wv, b, dl):
    bh, chunks, chunk, dk = qp.shape
    dv = wv.shape[3]
    wide, tall, square, decay, states = _specs(
        held, chunk, dk, dv, chunks, False
    )
    vma = jax.typeof(qp).vma
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bh // held.heads, chunks // held.chunks),
        in_specs=[wide, wide, wide, tall, square, decay],
        out_specs=[tall, states],
        out_shape=[
            jax.ShapeDtypeStruct(wv.shape, qp.dtype, vma=vma),
            jax.ShapeDtypeStruct(
                (bh, chunks, dv, dk), jnp.float32, vma=vma
            ),
        ],
        scratch_shapes=[pltpu.VMEM((held.heads, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name=FWD_KERNEL_NAME,
    )(qp, kd, wk, wv, b, dl)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _bwd_call(held: _Held, interpret: bool, qp, kd, wk, wv, b, dl, st, d_o):
    bh, chunks, chunk, dk = qp.shape
    dv = wv.shape[3]
    wide, tall, square, decay, states = _specs(
        held, chunk, dk, dv, chunks, True
    )
    vma = jax.typeof(qp).vma
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bh // held.heads, chunks // held.chunks),
        in_specs=[wide, wide, wide, tall, square, decay, states, tall],
        out_specs=[wide, wide, wide, tall, square, decay],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
            for x in (qp, kd, wk, wv, b, dl)
        ],
        scratch_shapes=[pltpu.VMEM((held.heads, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name=BWD_KERNEL_NAME,
    )(qp, kd, wk, wv, b, dl, st, d_o)


def _fwd_pallas(qp, kd, wk, wv, b, dl):
    held = _state_how(*qp.shape, wv.shape[3], qp.dtype.itemsize, False)
    return _fwd_call(held, _use_interpret(), qp, kd, wk, wv, b, dl)


def _bwd_pallas(qp, kd, wk, wv, b, dl, st, d_o):
    held = _state_how(*qp.shape, wv.shape[3], qp.dtype.itemsize, True)
    return tuple(
        _bwd_call(held, _use_interpret(), qp, kd, wk, wv, b, dl, st, d_o)
    )


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


# A group of heads whose k is at most this many elements runs at once
# (at 16 384 tokens and heads of 128: four). What a group keeps alive
# in HBM since the chunks' own work is a kernel pair, a head of 128 at
# 16 384 tokens: the caller's own work on the head (``prepare``:
# convolutions, norms and the decay, half a dozen float32 arrays as
# large as k) ~48 MiB; the five operands 20 (three in bf16, g in
# float32); the recurrence's five 18; the forward's chunk states
# [256, 128, 128] float32 16; and in the backward a cotangent beside
# each: ~0.2 GiB a head, where the XLA ``_prepare`` held a dozen
# float32 arrays as large as k and as many gradients. Groups of eight
# (2**24) also pass `tools/compile_step_v5e.py
# kimi-linear-48b-a3b-steady` (sixteen: refused by 396 MiB of 15.75
# GiB) and are no faster on the chip (a layer and micro-batch forward
# and backward 43.8 ms for 42.7: the kernels' grids do not care, and
# a group's copies in and out grow with it), so four stay.
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

    q, k: ``[batch, seq, key_heads, dk]`` (the caller L2-normalises
    them), v: ``[batch, seq, heads, dv]``, g: the log-decay (<= 0;
    summed in float32), ``[batch, seq, heads, dk]`` one a channel or
    ``[batch, seq, heads]`` ONE a head, beta: ``[batch, seq, heads]``.
    ``key_heads`` divides ``heads``: key head j serves the value heads
    ``j * heads / key_heads ..`` (q and k repeated, a group of heads at
    a time, after ``prepare``). One decay a head is the same rule with
    every channel's decay equal, ``A = tril(K K^T, -1) * D``, ``B =
    tril(Q K^T) * D``, ``D_ti = e^{G_t - G_i}``: on the kernel path the
    chunks' own work then runs in the body that takes the head's decay
    as such (``_head_work``; ``decay`` = ``head`` in the
    ``kda.schedule`` event, g's gradient summed over no channel), on
    the fallback it is broadcast over the ``dk`` channels into
    ``_prepare`` (``head_as_channel``); ``channel`` where ``g`` came a
    channel. The rank of ``g`` alone decides.
    ``scale`` multiplies q (default ``dk ** -0.5``). A row whose
    length ``chunk`` does not divide is padded with tokens that leave
    the state as it is (``chunk``: ``CHUNK`` unless a test asks for
    another). Returns ``[batch, seq, heads, dv]`` in ``v.dtype``.

    ``prepare``: where the caller's own work on a head (convolutions,
    norms, the decay's activation) is as large as the rule's, it hands
    over what it has BEFORE that work and ``prepare(q, k, v, g, beta,
    *per_head)`` is called on one group of heads at a time (the five
    arrays sliced to the group's heads on axis 2 — its share of the
    key heads for q and k —, g then as the caller pleases, ``None``
    where it brings a decay a channel itself; ``per_head`` arrays,
    heads or key heads leading, sliced on axis 0) and returns the five
    operands described above. Its work is then a group's too, and is
    done again in the group's backward."""
    batch, seq_len, key_heads, dk = q.shape
    heads, dv = v.shape[2], v.shape[-1]
    assert heads % key_heads == 0, (
        f"{key_heads} key heads for {heads} value heads"
    )
    a_head = g is not None and g.ndim == 3
    scale = dk**-0.5 if scale is None else float(scale)
    # A power of two (the solve halves a chunk down to single rows),
    # no longer than the row.
    chunk = 1 << (min(chunk or CHUNK, seq_len).bit_length() - 1)
    chunks = -(-seq_len // chunk)
    kernel = kernel_fits(dk, dv, chunk) if use_kernel is None else use_kernel
    groups = head_groups(batch * seq_len, heads, dk)
    groups = next(  # a group holds whole key heads
        n for n in range(groups, 0, -1)
        if heads % n == 0 and key_heads % n == 0
    )
    bh = batch * heads // groups  # of one call of the state kernels
    held, held_bwd = (
        _state_how(bh, chunks, chunk, dk, dv, q.dtype.itemsize, backward)
        if kernel else _Held(0, 0) for backward in (False, True)
    )
    # One decay a head has a chunk body of its own: what the rank of
    # ``g`` says, nothing else.
    own_fwd, own_bwd = (
        (OWN_HEAD_FWD_KERNEL_NAME, OWN_HEAD_BWD_KERNEL_NAME) if a_head
        else (OWN_FWD_KERNEL_NAME, OWN_BWD_KERNEL_NAME)
    )
    own_held = _own_how(chunks).held
    trace.event(
        "kda.schedule",
        heads=heads,
        key_heads=key_heads,
        value_heads=heads,
        decay=(("head" if kernel else "head_as_channel") if a_head
               else "channel"),
        head_dim=dk,
        v_dim=dv,
        seq_len=seq_len,
        chunk=chunk,
        chunks=chunks,
        sub_block=chunk if a_head and kernel else _sub_block(chunk),
        head_groups=groups,
        state_heads_a_step=held.heads,
        state_chunks_a_step=held.chunks,
        state_chunks_a_step_bwd=held_bwd.chunks,
        state_grid_steps=(bh // held.heads) * (chunks // held.chunks)
        if kernel else 0,
        padded=chunks * chunk - seq_len,
        dtype=q.dtype.name,
        path="kernel" if kernel else "fallback",
        product="pallas:" + FWD_KERNEL_NAME + "," + BWD_KERNEL_NAME
        if kernel else "scan",
        own_work="pallas:" + own_fwd + "," + own_bwd if kernel else "xla",
        # The chunk's inverse: levels of block products above a
        # sub-block, or forward substitution a column at a time over the
        # whole chunk (the one-decay body's); whether the forward rule
        # writes it out for the backward; chunks in one basic block of
        # the chunk kernels.
        inverse="substituted" if a_head and kernel else "levels",
        inverse_kept=kernel,
        chunks_abreast=(
            _abreast_of(own_held, _HEAD_ABREAST) if a_head else 1
        ) if kernel else 0,
        chunks_abreast_bwd=_abreast_of(
            own_held, _HEAD_ABREAST if a_head else _OWN_BWD_ABREAST
        ) if kernel else 0,
        backward=(
            BWD_KERNEL_NAME + " over the forward's chunk states, then "
            + own_bwd + " (the chunk's inverse as the forward rule wrote it "
            "out, the rest of its own work formed again in VMEM, its "
            "transpose by hand)" if kernel else
            "a reversed scan over the forward's chunk states; the chunks' "
            "own work by autodiff"
        ) + (", a group of heads at a time, done again in its backward"
             if groups > 1 else ""),
        saved_names=SAVED_OUT,
    )
    pad = chunks * chunk - seq_len

    def some_heads(per_token, per_head):
        if prepare is not None:
            per_token = prepare(*per_token, *per_head)
        q, k, v, g, beta = per_token  # [b, s, heads of the group, w]
        held = v.shape[2]
        if q.shape[2] != held:  # each key head on its value heads
            q, k = (jnp.repeat(x, held // x.shape[2], axis=2) for x in (q, k))
        g = g.astype(jnp.float32)
        one_decay = g.ndim == 3
        if one_decay:  # [b, s, h, 1]; a channel's where no body takes it
            g = g[..., None]
            if not kernel:
                g = jnp.broadcast_to(g, g.shape[:3] + (dk,))
        bh = batch * held

        def rows(x):  # [b, s, h, w] -> [b * h, chunks, C, w]
            x = jnp.swapaxes(x, 1, 2)
            if pad:
                x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
            return x.reshape(bh, chunks, chunk, x.shape[-1])

        def across(x):  # a number a token: [b * h, chunks, 1, C]
            return x.reshape(bh, chunks, 1, chunk)

        operands = (
            rows(q), rows(k), rows(v), rows(g),
            rows(beta.astype(jnp.float32)[..., None]),
        )
        if kernel and one_decay:
            prepared = _head_work(
                scale, *operands[:3], across(operands[3]), across(operands[4])
            )
        elif kernel:
            prepared = _own_work(scale, *operands[:4], across(operands[4]))
        else:
            prepared = _prepare(
                *(x.reshape((bh * chunks,) + x.shape[2:])
                  for x in operands[:4]),
                operands[4].reshape(bh * chunks, chunk), chunk, scale,
            )
            prepared = tuple(
                x.reshape((bh, chunks) + x.shape[1:]) for x in prepared[:5]
            ) + (prepared[5].reshape(bh, chunks, 1, dk),)
        out = _recurrence(kernel, *prepared)
        out = out.reshape(batch, held, chunks * chunk, dv)[:, :, :seq_len]
        return jnp.swapaxes(out, 1, 2).astype(v.dtype)

    per_token = (q, k, v, g, beta)
    if groups == 1:
        return checkpoint_name(some_heads(per_token, per_head), SAVED_OUT)

    # A group of heads at a time, in turn, each group's work done
    # again in its backward, so that what is alive at once (operands,
    # chunk states, cotangents: ``_GROUP_ELEMENTS``) is a group's and
    # not the layer's.
    def token_groups(x):  # [b, s, h, ...] -> [groups, b, s, h / groups, ...]
        shape = x.shape[:2] + (groups, x.shape[2] // groups) + x.shape[3:]
        return jnp.moveaxis(x.reshape(shape), 2, 0)

    def head_groups_of(x):  # [h, ...] -> [groups, h / groups, ...]
        return x.reshape((groups, x.shape[0] // groups) + x.shape[1:])

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
    if q.shape[2] != v.shape[2]:
        q, k = (
            jnp.repeat(x, v.shape[2] // x.shape[2], axis=2) for x in (q, k)
        )
    if g.ndim == 3:
        g = g[..., None]

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
