"""Flash attention: a Pallas TPU kernel for the attention hot loop.

``adaptdl_tpu.models.transformer.causal_attention`` materializes the
full [seq, seq] logits matrix — fine at tutorial sizes, HBM-bound at
real sequence lengths. This kernel is the blockwise online-softmax
formulation: one tile of logits at a time, the running (max, sum,
accumulator) triple in float32, both matmuls of a tile on the MXU and
no [seq, seq] intermediate. (The reference framework has no kernel
layer to compare against — it rides torch's prebuilt CUDA attention;
this is the TPU-native equivalent of that native layer.)

The forward schedule, chosen per call by :func:`_schedule` from
``seq_len``, ``head_dim``, the dtype's itemsize and ``causal`` (no
flag, no environment variable; ``flash.schedule`` in the trace journal
says what was chosen):

- **Positions along lanes.** Both kernels index ``[batch * heads,
  head_dim, seq]``: a (batch, head) is ``head_dim`` rows of whole
  sequences; K and V are ``[batch * kv_heads, head_dim, seq]``, ONCE
  a kv head ("fewer kv heads than query heads" below). That is how
  XLA itself lays out what the QKV projection
  and rotary write and what the out projection and their gradients
  read on the chip (``[batch, seq, heads, head_dim]`` with ``seq``
  minor-most: a minor dimension of 64 would leave half of every lane
  tile empty), so :func:`flash_attention`'s swap from its
  ``[batch, heads, seq, head_dim]`` contract and the model's swap into
  it are transposes of each other and no copy is made on either side
  of either kernel; the residuals are saved as the kernels index
  them. A caller that really holds row-major ``[batch, heads, seq,
  head_dim]`` pays one transposing copy an operand (PERF.md, PR 27;
  ``layout`` in the trace journal).
- **One grid step per (batch, head) and query tile, the k-loop inside
  the kernel.** K and V of the head are one VMEM block whose index
  does not depend on the query tile, so they are fetched once per
  head, not once per query tile. A traced ``lax.fori_loop`` walks the
  key tiles below the causal diagonal unmasked and stops there: a
  masked tile costs neither a grid step nor a DMA nor a trace.
- **A trip of that loop is one key tile, in updates of a block.** At
  q/k and v widths that fill the MXU's rows (whole ``_LANES``: heads
  of 128 and 256) an update is ``_LANES`` queries' with ``_LANES``
  keys (``_fwd_block``): its float32 logits are sixteen vregs that
  never leave the registers, a block's (max, sum, accumulator) live in
  VMEM scratch, and a key tile is 64 such updates in ONE straight
  line, eight blocks abreast that do not depend on each other — so the
  scheduler runs one block's ``K^T Q`` and ``V P`` on the MXU under
  another block's max / exp / sum, where the whole tile's update
  formed 4 MiB of logits in VMEM and walked them pass by pass with
  the MXU waiting (the bundle dump, PERF.md PR 57: the store slot
  full and no product issued through each softmax phase). The kernel
  alone on a v5e, bf16, causal, ms a call and share of the MXU's 197
  TFLOP/s (PERF.md, PR 57): ``[2, 256/256, 16384]`` 2.163 -> 1.713
  (64.5 -> 81.5%), ``[12 on 2 kv, 128/128, 16384]`` 7.496 -> 5.388
  (55.8 -> 77.7%), ``[16, 128/128, 8192]`` 2.179 -> 1.769 (64.0 ->
  78.9%). At any other width (64, 192) the small updates ran at HALF
  the whole tile's speed (6.98 -> 17.7 ms at ``[64 on 16, 64/64,
  8192]``, 3.37 -> 5.33 at ``[4, 192/128, 16384]``) and the block is
  the tile: one update a key tile, the statistics in the loop's carry,
  the program of before. Tried and slower (same PR): the next piece's
  logits carried through the loop (a 2 MiB carry is a VMEM copy a
  trip: 2.32 ms), blocks of 256 queries, a query tile of 512.
  The call itself is jitted (``_fwd_call``): a model makes it once a
  layer and run of heads, and every call after the first of a
  signature finds the 64-update kernel traced, and lowered once a
  program (one step program of glm-4.7-flash traces and lowers in
  12.8 s for 18.2).
- **Tiles are runs of the caller's tiles.** ``block_q`` / ``block_k``
  are the caller's granularity and the divisibility contract; the
  kernel fuses adjacent ones (runs of what divides both, where they
  differ) into one square tile of up to ``_TILE_ROWS`` (1024) rows,
  because a 128 x 128 update is too small a loop body to hide the
  MXU's latency (measured, PERF.md PR 25: 2.7 ms a call at
  128 x 128, 0.55 ms as shipped). What the larger
  tile would waste above the diagonal is won back inside it: the tile
  the diagonal crosses is done in pieces of ``_DIAG_ROWS`` (512) keys
  (of a block, where that is smaller), each multiplied only with the
  queries at or after it, and only the corner block of a piece is
  masked.
- **Logits are held** ``[keys, queries]``: a query's statistics run
  along lanes, so max and sum reduce across sublanes (plain VPU work,
  not lane rotations), the rescale of the accumulator ``[head_dim,
  queries]`` is a sublane broadcast, the output tile is the
  accumulator as it stands, and the log-sum-exp leaves as one float32
  per row, ``[bh, 1, seq]``, with no lane replication. The primal
  call has no such output at all. ``K^T Q`` contracts over
  ``head_dim``, which both hold along sublanes, so the small K piece
  is turned; ``V P`` is a plain product.
- **MXU operands in the input dtype, float32 accumulation** — the
  plain path's arithmetic: bf16 in means QK^T on bf16 operands with
  ``preferred_element_type=float32``, max / exp / sum / rescale and
  the accumulator in float32, probabilities rounded to ``v.dtype`` for
  PV. float32 in means float32 operands. Read from ``q.dtype``.
- **K/V resident while they fit.** K and V of a head, double-buffered,
  stay in VMEM whole while they fit ``_KV_VMEM_BUDGET`` (8 MiB: up to
  16k keys at head 64 in bf16). Beyond it the key axis is blocked
  into the largest chunks of whole tiles that fit: a third,
  ``arbitrary`` grid axis, the triple carried in VMEM scratch, and
  index maps clamped to the diagonal so that chunks above it are
  neither fetched nor computed. One algorithm; the shape decides how
  many chunks there are.

Differentiation: ``pallas_call`` is not autodiff-transparent, so
:func:`flash_attention` is a ``jax.custom_vjp`` whose residuals are
``(q, k, v, out, lse)``. The backward is ONE Pallas kernel on the same
schedule with the loops swapped (:func:`_bwd_kernel`; ``flash.
schedule_bwd`` in the trace journal): P is recomputed from the saved
log-sum-exp, never stored, so backward memory stays O(seq). The forward
rule names ``out`` and ``lse`` (``SAVED_OUT``, ``SAVED_LSE``) and a
remat'd block keeps them by name, so under remat the kernel's output
is not computed a second time (``models.transformer.block_remat``).

- **One grid step per (batch, kv head), key chunk, query head of the
  kv head's group and query tile.** The
  chunk's K and V (all of them while they fit the budget above) stay
  in VMEM while the group's query tiles pass; its dK and dV accumulate
  in float32 scratch over the query tiles at or after it, of every
  query head of the group, and the tile's
  dQ over the chunk's keys at or before it, so P and dS are computed
  once for all three gradients: five matmuls a block of logits, where
  the usual pair of kernels (dK/dV, then dQ) pays seven.
- **Only the blocks at or below the diagonal**, in updates of
  ``_BWD_DIAG_ROWS`` (256) keys: a traced loop over those below the
  diagonal tile, then the diagonal tile's pieces, each with the
  queries at or after it and only its corner block masked.
- **Logits** ``[keys, queries]``, **operands in the input dtype**, as
  forward: ``lse`` and ``delta = rowsum(dO * O)`` (taken in the
  kernel, in float32, a sublane reduction) broadcast along sublanes;
  ``dV = dO P^T`` and ``dK = Q dS^T`` contract over lanes as they
  stand, ``dQ = K dS`` is a plain product, and all three leave as
  they were accumulated, ``[head_dim, positions]``. The logits and
  ``dP`` contract over ``head_dim``: the K and V pieces are turned
  update by update, or, where more than one query tile passes a
  chunk, the chunk once into VMEM scratch. ``exp``, ``delta``,
  ``dS`` and the three accumulators are float32; float32 in means
  float32 operands.
- **Beyond the budget** the key chunks are a grid axis: a query tile
  before a chunk fetches and computes nothing, and dQ leaves as one
  float32 partial per chunk, summed outside. Correct and 7 x the scan
  it replaced at 16k x 128 (PERF.md, PR 26); tuned in no cell.

Fewer kv heads than query heads (grouped-query attention): ``k`` and
``v`` may have ``kv_heads`` heads with ``heads % kv_heads == 0``, and
``group = heads // kv_heads`` is read from the shapes (``_kv_group``;
``flash.schedule*`` carry ``kv_group`` and ``kv_heads``). Nothing is
repeated in HBM, on the way in or out:

- **Forward**: q, the output and the log-sum-exp stay ``[batch *
  heads, ...]``; the K / V index maps take ``row // group``
  (``_kv_row``), so while K / V are resident the heads of a group
  repeat one block's index in turn and it is fetched once. On the band
  the group's heads are the INNERMOST grid axis (``_band_grid``:
  ``(batch * kv_heads, query tile, group)``), under one tile's K / V
  blocks.
- **Backward**: one more ``arbitrary`` grid axis over the group's query
  heads INSIDE a kv head — ``(batch * kv_heads, chunk, group, query
  tile)`` on the chunked schedule, so the chunk's K, V, their turned
  copies and the float32 dK / dV scratch are set up once a kv head and
  chunk and written out after the group's last query tile; ``(batch *
  kv_heads, query tile [+ before], group)`` on the band, so a ring
  slot gathers every query head of a K / V block before it is flushed.
  dQ (and its float32 partial a chunk) stays a query head's. dK and dV
  leave ``[batch * kv_heads, head_dim, seq]``, summed over the group in
  float32 and rounded ONCE.
- **Equal head counts are the degenerate case, decided in Python**:
  ``group == 1`` builds the grids, index maps and kernels of before
  there were groups — the same lowered program
  (``tests/flash_digests.py``; ``tools/lowered_step_diff.py`` at real
  sizes).
- **The caller** recognises these functions by ``takes_kv_heads`` (set
  on :func:`flash_attention` and on :func:`make_flash_attention`'s
  result; ``models.transformer.GroupedQueryAttention`` looks behind
  any ``functools.partial``) and repeats k and v only for a function
  without it.

A lower bound on the keys (``window``, sliding-window attention): a
query sees itself and the ``window - 1`` keys before it, so a query
tile needs its own K/V block and the few before it whatever the row's
length, and the walk is the BAND's alone — neither resident K/V nor
key chunks, no partial dQ, the shape and the window decide
(``_band_schedule``; ``flash.schedule*`` carry ``window``,
``tiles_visited``, ``tiles_in_band``, and ``window.keys`` the logit
columns computed against the band's pairs). The section "a lower bound
on the keys" below holds both kernels; ``window`` None or ``>= seq``
is the schedule above, the same program.

On CPU the kernel runs in interpret mode (bit-accurate semantics,
Python speed) so the whole path is testable without hardware — and a program lowered that way carries no ``MOSAIC_CALL``,
which is what the chip-path checks look for. The mesh-sharded
long-context path still uses
``adaptdl_tpu.parallel.ring_attention`` — this kernel is the
*within-chip* block engine.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adaptdl_tpu import trace

NEG_INF = -1e30

# VMEM the K and V blocks of one grid step may take, double-buffered
# as the pipeline holds them. While a head's whole K and V fit, they
# stay resident across its query tiles; beyond it (bf16, head 64: past
# 16k keys) the key axis is blocked into chunks that do.
_KV_VMEM_BUDGET = 8 * 2**20
# What the kernel asks of Mosaic for one call: the budget above, the
# query / output blocks and the float32 logits of one tile (the chip's
# default, 16 MiB of its 128, leaves those too little at the budget).
_VMEM_LIMIT = 32 * 2**20
# The backward holds, beside K and V of the chunk, its dK and dV blocks
# and their float32 accumulators: three times the budget at most.
_VMEM_LIMIT_BWD = 64 * 2**20
# Adjacent caller tiles are fused into one tile of this many rows at
# most, and the tile the causal diagonal crosses is done in pieces of
# this many keys at most (whole vregs of lanes). Measured on a v5e at
# (16, 12, 1024, 64) bf16, forward ms a call (PERF.md, PR 25): tile
# 1024 in pieces of 512 0.55, of 256 0.57 (and a quarter more to trace
# and lower), of 128 0.60, unsplit 0.85; tile 512 0.67; 128 x 128 2.7.
# Again by the device trace, the kernel alone (PERF.md, PR 57): pieces
# of 512 0.407, of 256 0.452, of 128 0.463; at (1, 64 on 16, 8192, 64)
# 6.98 / 7.07 / 7.11; a tile of 512 0.558 and 8.92. Where a head's
# widths fill the MXU the forward's updates are smaller than either
# (``_fwd_block``); a tile of 512 there is a third slower than one of
# 1024 (2.32 for 1.71 ms at [2, 256, 16384]: half as many updates in
# a trip's straight line).
_TILE_ROWS = 1024
_DIAG_ROWS = 512
# The backward's updates cover this many keys at most, in the diagonal
# tile and below it: it holds four float32 [keys, queries] values live
# per update where the forward holds two. Measured on a v5e, backward
# kernel ms a call from the device trace (PERF.md, PR 26), pieces of
# 512 / 256 / 128 keys: (16, 12, 1024, 64) bf16 1.086 / 0.918 / 0.878
# (128 costs half as much again to trace and lower as 256); seq 2048
# 1.71 / 1.59; 4096 x 128 2.16 / 2.10; bidirectional 1.39 / 1.44.
_BWD_DIAG_ROWS = 256
_LANES = 128

# How the Mosaic-compiled kernel appears in a lowered or compiled
# program's text. Interpret mode leaves no such call, so code that
# measures or proves the chip path (chip_smoke.py, the chip compile
# tests) asserts this string is present.
MOSAIC_CALL = "tpu_custom_call"
# The backward kernel's name in a lowered program and in a device
# trace (``%flash_bwd.<n>``), and the scope the forward's call stands
# in: a custom call is named after the innermost scope, the model's
# ``attention`` before the call had one of its own, and the benchmark
# finds the forward as ``%attention.<n>``.
BWD_KERNEL_NAME = "flash_bwd"
FWD_KERNEL_NAME = "attention"
# What the forward rule names (``jax.ad_checkpoint.checkpoint_name``)
# of what it produces: the kernel's output and log-sum-exp, as the
# backward reads them. A remat'd block saves them by these names
# (``models.transformer.block_remat``), so the kernel runs once a step
# and not once more inside every backward; outside a remat a name is
# an identity.
SAVED_OUT = "flash_out"
SAVED_LSE = "flash_lse"
# ... and of what it consumes: q, k and v in the kernels' layout, the
# residuals the backward kernel reads. A remat'd block keeps them where
# the device has the bytes (``block_remat``'s ladder), and then neither
# the QKV projection nor rotary runs in the backward; named HERE so
# that what is kept is the array the backward kernel reads.
SAVED_QKV = "flash_qkv"
# What both kernels index, as the ``flash.schedule*`` events name it:
# ``[batch * heads, head_dim, seq]``.
LAYOUT = "bhds"


def _use_interpret() -> bool:
    """Interpret mode (Python-speed reference semantics) off the TPU,
    so the CPU tests can run the kernel; the compiled kernel on it."""
    return jax.default_backend() != "tpu"


def _kv_group(q, k) -> int:
    """Query heads a kv head serves in a call, from the kernels' own
    operands (``[batch * heads, ...]`` against ``[batch * kv_heads,
    ...]``): query row ``r`` reads kv row ``r // group``, the order
    ``jnp.repeat(k, group, axis=heads)`` gives."""
    group, rest = divmod(q.shape[0], k.shape[0])
    assert group >= 1 and rest == 0, (q.shape, k.shape)
    return group


def _kv_row(row, group: int):
    """The kv row of query row ``row`` in an index map. Decided in
    Python: with equal head counts it is ``row`` itself and the
    program is the one of before there were groups."""
    return row if group == 1 else lax.div(row, group)


class _Schedule(NamedTuple):
    """How one forward call is laid on the grid: chosen by
    :func:`_schedule` from what the call can see, never by the
    caller."""

    tile: int  # query rows of a grid step = keys of one softmax update
    diag: int  # keys of one update inside the tile the diagonal crosses
    chunk_k: int  # keys held in VMEM at once; seq_len = K/V resident


def _fuse(block: int, size: int, target: int) -> int:
    """The longest run of ``block`` rows that divides ``size`` and
    stays within ``target`` rows (``block`` itself where that is
    larger already)."""
    return max(
        rows
        for rows in range(block, max(target, block) + 1, block)
        if size % rows == 0
    )


def _schedule(
    seq_len: int,
    head_dim: int,
    itemsize: int,
    block_q: int,
    block_k: int,
    diag_rows: int | None = None,
    v_dim: int | None = None,
) -> _Schedule:
    """``diag_rows``: the most keys of one update inside a tile; the
    forward's ``_DIAG_ROWS`` unless the backward passes its own.
    ``v_dim``: the width of a head of v (and of the output) where it
    is not q's and k's ``head_dim``."""
    block_q = min(block_q, seq_len)
    block_k = min(block_k, seq_len)
    assert seq_len % block_q == 0 and seq_len % block_k == 0, (
        f"seq_len {seq_len} must divide into blocks "
        f"({block_q}, {block_k})"
    )
    # One tile for queries and keys, a run of what both caller tiles
    # are made of, so the causal diagonal crosses exactly the tiles
    # (i, i), corner to corner.
    tile = _fuse(math.gcd(block_q, block_k), seq_len, _TILE_ROWS)
    # Lane-aligned pieces of that tile, or the tile whole.
    diag = tile
    if tile % _LANES == 0:
        diag = _fuse(
            _LANES, tile, _DIAG_ROWS if diag_rows is None else diag_rows
        )
    # K and V of one head, each double-buffered by the pipeline.
    bytes_per_key = 2 * (head_dim + (v_dim or head_dim)) * itemsize
    chunk_k = _fuse(
        tile, seq_len, min(seq_len, _KV_VMEM_BUDGET // bytes_per_key)
    )
    return _Schedule(tile, diag, chunk_k)


def _tiles_visited(sched: _Schedule, seq_len: int, causal: bool) -> int:
    """(``diag`` x ``diag``) blocks of logits one (batch, head)
    computes: all ``(seq_len / diag) ** 2``, or those the causal loop
    bounds leave."""
    num_q, per_tile = seq_len // sched.tile, sched.tile // sched.diag
    if not causal:
        return (num_q * per_tile) ** 2
    below = per_tile**2 * num_q * (num_q - 1) // 2
    return below + num_q * per_tile * (per_tile + 1) // 2


def _corner_mask(s, diag: int):
    """Mask the corner block of transposed logits ``[keys, queries]``
    whose first key and first query sit at the same position: query
    lane >= key row within the first ``diag`` queries; every later
    query sees all of these keys."""
    corner = s[:, :diag]
    visible = lax.broadcasted_iota(
        jnp.int32, corner.shape, 1
    ) >= lax.broadcasted_iota(jnp.int32, corner.shape, 0)
    corner = jnp.where(visible, corner, NEG_INF)
    if s.shape[1] == diag:
        return corner
    return jnp.concatenate([corner, s[:, diag:]], axis=1)


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *rest,
    causal: bool,
    scale: float,
    diag: int,
    block: int,
    num_chunks: int,
    with_lse: bool,
):
    """One grid step: one (batch, head), one query tile, one chunk of
    keys (all of them when K/V are resident). Every block is
    ``[head_dim, positions]``, positions along lanes; logits are
    ``[keys, queries]``, so the softmax statistics of a query run
    along lanes too. A traced loop walks the chunk's key tiles below
    the causal diagonal unmasked; the tile the diagonal crosses comes
    last, in ``diag``-key pieces, each only for the queries at or
    after it, and only its corner block is masked.

    An update is ``block`` queries' with ``block`` keys (``diag`` in
    the diagonal tile). Where ``block`` is the tile, a key tile is one
    update and the statistics are the loop's carry. Where it is a
    piece of the tile (``_fwd_block``), a key tile is a straight line
    of small updates, those of different blocks independent of each
    other: one block's products run on the MXU under another's max /
    exp / sum, a block's logits stay in registers, and the statistics
    stay in VMEM scratch, each update reading and writing its
    block's."""
    tile = q_ref.shape[2]
    v_dim = v_ref.shape[1]  # the accumulator's and the output's rows
    chunk_tiles = k_ref.shape[2] // tile
    blocks = range(tile // block)
    rest = list(rest)
    lse_ref = rest.pop(0) if with_lse else None
    state = rest  # VMEM scratch (m, l, acc): across chunks, or blocks
    qi, ci = pl.program_id(1), pl.program_id(2)
    first_tile = ci * chunk_tiles  # of this chunk, among all key tiles
    # The last chunk with keys this query tile sees: the diagonal's.
    last = qi // chunk_tiles if causal else num_chunks - 1

    def update(kv, queries, carry, mask=None):
        """One online-softmax update of the statistics and accumulator
        of ``queries`` (a static range of the tile's positions) with
        the keys whose K and V are ``kv``. Operands in the input
        dtype."""
        m_prev, l_prev, acc = carry  # [1, n], [1, n], [d, n]
        q, (k, v) = q_ref[0, :, queries], kv
        s = scale * lax.dot_general(
            k, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [keys, queries]: only the small K is transposed
        if mask is not None:
            s = mask(s)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_next)
        rescale = jnp.exp(m_prev - m_next)
        l_next = l_prev * rescale + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * rescale + jnp.dot(
            v, p.astype(v.dtype), preferred_element_type=jnp.float32
        )
        return m_next, l_next, acc

    def at_keys(keys):
        """K and V of ``keys`` (positions of the chunk), read once for
        all the blocks they update."""
        return k_ref[0, :, keys], v_ref[0, :, keys]

    def update_block(kv, b, carry, lo=0, mask=None):
        """``update`` of block ``b``'s queries from its ``lo``-th on.
        Several blocks: in place, in scratch. One: in ``carry``."""
        queries = slice(b * block + lo, (b + 1) * block)
        if len(blocks) > 1:
            stats = update(
                kv, queries, tuple(ref[:, queries] for ref in state), mask
            )
            for ref, value in zip(state, stats):
                ref[:, queries] = value
            return carry
        done = tuple(x[:, :lo] for x in carry)
        live = update(kv, queries, tuple(x[:, lo:] for x in carry), mask)
        return tuple(
            jnp.concatenate(pair, axis=1) if lo else pair[1]
            for pair in zip(done, live)
        )

    def whole_tile(t, carry):
        for at in range(0, tile, block):
            kv = at_keys(pl.ds(pl.multiple_of(t * tile + at, block), block))
            for b in blocks:
                carry = update_block(kv, b, carry)
        return carry

    corner_mask = functools.partial(_corner_mask, diag=diag)

    def diagonal_tile(carry):
        start = pl.multiple_of((qi - first_tile) * tile, tile)
        for at in range(0, tile, diag):
            kv = at_keys(pl.ds(start + at, diag))
            for b in blocks[at // block:]:
                # The block's queries at or after the piece: all of
                # them but in the block the diagonal crosses.
                carry = update_block(
                    kv, b, carry, max(at - b * block, 0),
                    corner_mask if b == at // block else None,
                )
        return carry

    def finish(carry):
        carry = diagonal_tile(carry) if causal else carry
        # Several blocks carry nothing: their statistics are the scratch.
        m, l, acc = carry or (ref[...] for ref in state)
        l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = m + jnp.log(l)

    def clear():
        for ref, value in zip(state, (NEG_INF, 0.0, 0.0)):
            ref[...] = jnp.full_like(ref, value)

    # (A ``when`` around the whole body even where it always holds,
    # K/V resident: interpret mode under a shard_map cannot run block
    # accesses in a kernel's own straight line, only inside regions.)
    @pl.when(ci <= last)
    def _chunk():
        if num_chunks > 1:
            pl.when(ci == 0)(clear)
        elif len(blocks) > 1:
            clear()
        if len(blocks) > 1:
            carry = ()  # nothing but the scratch
        elif num_chunks > 1:
            carry = tuple(ref[...] for ref in state)
        else:
            carry = (
                jnp.full((1, tile), NEG_INF, jnp.float32),
                jnp.zeros((1, tile), jnp.float32),
                jnp.zeros((v_dim, tile), jnp.float32),
            )
        # Key tiles of the chunk that every query of the tile sees
        # whole: those before the diagonal's, or all.
        below = chunk_tiles
        if causal:
            below = jnp.minimum(qi - first_tile, chunk_tiles)
        carry = lax.fori_loop(0, below, whole_tile, carry)
        if num_chunks == 1:
            finish(carry)
        else:
            for ref, value in zip(state, carry):
                ref[...] = value
            pl.when(ci == last)(functools.partial(finish, carry))


def _fwd_block(tile: int, head_dim: int, v_dim: int) -> int:
    """Queries (= keys) of one forward update. Widths that fill the
    MXU's rows, multiples of ``_LANES``: ``_LANES``, so a block's
    logits are sixteen vregs that never leave the registers and a key
    tile is ``(tile / _LANES) ** 2`` updates in one straight line.
    Any other width: the tile, the update of before (measured, PERF.md
    PR 57: at head 64 and at 192 the small updates run at half the
    speed of the whole tile's)."""
    widths_fill = head_dim % _LANES == 0 and v_dim % _LANES == 0
    return _LANES if widths_fill and tile % _LANES == 0 else tile


def _fwd_updates(
    sched: _Schedule, block: int, seq_len: int, causal: bool
) -> int:
    """Softmax updates one (batch, head) makes forward: ``block``
    queries' with ``block`` keys in a tile every query sees whole,
    with ``diag`` keys in the tile the diagonal crosses, there only
    the blocks at or after the piece."""
    num_q, across = seq_len // sched.tile, sched.tile // block
    if not causal:
        return (num_q * across) ** 2
    crossed = sum(
        across - at // block for at in range(0, sched.tile, sched.diag)
    )
    return across**2 * num_q * (num_q - 1) // 2 + num_q * crossed


def _fwd_pallas(q, k, v, causal, scale, block_q, block_k, with_lse):
    """q: [bh, d, seq], k: [bh / group, d, seq], v: [bh / group, dv,
    seq] -> (out [bh, dv, seq], lse [bh, 1, seq] or None)."""
    bh, head_dim, seq_len = q.shape
    v_dim = v.shape[1]
    group = _kv_group(q, k)
    sched = _schedule(
        seq_len, head_dim, q.dtype.itemsize, block_q, block_k,
        v_dim=v_dim,
    )
    block = _fwd_block(sched.tile, head_dim, v_dim)
    # The diagonal tile's pieces are no larger than a block.
    sched = sched._replace(diag=min(sched.diag, block))
    tile, diag, chunk_k = sched
    num_chunks = seq_len // chunk_k
    updates = _fwd_updates(sched, block, seq_len, causal)
    trace.event(
        "flash.schedule",
        seq_len=seq_len,
        head_dim=head_dim,
        dtype=q.dtype.name,
        causal=causal,
        layout=LAYOUT,
        kv_resident=num_chunks == 1,
        tile=tile,
        diag_tile=diag,
        grid_steps=bh * (seq_len // tile) * num_chunks,
        k_tiles_visited=_tiles_visited(sched, seq_len, causal),
        k_tiles_total=(seq_len // diag) ** 2,
        kv_group=group,
        kv_heads=bh // group,
        # An update: ``piece`` queries' with ``piece`` keys; a key
        # tile's stand ``pieces_in_flight`` abreast in one straight
        # line, independent of each other, and an update is
        # ``overlapped`` where another block's stands beside it.
        piece=block,
        pieces_in_flight=tile // block,
        updates_overlapped=updates if block < tile else 0,
        updates_total=updates,
    )
    return _fwd_call(
        q, k, v, causal=causal, scale=scale, tile=tile, diag=diag,
        block=block, chunk_k=chunk_k, with_lse=with_lse,
        interpret=_use_interpret(),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "tile", "diag", "block", "chunk_k", "with_lse",
        "interpret",
    ),
)
def _fwd_call(
    q, k, v, *, causal, scale, tile, diag, block, chunk_k, with_lse,
    interpret,
):
    """The forward kernel's call on a schedule, jitted: a model calls
    it once a layer and run of heads, a hundred times a step program
    (glm-4.7-flash), and every call after the first of a signature
    finds the kernel traced, and lowered once a program."""
    bh, head_dim, seq_len = q.shape
    v_dim = v.shape[1]
    group = _kv_group(q, k)
    num_chunks = seq_len // chunk_k
    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        scale=scale,
        diag=diag,
        block=block,
        num_chunks=num_chunks,
        with_lse=with_lse,
    )

    def kv_index(b, qi, ci):
        if causal:
            # A chunk above the diagonal repeats the index of the one
            # the diagonal is in: nothing is fetched for it, and the
            # kernel does nothing in it.
            ci = jnp.minimum(ci, qi * tile // chunk_k)
        # The head's kv head: while K/V are resident, the heads of a
        # group repeat one index in turn and it is fetched once.
        return (_kv_row(b, group), 0, ci)

    q_spec = pl.BlockSpec(
        (1, head_dim, tile), lambda b, qi, ci: (b, 0, qi)
    )
    kv_spec = pl.BlockSpec((1, head_dim, chunk_k), kv_index)
    v_spec = pl.BlockSpec((1, v_dim, chunk_k), kv_index)
    # Inside a shard_map (the trainer's data/seq axes) pallas outputs
    # must declare how they vary: the same way q does.
    vma = jax.typeof(q).vma
    out_specs = [
        pl.BlockSpec((1, v_dim, tile), lambda b, qi, ci: (b, 0, qi))
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bh, v_dim, seq_len), q.dtype, vma=vma)
    ]
    if with_lse:
        # One float32 per query row, rows along lanes.
        out_specs.append(
            pl.BlockSpec((1, 1, tile), lambda b, qi, ci: (b, 0, qi))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((bh, 1, seq_len), jnp.float32, vma=vma)
        )
    scratch_shapes = []
    if num_chunks > 1 or block < tile:
        scratch_shapes = [
            pltpu.VMEM((1, tile), jnp.float32),  # running max
            pltpu.VMEM((1, tile), jnp.float32),  # running sum
            pltpu.VMEM((v_dim, tile), jnp.float32),  # accumulator
        ]
    # The scope is the custom call's name in a compiled program and in
    # a device trace (``%attention.<n>``), under a model's own scopes
    # or none: the benchmark's readers find the forward by it.
    with jax.named_scope(FWD_KERNEL_NAME):
        out, *lse = pl.pallas_call(
            kernel,
            grid=(bh, seq_len // tile, num_chunks),
            in_specs=[q_spec, kv_spec, v_spec],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
        )(q, k, v)
    return out, (lse[0] if with_lse else None)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    window: int | None = None,
):
    """Blockwise exact attention.

    Args:
      q, k, v: ``[batch, heads, seq, head_dim]``; v's heads may have
        another width than q's and k's, and the result has v's. k and
        v may have FEWER heads than q, ``kv_heads`` with ``heads %
        kv_heads == 0`` (grouped-query attention): query head ``h``
        sees kv head ``h // (heads // kv_heads)``, what
        ``jnp.repeat(k, heads // kv_heads, axis=1)`` would hand it,
        and nothing is repeated: the kernels index K and V by kv
        head, and dK / dV come back ``kv_heads`` wide, summed over a
        group's query heads in float32 and rounded once.
      causal: apply the causal mask.
      scale: logit scale; default ``head_dim ** -0.5``.
      block_q / block_k: the caller's tile granularity (must divide
        seq; ``min(block, seq)`` is used). Both kernels fuse adjacent
        tiles and decide themselves how much a grid step and an
        update cover (module docstring).
      window: a lower bound on the keys (sliding-window attention,
        causal only): query ``i`` sees the keys ``j <= i`` with ``i -
        j < window``, itself and the ``window - 1`` before it. None,
        or a window that reaches the row's start from its last query
        (``window >= seq``), is plain causal attention: the same
        program. A shorter one takes the band schedule (module
        docstring, "A lower bound on the keys").

    Returns:
      ``[batch, heads, seq, v's head_dim]``, dtype of ``q``.
    """
    *_, out, _ = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, False, window
    )
    return _from_kernel(out, q.shape[:3] + v.shape[3:])


def _to_kernel(x):
    """``[batch, heads, seq, head_dim]`` as the kernels index it:
    ``[batch * heads, head_dim, seq]``, positions along lanes. That is
    how XLA itself lays out what the QKV projection and rotary write
    and what the out projection and their gradients read (a minor
    dimension of ``head_dim`` would waste lanes), so inside a model
    this swap and the model's own to ``[batch, heads, seq, head_dim]``
    are transposes of each other that cost no copy."""
    batch, heads, seq_len, head_dim = x.shape
    return jnp.swapaxes(x, 2, 3).reshape(batch * heads, head_dim, seq_len)


def _from_kernel(x, shape):
    batch, heads, seq_len, head_dim = shape
    return jnp.swapaxes(x.reshape(batch, heads, head_dim, seq_len), 2, 3)


def _band(window, seq_len: int, causal: bool) -> int | None:
    """The window where it bounds the walk: None for no window and for
    one that every query's keys fit (today's program)."""
    if window is None or window >= seq_len:
        return None
    if not causal or window < 1:
        raise ValueError(
            f"window={window}: a window is a lower bound on a CAUSAL "
            "query's keys, at least the query itself"
        )
    return int(window)


def _flash_fwd(
    q, k, v, causal, scale, block_q, block_k, with_lse, window=None
):
    """-> (q, k, v, out, lse) as the kernels index them."""
    head_dim = q.shape[-1]
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError(
            f"{q.shape[1]} query heads on {k.shape[1]} key and "
            f"{v.shape[1]} value heads: kv heads must divide the query "
            "heads"
        )
    resolved_scale = (
        head_dim**-0.5 if scale is None else float(scale)
    )
    operands = tuple(_to_kernel(x) for x in (q, k, v))
    if with_lse:  # the forward rule: what a remat around it may keep
        operands = tuple(checkpoint_name(x, SAVED_QKV) for x in operands)
    band = _band(window, q.shape[2], causal)
    if band is None:
        out, lse = _fwd_pallas(
            *operands, causal, resolved_scale, block_q, block_k, with_lse
        )
    else:
        out, lse = _window_fwd_pallas(
            *operands, resolved_scale, block_q, block_k, band, with_lse
        )
    return (*operands, out, lse)


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, window=None):
    """Residuals ``(q, k, v, out, lse)`` as the backward kernel reads
    them (a copy here would be a copy a step): the first four in the
    kernels' layout, ``lse`` as ``[batch * heads, 1, seq]``. All five
    are NAMED before anything reads them (the operands in
    ``_flash_fwd``), so that a ``jax.checkpoint`` policy around the
    call can keep them: a name inside a ``custom_vjp``'s forward rule
    is seen by the enclosing remat."""
    *operands, out, lse = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, True, window
    )
    out = checkpoint_name(out, SAVED_OUT)
    lse = checkpoint_name(lse, SAVED_LSE)
    residuals = (*operands, out, lse)
    return _from_kernel(out, q.shape[:3] + v.shape[3:]), residuals


def _bwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    o_ref,
    lse_ref,
    dq_ref,
    dk_ref,
    dv_ref,
    delta_ref,
    dq_acc,
    dk_acc,
    dv_acc,
    *turned,
    causal: bool,
    scale: float,
    diag: int,
    num_chunks: int,
    group: int,
):
    """One grid step of the backward: one (batch, kv head), one chunk
    of keys (all of them when K/V are resident), one of the kv head's
    ``group`` query heads, one query tile. The
    forward's schedule with the loops swapped: the chunk's K and V stay
    while the group's query tiles pass, its dK and dV accumulate in
    float32 scratch over the query tiles at or after it of EVERY query
    head of the group, and the query tile's
    dQ over the chunk's keys at or before it. Blocks are ``[head_dim,
    positions]`` and logits ``[keys, queries]``, so ``lse`` and
    ``delta`` broadcast along sublanes, dV = dO P^T and dK = Q dS^T
    contract over lanes as they stand, and dQ = K dS is a plain
    product. Every update covers ``diag`` keys: a traced loop walks
    those below the diagonal tile unmasked; the diagonal tile's pieces
    take only the queries at or after them, corner block masked."""
    _, head_dim, tile = q_ref.shape  # v and dO may be wider or narrower
    chunk_k = k_ref.shape[2]
    chunk_tiles, per_tile = chunk_k // tile, tile // diag
    # The grid: (batch * kv heads, chunk, [query head of the group,]
    # query tile); with equal head counts there is no third axis.
    tiles = 2 if group == 1 else 3
    ci, qi = pl.program_id(1), pl.program_id(tiles)

    def at_group(tile_index, head_index):
        """Whether the step is the kv head's and chunk's first (0, 0)
        or last: dK / dV are set up in the one and written out in the
        other."""
        here = qi == tile_index
        if group > 1:
            here &= pl.program_id(2) == head_index
        return here

    first_tile = ci * chunk_tiles  # of this chunk, among all key tiles
    nt = (((1,), (1,)), ((), ()))  # a @ b.T
    lead = (0,) * (len(dq_ref.shape) - 2)  # [chunk,] batch-head
    kt_ref, vt_ref = turned or (None, None)

    def across_head_dim(ref, turned_ref, keys, x):
        """``ref[0, :, keys].T @ x``: K or V of ``keys`` against q or
        dO over ``head_dim``, which both hold along sublanes. One of
        the two has to be turned: the piece here, update by update,
        or, where more than one query tile passes the chunk, the whole
        chunk once (``turned_ref``)."""
        if turned_ref is not None:
            return jnp.dot(
                turned_ref[keys, :], x, preferred_element_type=jnp.float32
            )
        return lax.dot_general(
            ref[0, :, keys], x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def update(keys, queries, mask=None):
        q, do = q_ref[0, :, queries], do_ref[0, :, queries]  # [d, n]
        s = scale * across_head_dim(k_ref, kt_ref, keys, q)  # [keys, queries]
        if mask is not None:
            s = mask(s)
        p = jnp.exp(s - lse_ref[0, :, queries])
        dp = across_head_dim(v_ref, vt_ref, keys, do)
        ds = (p * (dp - delta_ref[:, queries])).astype(q.dtype)
        dv_acc[:, keys] += lax.dot_general(
            do, p.astype(do.dtype), nt,
            preferred_element_type=jnp.float32,
        )
        dk_acc[:, keys] += lax.dot_general(
            q, ds, nt, preferred_element_type=jnp.float32
        )
        dq_acc[:, queries] += jnp.dot(
            k_ref[0, :, keys], ds, preferred_element_type=jnp.float32
        )

    def piece_below(t, carry):
        update(pl.ds(pl.multiple_of(t * diag, diag), diag), slice(None))
        return carry

    def diagonal_tile():
        start = pl.multiple_of((qi - first_tile) * tile, tile)
        mask = functools.partial(_corner_mask, diag=diag)
        for lo in range(0, tile, diag):
            update(pl.ds(start + lo, diag), slice(lo, None), mask)

    # (Every block access sits inside a ``when``, as in the forward:
    # interpret mode under a shard_map needs it.)
    @pl.when(at_group(0, 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        for ref, turned_ref in zip((k_ref, v_ref), turned):
            turned_ref[...] = ref[0].T

    # Query tiles before the chunk see none of its keys.
    sees = qi >= first_tile if causal else qi >= 0

    @pl.when(sees)
    def _tile():
        # delta = rowsum(dO * O) in float32, positions along lanes as
        # lse has them.
        d_o = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        delta_ref[...] = jnp.sum(d_o, axis=0, keepdims=True)
        dq_acc[...] = jnp.zeros_like(dq_acc)
        below = chunk_tiles
        if causal:
            below = jnp.minimum(qi - first_tile, chunk_tiles)
        lax.fori_loop(0, below * per_tile, piece_below, 0)
        if causal and num_chunks == 1:
            diagonal_tile()
        elif causal:
            pl.when(qi - first_tile < chunk_tiles)(diagonal_tile)
        dq_ref[lead] = (scale * dq_acc[...]).astype(dq_ref.dtype)

    if causal and num_chunks > 1:

        @pl.when(jnp.logical_not(sees))
        def _no_keys():
            dq_ref[lead] = jnp.zeros((head_dim, tile), dq_ref.dtype)

    @pl.when(at_group(pl.num_programs(tiles) - 1, group - 1))
    def _finish():
        dk_ref[0] = (scale * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, do, out, lse, causal, scale, block_q, block_k):
    """q: [bh, d, seq]; k: [bh / group, d, seq]; v: [bh / group, dv,
    seq]; do/out: [bh, dv, seq]; lse: [bh, 1, seq] float32 -> (dq, dk,
    dv), each as its primal."""
    bh, head_dim, seq_len = q.shape
    v_dim = v.shape[1]
    group = _kv_group(q, k)
    sched = _schedule(
        seq_len, head_dim, q.dtype.itemsize, block_q, block_k,
        diag_rows=_BWD_DIAG_ROWS, v_dim=v_dim,
    )
    tile, diag, chunk_k = sched
    num_chunks = seq_len // chunk_k
    # ``at``: a grid step's (query row, kv row, chunk, query tile).
    # The group's query heads pass INSIDE a kv head and chunk, so the
    # chunk's K, V, dK and dV stay while all of them gather.
    if group == 1:
        grid = (bh, num_chunks, seq_len // tile)

        def at(b, ci, qi):
            return b, b, ci, qi
    else:
        grid = (bh // group, num_chunks, group, seq_len // tile)

        def at(b, ci, gi, qi):
            return b * group + gi, b, ci, qi

    visited = _tiles_visited(sched, seq_len, causal)
    trace.event(
        "flash.schedule_bwd",
        seq_len=seq_len,
        head_dim=head_dim,
        dtype=q.dtype.name,
        causal=causal,
        layout=LAYOUT,
        kv_resident=num_chunks == 1,
        tile=tile,
        diag_tile=diag,
        grid_steps=math.prod(grid),
        kernels=1,  # dK, dV and dQ from one recomputation of P, dS
        dkv_tiles_visited=visited,
        dq_tiles_visited=visited,
        k_tiles_total=(seq_len // diag) ** 2,
        kv_group=group,
        kv_heads=bh // group,
    )

    def q_index(*step):
        row, _, ci, qi = at(*step)
        if causal:
            # A query tile before the chunk repeats the index of the
            # first that sees it: nothing is fetched for it.
            qi = jnp.maximum(qi, ci * chunk_k // tile)
        return (row, 0, qi)

    def kv_index(*step):
        _, kv_row, ci, _ = at(*step)
        return (kv_row, 0, ci)

    q_spec = pl.BlockSpec((1, head_dim, tile), q_index)
    lse_spec = pl.BlockSpec((1, 1, tile), q_index)
    kv_spec = pl.BlockSpec((1, head_dim, chunk_k), kv_index)
    v_spec = pl.BlockSpec((1, v_dim, chunk_k), kv_index)
    do_spec = pl.BlockSpec((1, v_dim, tile), q_index)
    vma = jax.typeof(q).vma
    # dQ of a query tile is summed over the key chunks: the gradient
    # itself while K/V are resident; beyond, one float32 partial per
    # chunk, added up outside. A query head's either way.
    if num_chunks == 1:

        def dq_index(*step):
            row, _, _, qi = at(*step)
            return (row, 0, qi)

        dq_spec = pl.BlockSpec((1, head_dim, tile), dq_index)
        dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma)
    else:

        def dq_index(*step):
            row, _, ci, qi = at(*step)
            return (ci, row, 0, qi)

        dq_spec = pl.BlockSpec((1, 1, head_dim, tile), dq_index)
        dq_shape = jax.ShapeDtypeStruct(
            (num_chunks, *q.shape), jnp.float32, vma=vma
        )
    # Where more than one query tile passes a chunk: its K and V once
    # more with keys along sublanes (measured, PERF.md PR 27: 2.5-4% of
    # the kernel at 4096 and 16k keys; with one tile a chunk, turning
    # piece by piece costs 4% less).
    turned = []
    if seq_len > tile:
        turned = [
            pltpu.VMEM((chunk_k, head_dim), k.dtype),
            pltpu.VMEM((chunk_k, v_dim), v.dtype),
        ]
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel,
            causal=causal,
            scale=scale,
            diag=diag,
            num_chunks=num_chunks,
            group=group,
        ),
        grid=grid,
        in_specs=[q_spec, kv_spec, v_spec, do_spec, do_spec, lse_spec],
        out_specs=[dq_spec, kv_spec, v_spec],
        out_shape=[
            dq_shape,
            jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
            jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, tile), jnp.float32),  # delta of the tile
            pltpu.VMEM((head_dim, tile), jnp.float32),  # dQ of the tile
            pltpu.VMEM((head_dim, chunk_k), jnp.float32),  # dK of the chunk
            pltpu.VMEM((v_dim, chunk_k), jnp.float32),  # dV of the chunk
        ]
        + turned,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
            + ("arbitrary",) * (len(grid) - 1),
            vmem_limit_bytes=_VMEM_LIMIT_BWD,
        ),
        interpret=_use_interpret(),
        # Not the forward's ``%attention.<n>``: the trace tells the two
        # kernels apart by name.
        name=BWD_KERNEL_NAME,
    )(q, k, v, do, out, lse)
    if num_chunks > 1:
        dq = jnp.sum(dq, axis=0).astype(q.dtype)
    return dq, dk, dv


def _flash_vjp_bwd(causal, scale, block_q, block_k, window, residuals, g):
    """The flash-attention backward identities, on the forward's
    schedule in one Pallas kernel (:func:`_bwd_kernel`): P is
    recomputed from the saved log-sum-exp, never stored.

        P = exp(S - lse)
        dV = P^T dO
        dP = dO V^T
        dS = P * (dP - delta),  delta = rowsum(dO * O)
        dQ = dS K * scale ;  dK = dS^T Q * scale
    """
    q, k, v, out, lse = residuals
    head_dim = q.shape[1]
    resolved_scale = head_dim**-0.5 if scale is None else float(scale)
    band = _band(window, q.shape[2], causal)
    if band is None:
        grads = _bwd_pallas(
            q, k, v, _to_kernel(g), out, lse,
            causal, resolved_scale, block_q, block_k,
        )
    else:
        grads = _window_bwd_pallas(
            q, k, v, _to_kernel(g), out, lse,
            resolved_scale, block_q, block_k, band,
        )
    batch, _, seq_len, _ = g.shape
    return tuple(
        _from_kernel(x, (batch, x.shape[0] // batch, seq_len, x.shape[1]))
        for x in grads
    )


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
# What a caller with fewer kv heads than query heads looks for on the
# function it was handed, behind any ``functools.partial``
# (``models.transformer._takes_kv_heads``): k and v go in ``kv_heads``
# wide. On :func:`make_flash_attention`'s result too.
flash_attention.takes_kv_heads = True


# ---- a lower bound on the keys: the band schedule ---------------------
#
# With ``window`` < ``seq_len`` a query's keys are the ``window`` ending
# at itself, so a query tile needs its own K/V block and the
# ``ceil((window - 1) / tile)`` before it, whatever the row's length:
# K and V are blocks that FOLLOW the query tile (the same array handed
# to the call once a block, each with its own index map), never a
# head's whole K and V and never a third grid axis. Inside a tile the
# band is walked in ``piece``-key updates, each with exactly the query
# blocks that see one of its keys; the block the diagonal crosses and
# the block(s) the band's LOWER edge crosses are masked, every other
# one is multiplied whole.

# Query rows of a grid step (= keys of one K/V block), and the keys of
# one update: whole pieces multiply (window + piece) / window times the
# band's pairs (512: 2.0 x, 256: 1.5 x, 128: 1.25 x) and smaller ones
# fill the MXU worse. Measured on a v5e at (1, 16, 16384, 128) bf16,
# window 512, 16 chained calls, ms a call forward / forward + backward
# (PERF.md, PR 54), tile, forward piece, backward piece: 1024 256 256
# 1.055 / 2.744; 1024 128 128 1.048 / 2.710; 1024 512 256 1.083 /
# 2.775; 1024 512 512 1.081 / 3.162; 512 256 256 1.331 / 3.202; 512
# 128 128 1.320 / 3.336; 512 512 256 1.232 / 3.109; 256 128 128 2.210
# / 5.243; 256 256 256 2.316 / 5.012; the same heads walked causally
# whole 10.10 / 26.23. The tile decides (grid steps cost more than the
# half K/V block a tile of 1024 fetches for nothing); between pieces of
# 128 and 256 the time is the same to 1% and 256 is half the code.
_WINDOW_TILE = 1024
_WINDOW_PIECE = 256
_WINDOW_PIECE_BWD = 256
# The band kernels' names in a lowered program and in a device trace
# (``%window_attn_fwd.<n>``): neither ``%attention`` nor ``flash_bwd``,
# so the readers of the full layers' kernels keep reading those alone.
WINDOW_FWD_NAME = "window_attn_fwd"
WINDOW_BWD_NAME = "window_attn_bwd"


class _BandSchedule(NamedTuple):
    """How a windowed call is laid on the grid (forward and backward
    alike, but for ``piece``)."""

    tile: int  # query rows of a grid step = keys of one K/V block
    piece: int  # keys of one update = queries of one (un)masked block
    before: int  # K/V blocks before the tile's own that hold its keys


def _band_schedule(
    seq_len: int, window: int, block_q: int, block_k: int, piece_rows: int
) -> _BandSchedule:
    block_q, block_k = min(block_q, seq_len), min(block_k, seq_len)
    assert seq_len % block_q == 0 and seq_len % block_k == 0, (
        f"seq_len {seq_len} must divide into blocks "
        f"({block_q}, {block_k})"
    )
    tile = _fuse(math.gcd(block_q, block_k), seq_len, _WINDOW_TILE)
    piece = tile
    if tile % _LANES == 0:
        piece = _fuse(_LANES, tile, piece_rows)
    return _BandSchedule(tile, piece, -(-(window - 1) // tile))


def _band_stop(a: int, piece: int, window: int) -> int:
    """Where the queries that see a key of the piece at ``a`` stop:
    query block ``b`` (a whole piece) sees one iff ``a <= b`` (causal)
    and ``b - (a + piece - 1) < window``."""
    return (a + piece + window - 2) // piece * piece + piece


def _band_updates(sched: _BandSchedule, window: int):
    """The updates of ONE query tile, static: ``[(block, at, first,
    stop)]`` — the ``piece`` keys at ``at`` of K/V block ``block`` (0
    the tile's own, 1 the one before it, ...) against the tile's
    queries ``first .. stop`` (whole ``piece`` blocks: those that see
    at least one of these keys). In the order the forward's online
    softmax needs: keys descending, so the first update a query is in
    holds its own position, a visible key."""
    tile, piece, before = sched
    updates = []
    for a in range(tile - piece, -before * tile - 1, -piece):
        # ``a``: the piece's first key, from the tile's first query.
        first, stop = max(a, 0), min(tile, _band_stop(a, piece, window))
        if stop > first:
            updates.append((-(a // tile), a % tile, first, stop))
    return updates


def _band_mask(s, a: int, first: int, piece: int, window: int):
    """Mask transposed logits ``[piece keys from a, queries from
    first]`` (positions from the tile's first query, static) to the
    band ``0 <= query - key < window``, block of ``piece`` queries by
    block: only a block an edge of the band crosses is touched."""
    blocks = []
    for b in range(first, first + s.shape[1], piece):
        block = s[:, b - first:b - first + piece]
        # query - key over the block: from b - (a + piece - 1) to
        # b + piece - 1 - a.
        above, below = b - (a + piece - 1) < 0, b + piece - 1 - a >= window
        if above or below:
            ahead = (
                lax.broadcasted_iota(jnp.int32, block.shape, 1)
                - lax.broadcasted_iota(jnp.int32, block.shape, 0)
                + (b - a)
            )
            visible = ahead >= 0 if above else ahead < window
            if above and below:
                visible = (ahead >= 0) & (ahead < window)
            block = jnp.where(visible, block, NEG_INF)
        blocks.append(block)
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)


def _band_tiles(
    sched: _BandSchedule, seq_len: int, window: int
) -> tuple[int, int]:
    """(``piece`` x ``piece``) blocks of logits one (batch, head)
    computes under the band schedule (summed over its query tiles'
    updates), and how many such blocks of the whole row hold a pair of
    the band at all (counted a key piece at a time over the row, not
    from the updates: the least a kernel of whole blocks can do)."""
    tile, piece, _ = sched
    updates = _band_updates(sched, window)
    visited = sum(
        (stop - first) // piece
        for qi in range(seq_len // tile)
        for block, _at, first, stop in updates
        if qi >= block
    )
    in_band = sum(
        min(seq_len, _band_stop(a, piece, window)) - a
        for a in range(0, seq_len, piece)
    ) // piece
    return visited, in_band


def _operand_precision(dtype):
    """The MXU's passes for a band kernel's products: the default for
    bfloat16 operands (exact in float32), ``HIGHEST`` for float32
    operands — by default the chip multiplies those in ONE bfloat16
    pass (measured, PERF.md PR 54: 3e-3 of the output), which "float32
    in means float32 operands" does not mean."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _band_grid(bh: int, group: int, tiles: int, tail: int = 0):
    """The band kernels' grid over ``bh`` query rows, ``group`` of
    them a kv row, and ``tiles`` query tiles (``tail`` more steps, the
    backward's that flush its ring): ``(grid, at)``, ``at`` a grid
    step's (query row, kv row, tile). The group's query heads are the
    INNERMOST axis, so the K/V blocks of a tile are fetched once for
    all of them, and the
    backward's ring gathers every query head's dK / dV of a block
    before it is written. With equal head counts there is no such
    axis: the program of before there were groups."""
    if group == 1:
        return (bh, tiles + tail), lambda b, qi: (b, b, qi)

    def at(b, qi, gi):
        if tail:
            # A step past the last tile fetches and gathers nothing:
            # it repeats the indices of the step before it.
            gi = jnp.where(qi < tiles, gi, group - 1)
        return b * group + gi, b, qi

    return (bh // group, tiles + tail, group), at


def keys_in_window(seq_len: int, window: int) -> int:
    """``sum_i min(i + 1, window)``: the pairs of one row's band."""
    reach = min(window, seq_len)
    return reach * (reach + 1) // 2 + (seq_len - reach) * reach


def _window_fwd_kernel(
    q_ref, *refs, scale: float, sched: _BandSchedule, window: int,
    with_lse: bool,
):
    """One grid step of the band's forward: one (batch, head), one
    query tile, its own K/V block and the ``before`` ahead of it (the
    heads of a kv head's group pass in turn under one tile's blocks:
    ``_band_grid``).
    Blocks ``[head_dim, positions]``, logits ``[keys, queries]``, the
    online softmax of :func:`_fwd_kernel`; every update's key piece and
    query range are static (``_band_updates``), and a block before the
    row's start is skipped whole."""
    tile, piece, before = sched
    k_refs, v_refs = refs[:before + 1], refs[before + 1:2 * before + 2]
    o_ref = refs[2 * before + 2]
    lse_ref = refs[2 * before + 3] if with_lse else None
    v_dim = o_ref.shape[1]
    qi = pl.program_id(1)
    precision = _operand_precision(q_ref.dtype)

    def update(block, at, first, stop, carry):
        m_prev, l_prev, acc = (x[:, first:stop] for x in carry)
        q = q_ref[0, :, first:stop]
        k = k_refs[block][0, :, at:at + piece]
        v = v_refs[block][0, :, at:at + piece]
        s = scale * lax.dot_general(
            k, q, (((0,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        )  # [keys, queries]
        s = _band_mask(s, at - block * tile, first, piece, window)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_next)
        rescale = jnp.exp(m_prev - m_next)
        l_next = l_prev * rescale + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * rescale + jnp.dot(
            v, p.astype(v.dtype), precision=precision,
            preferred_element_type=jnp.float32,
        )
        def put(x, new):  # the tile's value with first .. stop replaced
            parts = [x[:, :first]] * (first > 0) + [new]
            parts += [x[:, stop:]] * (stop < tile)
            return parts[0] if len(parts) == 1 else jnp.concatenate(
                parts, axis=1
            )

        return tuple(map(put, carry, (m_next, l_next, acc)))

    updates = _band_updates(sched, window)

    def with_block(block):
        def run(carry):
            for at_block, at, first, stop in updates:
                if at_block == block:
                    carry = update(block, at, first, stop, carry)
            return carry

        return run

    # (Inside a ``when``, as every block access of this file.)
    @pl.when(qi >= 0)
    def _tile():
        carry = (
            jnp.full((1, tile), NEG_INF, jnp.float32),
            jnp.zeros((1, tile), jnp.float32),
            jnp.zeros((v_dim, tile), jnp.float32),
        )
        carry = with_block(0)(carry)
        for block in range(1, before + 1):
            # The first ``block`` tiles have no such block before them.
            carry = lax.cond(
                qi >= block, with_block(block), lambda c: c, carry
            )
        m, l, acc = carry
        l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = m + jnp.log(l)


def _window_fwd_pallas(q, k, v, scale, block_q, block_k, window, with_lse):
    """q: [bh, d, seq], k: [bh / group, d, seq], v: [bh / group, dv,
    seq] -> (out [bh, dv, seq], lse [bh, 1, seq] or None), each query
    over its ``window`` keys."""
    bh, head_dim, seq_len = q.shape
    v_dim = v.shape[1]
    group = _kv_group(q, k)
    sched = _band_schedule(seq_len, window, block_q, block_k, _WINDOW_PIECE)
    tile, piece, before = sched
    grid, at = _band_grid(bh, group, seq_len // tile)
    visited, in_band = _band_tiles(sched, seq_len, window)
    trace.event(
        "flash.schedule",
        seq_len=seq_len,
        head_dim=head_dim,
        dtype=q.dtype.name,
        causal=True,
        layout=LAYOUT,
        kv_resident=False,  # blocks that follow the query tile
        tile=tile,
        diag_tile=piece,
        grid_steps=math.prod(grid),
        k_tiles_visited=visited,
        k_tiles_total=(seq_len // piece) ** 2,
        window=window,
        kv_blocks=before + 1,
        tiles_visited=visited,
        tiles_in_band=in_band,
        kv_group=group,
        kv_heads=bh // group,
    )
    # What the pair of kernels multiplies a row's queries with, against
    # the band itself: static, so journalled here where the schedule is
    # chosen (the backward's from its own schedule, which is a function
    # of the same shapes).
    bwd = _band_schedule(seq_len, window, block_q, block_k, _WINDOW_PIECE_BWD)
    columns = (
        visited * piece**2 + _band_tiles(bwd, seq_len, window)[0] * bwd.piece**2
    )
    trace.event(
        "window.keys",
        seq_len=seq_len,
        window=window,
        batch_heads=bh,
        keys_visited=columns / 2,  # a query row set, mean of the two
        keys_visited_fwd=visited * piece**2,
        keys_visited_bwd=columns - visited * piece**2,
        keys_in_window=keys_in_window(seq_len, window),
    )

    def block_spec(width, back, of_kv=False):
        def index(*step):
            row, kv_row, qi = at(*step)
            return (kv_row if of_kv else row, 0, jnp.maximum(qi - back, 0))

        return pl.BlockSpec((1, width, tile), index)

    vma = jax.typeof(q).vma
    out_specs = [block_spec(v_dim, 0)]
    out_shape = [jax.ShapeDtypeStruct((bh, v_dim, seq_len), q.dtype, vma=vma)]
    if with_lse:
        out_specs.append(block_spec(1, 0))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, 1, seq_len), jnp.float32, vma=vma)
        )
    blocks = range(before + 1)
    out, *lse = pl.pallas_call(
        functools.partial(
            _window_fwd_kernel, scale=scale, sched=sched, window=window,
            with_lse=with_lse,
        ),
        grid=grid,
        in_specs=[block_spec(head_dim, 0)]
        + [block_spec(head_dim, back, True) for back in blocks]
        + [block_spec(v_dim, back, True) for back in blocks],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
            + ("arbitrary",) * (len(grid) - 2),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_use_interpret(),
        name=WINDOW_FWD_NAME,
    )(q, *(k for _ in blocks), *(v for _ in blocks))
    return out, (lse[0] if with_lse else None)


def _window_bwd_kernel(
    q_ref, do_ref, o_ref, lse_ref, *refs, scale: float,
    sched: _BandSchedule, window: int, num_q: int, group: int,
):
    """One grid step of the band's backward: one (batch, kv head), one
    query tile with its own K/V block and the ``before`` ahead of it —
    the forward's walk, so dQ of the tile is whole when the step ends
    (no partial a chunk) —, one of the kv head's ``group`` query heads
    (``_band_grid``). dK and dV of a K/V block gather over the
    ``before + 1`` query tiles that see it, of every query head of the
    group, in a ring of as many
    float32 slots; a block's slot is written out, and free again, in
    the step of the last tile and query head that see it, and
    ``before`` tiles past
    the last query tile flush the ring. The identities and the layout
    are :func:`_bwd_kernel`'s."""
    tile, piece, before = sched
    ring = before + 1
    k_refs, v_refs = refs[:ring], refs[ring:2 * ring]
    dq_ref, dk_ref, dv_ref = refs[2 * ring:2 * ring + 3]
    delta_ref, dq_acc, dk_ring, dv_ring = refs[2 * ring + 3:]
    qi = pl.program_id(1)
    # (Read out here: interpret mode knows a grid index in the
    # kernel's own straight line alone.)
    gi = pl.program_id(2) if group > 1 else None

    def of_group(head_index, then):
        """``then`` where the step's query head is the group's
        ``head_index``-th: always, with equal head counts."""
        if group == 1:
            then()
        else:
            pl.when(gi == head_index)(then)

    nt = (((1,), (1,)), ((), ()))  # a @ b.T
    tn = (((0,), (0,)), ((), ()))  # a.T @ b
    dot = functools.partial(
        lax.dot_general, precision=_operand_precision(q_ref.dtype),
        preferred_element_type=jnp.float32,
    )

    def update(block, at, first, stop, slot):
        q, do = q_ref[0, :, first:stop], do_ref[0, :, first:stop]
        keys = slice(at, at + piece)
        k, v = k_refs[block][0, :, keys], v_refs[block][0, :, keys]
        s = scale * dot(k, q, tn)  # [keys, queries]
        s = _band_mask(s, at - block * tile, first, piece, window)
        p = jnp.exp(s - lse_ref[0, :, first:stop])
        dp = dot(v, do, tn)
        ds = (p * (dp - delta_ref[:, first:stop])).astype(q.dtype)
        dv_ring[slot, :, keys] += dot(do, p.astype(do.dtype), nt)
        dk_ring[slot, :, keys] += dot(q, ds, nt)
        dq_acc[:, first:stop] += dot(k, ds, (((1,), (0,)), ((), ())))

    updates = _band_updates(sched, window)

    def with_block(block):
        def run():
            slot = lax.rem(qi - block, ring)
            for at_block, at, first, stop in updates:
                if at_block == block:
                    update(block, at, first, stop, slot)

        return run

    @pl.when(qi < num_q)
    def _tile():
        # The tile's own K/V block enters the ring: its slot was
        # written out in the tile before.
        own = lax.rem(qi, ring)

        def _enter():
            dk_ring[own] = jnp.zeros(dk_ring.shape[1:], dk_ring.dtype)
            dv_ring[own] = jnp.zeros(dv_ring.shape[1:], dv_ring.dtype)

        of_group(0, _enter)
        d_o = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        delta_ref[...] = jnp.sum(d_o, axis=0, keepdims=True)
        dq_acc[...] = jnp.zeros_like(dq_acc)
        with_block(0)()
        for block in range(1, before + 1):
            pl.when(qi >= block)(with_block(block))
        dq_ref[0] = (scale * dq_acc[...]).astype(dq_ref.dtype)

    @pl.when(qi >= before)
    def _past():  # K/V block qi - before: no later tile sees it
        def _flush():
            slot = lax.rem(qi - before, ring)
            dk_ref[0] = (scale * dk_ring[slot]).astype(dk_ref.dtype)
            dv_ref[0] = dv_ring[slot].astype(dv_ref.dtype)

        of_group(group - 1, _flush)


def _window_bwd_pallas(q, k, v, do, out, lse, scale, block_q, block_k, window):
    """As :func:`_bwd_pallas`, each query over its ``window`` keys."""
    bh, head_dim, seq_len = q.shape
    v_dim = v.shape[1]
    group = _kv_group(q, k)
    sched = _band_schedule(
        seq_len, window, block_q, block_k, _WINDOW_PIECE_BWD
    )
    tile, piece, before = sched
    num_q = seq_len // tile
    grid, at = _band_grid(bh, group, num_q, tail=before)
    visited, in_band = _band_tiles(sched, seq_len, window)
    trace.event(
        "flash.schedule_bwd",
        seq_len=seq_len,
        head_dim=head_dim,
        dtype=q.dtype.name,
        causal=True,
        layout=LAYOUT,
        kv_resident=False,
        tile=tile,
        diag_tile=piece,
        grid_steps=math.prod(grid),
        kernels=1,
        dkv_tiles_visited=visited,
        dq_tiles_visited=visited,
        k_tiles_total=(seq_len // piece) ** 2,
        window=window,
        kv_blocks=before + 1,
        tiles_visited=visited,
        tiles_in_band=in_band,
        kv_group=group,
        kv_heads=bh // group,
    )

    def block_spec(width, back, of_kv=False):
        # ``back`` blocks before the step's query tile, held inside the
        # row: a step before the row's start or past its end repeats an
        # index, and nothing is fetched for it.
        def index(*step):
            row, kv_row, qi = at(*step)
            return (
                kv_row if of_kv else row, 0,
                jnp.clip(qi - back, 0, num_q - 1),
            )

        return pl.BlockSpec((1, width, tile), index)

    vma = jax.typeof(q).vma
    blocks = range(before + 1)
    return pl.pallas_call(
        functools.partial(
            _window_bwd_kernel, scale=scale, sched=sched, window=window,
            num_q=num_q, group=group,
        ),
        grid=grid,
        in_specs=[
            block_spec(head_dim, 0), block_spec(v_dim, 0),
            block_spec(v_dim, 0), block_spec(1, 0),
        ]
        + [block_spec(head_dim, back, True) for back in blocks]
        + [block_spec(v_dim, back, True) for back in blocks],
        out_specs=[
            block_spec(head_dim, 0), block_spec(head_dim, before, True),
            block_spec(v_dim, before, True),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
            jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, tile), jnp.float32),  # delta of the tile
            pltpu.VMEM((head_dim, tile), jnp.float32),  # dQ of the tile
            pltpu.VMEM((before + 1, head_dim, tile), jnp.float32),  # dK ring
            pltpu.VMEM((before + 1, v_dim, tile), jnp.float32),  # dV ring
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
            + ("arbitrary",) * (len(grid) - 1),
            vmem_limit_bytes=_VMEM_LIMIT_BWD,
        ),
        interpret=_use_interpret(),
        name=WINDOW_BWD_NAME,
    )(q, do, out, lse, *(k for _ in blocks), *(v for _ in blocks))


def heads_a_call(
    heads: int, seq_len: int, head_dim: int, v_dim: int, itemsize: int,
    block_q: int = 128, block_k: int = 128, window: int | None = None,
    group: int = 1,
) -> int:
    """How many heads (a divisor of ``heads``) a caller that can
    split them should give one call; with ``group`` query heads a kv
    head, whole groups or a divisor of one group, so that a call's kv
    heads are whole (a group of 7 goes 7, 14, 28 or 1 a call). All of
    them while K and V of a head stay in VMEM; beyond, the backward writes one float32 dQ a
    key chunk (``_bwd_pallas``), ``chunks * 4 / itemsize`` times q for
    the heads it is given: a call's partials are held to the bytes of
    q itself, all heads. (Blocks that do not divide the row are cut
    to ones that do: this asks about memory and refuses no shape.)
    Under a ``window`` shorter than the row nothing is chunked, no
    partial is written and, since the kernels index k and v by kv
    head, nothing is repeated for a call either: all of them.
    (Measured on a v5e, PERF.md PR 55: 64 heads of 128 on 8 kv heads
    at 16 384 keys, window 512, ONE call 4.74 ms forward / 11.10
    forward + backward with 256 / 516 MiB of temporaries; four calls
    of 16 and their concatenate 5.49 / 11.98 with 448 / 585. Until PR
    55 a call was held to 16 such heads, 64 MiB an operand, because
    its caller repeated k and v for it.)"""
    if window is not None and window < seq_len:
        return heads
    sched = _schedule(
        seq_len, head_dim, itemsize, math.gcd(block_q, seq_len),
        math.gcd(block_k, seq_len), diag_rows=_BWD_DIAG_ROWS, v_dim=v_dim,
    )
    chunks = seq_len // sched.chunk_k
    if chunks == 1:
        return heads
    at_once = max(1, heads * itemsize // (4 * chunks))
    return next(
        n for n in range(at_once, 0, -1)
        if heads % n == 0 and (n % group == 0 or group % n == 0)
    )


def make_flash_attention(
    causal: bool = True, block_q: int = 128, block_k: int = 128
):
    """Partial suitable for ``TransformerConfig.attention_fn``
    (signature ``attn(q, k, v) -> out``, and ``window=`` on a sliding
    layer); its ``heads_a_call`` is :func:`heads_a_call` at these
    blocks."""

    def attn(q, k, v, window=None):
        return flash_attention(
            q, k, v, causal, None, block_q, block_k, window
        )

    attn.heads_a_call = functools.partial(
        heads_a_call, block_q=block_q, block_k=block_k
    )
    attn.takes_kv_heads = True
    return attn
