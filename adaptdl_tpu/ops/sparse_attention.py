"""Attention over the keys a learned indexer selects, per query: Pallas
TPU kernels for the index scores, the exact selection, the attention
forward and backward over the selected pairs, and the indexer's own
Kullback-Leibler loss.

The mathematics (one batch row; ``t`` a query, ``s`` a key, ``s <= t``):

    I[t, s]  = sum_j w[t, j] * relu(qi[t, j] . ki[s])       float32
    S[t]     = the ``topk`` keys of largest I[t, .], ties to the lower
               key; every earlier key while ``t < topk``
    o[t, h]  = sum_{s in S[t]} softmax_{S[t]}(q[t, h] . k[s, g(h)]
               * scale) v[s, g(h)]                 g(h) = h // group
    p[t, s]  = mean_h softmax_{S[t]}(...)[t, h, s]   (no gradient)
    L_I[t]   = sum_{s in S[t]} p[t, s] (log p[t, s]
               - log softmax_{S[t]}(I[t, .])[s])

``sparse_attention`` returns ``(o, L_I per query, counters)``. The
cotangent of ``o`` reaches ``q, k, v`` over the selected pairs only and
nothing else; the cotangent of ``L_I`` reaches ``qi, ki, w`` and nothing
else; no gradient passes through the choice of ``S``.

**The schedule: every causal tile, masked.** The kernels (the
selection, the forward, the loss's second pass, the backward) walk the
key tiles at or below the diagonal and multiply every pair of a tile,
then mask what the selection left out: ``topk`` of up to ``seq`` keys
a query lie scattered over all key tiles (2048 of 16 384: on average 64
in every tile of 512), so no tile can be skipped, and a gather of 2048
keys a query would read ``seq * topk`` K and V rows per kv head from
HBM (69 GB a layer and row at 16k: 84 ms at the HBM peak) where the
dense tiles cost 2.2 TFLOP (11 ms at the MXU peak) and reuse each K/V
tile for 128 queries and 8 query heads. ``sparse.schedule`` in the
trace journal says ``path="causal_tiles_masked"``, and the
``keys_visited`` counter of ``sparse.select`` counts what the kernels
multiply, not what was selected.

**The backward visits a tile once where the row's accumulators fit
VMEM.** A tile adds to its QUERY tile's dq / dqI / dw and to its KEY
tile's dk / dv / dkI. ``sparse_attn_bwd`` walks (row, query tile, key
tile) with the query tile's accumulators in scratch as the forward
does, and holds dk, dv and dkI of the WHOLE ROW as float32 scratch
(``backward_schedule``: 72 MiB at 4 kv heads of 128 and a row of
16 384), added to at the key tile's offset and written out, tile by
tile, while the row's last query tile passes: mask, index products,
``S``, ``P``, ``dP`` and ``dS`` are computed once a tile and feed all
six gradients, five matrix products a pair and head. A row whose
accumulators are past ``_ROW_BUDGET`` (32 768 keys, or 8 kv heads at
16 384) takes ``sparse_attn_bwd_q`` and ``sparse_attn_bwd_kv``, which
hold one tile's accumulators each and compute the shared part twice
(seven products); ``sparse.schedule`` says which (``backward``,
``backward_vmem_bytes``). The sums run in the same order either way.

**The selection is two integers a query.** ``index_select`` holds the
scores of one query tile against all earlier keys in VMEM as
order-preserving int32 keys and finds the exact ``topk``-th largest by
32 steps of bisection on its bits (a count a step; no sort), then, only
where a query has more keys AT the threshold than it still needs, the
position up to which they are taken (ties to the lower key). A pair is
selected iff ``key > thr`` or ``key == thr and s <= cut``. The attention
kernels recompute a tile's scores with the same code on the same tile
shape and apply that rule, so the set never exists in HBM; a remat'd
block saves ``thr``, ``cut``, the indexer's log-sum-exp, ``out`` and
``lse`` by name (``SAVED_NAMES``) and runs neither the selection nor
the forward a second time.

**Layout.** Keys along sublanes, queries along lanes: logits are held
``[keys, queries]``, so a query's statistics (max, sum, threshold,
log-sum-exp) are ``[1, queries]`` rows, reductions over keys are plain
VPU adds, and nothing with a minor dimension of 1 is stored. The output
leaves as ``[batch, heads, head_dim, seq]`` and is turned outside.
All query heads of a query tile are one grid step, so the mask of a
tile is computed once for the 32 heads and dK / dV sum over a kv head's
group inside the kernel: no ``repeat`` of K and V. Inside the step
every kernel walks the heads 0 .. ``heads - 1`` in order, so every sum
keeps its order. The forward and the loss's second pass
(``_over_heads``; ``sparse.schedule``:
``head_loop="heads_abreast_in_pieces"``) stand as many heads as keep
within ``_LINE_UPDATES`` updates — all 32 at the published widths —
abreast as straight-line code, because only inside one straight line
does the scheduler run one update's matrix products under another's
max / exp / sum, and every line has a head and a tail where nothing
does. The forward makes a head's update of a tile in pieces of 128 keys
where ``head_dim`` and both tiles are whole 128s (``_piece``; the key
tile elsewhere): a piece's float32 logits ``[128 keys, 128 queries]``
are sixteen vregs that are produced, masked by a slice of the tile's
ONE mask, exponentiated and consumed without a store to VMEM, and a
head's (max, sum, accumulator) are read once and written once a tile.
By the TPU compiler's bundle listing (PERF.md, PR 59; a bundle holds
one store, three loads, four VALU ops, a ``vmatmul`` every fourth): a
tile of the forward was 2088 bundles of mask (sixteen index products
whose K = 64 costs a ``vmatmul`` all the same: at the MXU's rate alone)
and 4 trips of 2589 over the kv heads with a group's 8 heads and its
logits as ONE ``[512, 8 x 128]`` product in the body — 1503 stores a
trip, two runs of ~400 bundles with the store slot full and no product
issuing, 12 700 bundles for the 10 240 its 2560 ``vmatmul`` need — and
is 10 513 as one line. Pieces in the same loop over kv heads moved
nothing (a ~500-bundle head and tail a trip at half the MXU's rate took
what the store slot gave back), one line without pieces little: 21.31
ms a call as it was, 21.28 / 20.28 / **16.65** (v5e, row of 16 384).
The second pass keeps no running statistics and the compiler already
held its logits and its ``[512, 128]`` sum in registers (139 stores a
trip); it takes a head's whole tile an update and gains from the one
line alone, 11.41 -> 9.91 ms. The two calls are jitted (a line is
thousands of equations; traced once a signature). The backward
multiplies head by head in a loop over the kv heads with a group's
heads in its body (``_backward_tile``), untouched.

MXU operands in the input dtype with float32 accumulation; index
scores, thresholds, softmax statistics and accumulators in float32.
On CPU the kernels run in interpret mode.
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

NEG_INF = -1e30
INT_MIN = -(2**31)
_VMEM_LIMIT = 100 * 2**20
# What the one-kernel backward may hold for a whole row (dK, dV, dkI in
# float32: ``backward_schedule``) beside its tiles' working set; a
# row past it takes the two kernels that hold one tile's each.
_ROW_BUDGET = 76 * 2**20

# The kernels' names in a lowered program and a device trace
# (``%sparse_attn_fwd.<n>`` ...): the benchmark's readers find the
# attention kernels by ``sparse_attn`` and the selection by
# ``sparse_index``.
SELECT_KERNEL_NAME = "sparse_index_select"
FWD_KERNEL_NAME = "sparse_attn_fwd"
KL_KERNEL_NAME = "sparse_attn_kl"
BWD_KERNEL_NAME = "sparse_attn_bwd"
BWD_Q_KERNEL_NAME = "sparse_attn_bwd_q"
BWD_KV_KERNEL_NAME = "sparse_attn_bwd_kv"
# What the forward rule names of what it produces; a remat'd block
# keeps them (``models.transformer.block_remat``).
SAVED_NAMES = (
    "sparse_out", "sparse_lse", "sparse_thr", "sparse_cut",
    "sparse_index_lse", "sparse_index_loss",
)
PATH = "causal_tiles_masked"
# How the forward and the loss's second pass walk a tile's query heads
# (``_over_heads``), as ``sparse.schedule`` reports it.
HEAD_LOOP = "heads_abreast_in_pieces"
_LANES = 128
# Updates (a head's with one piece of keys) that one straight line of
# ``_over_heads`` holds at most: the published widths' 32 heads x 4
# pieces stand in ONE line. Each line pays its head and tail once (an
# update's chain product -> max -> exp -> product -> rescale is ~450
# bundles long and nothing runs under the first's or the last's), ~1.2
# ms a call at a row of 16 384, and the kernels' code grows with it.
_LINE_UPDATES = 128


def _use_interpret() -> bool:
    """As the flash kernels decide (whoever steers those for a compile
    without the chip steers these)."""
    import importlib

    return importlib.import_module(
        "adaptdl_tpu.ops.flash_attention"
    )._use_interpret()


def _tiles(seq_len: int, block_q: int, block_k: int) -> tuple[int, int]:
    tq, tk = min(block_q, seq_len), min(block_k, seq_len)
    assert seq_len % tq == 0 and seq_len % tk == 0, (
        f"sequence {seq_len} is not whole tiles of {tq} queries and "
        f"{tk} keys"
    )
    return tq, tk


def keys_visited(seq_len: int, block_q: int = 128, block_k: int = 512) -> int:
    """Keys the attention kernels multiply, summed over the queries of
    one row: every key of every key tile at or below the diagonal of a
    query tile."""
    tq, tk = _tiles(seq_len, block_q, block_k)
    return sum(
        tq * tk * ((q0 + tq - 1) // tk + 1)
        for q0 in range(0, seq_len, tq)
    )


# ---- what every kernel shares ---------------------------------------


def _to_key(x):
    """float32 -> int32 with the same order (an involution on bits)."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))


def _from_key(key):
    b = key ^ ((key >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(b, jnp.float32)


def _nt(a, b):
    """``a [m, d] . b [n, d] -> [m, n]`` in float32."""
    return lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _nn(a, b):
    return lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _index_scores(ki, qi_ref, wt_ref, relu_ref=None):
    """``I^T`` of one tile, ``[keys, queries]`` float32: ``ki`` [keys,
    di]; ``qi_ref`` block (1, heads, queries, di); ``wt_ref`` block (1,
    heads, queries). One code path for every kernel: the attention
    kernels must reproduce the selection kernel's scores to the bit.
    ``relu_ref`` (heads, keys, queries), where given, keeps each head's
    ``relu(qi_j . ki)`` for the backward (``> 0`` where the head is
    live), so that no kernel multiplies them a second time."""
    acc = None
    for j in range(qi_ref.shape[1]):
        relu = jnp.maximum(_nt(ki, qi_ref[0, j]), 0.0)
        if relu_ref is not None:
            relu_ref[j] = relu
        part = wt_ref[0, j:j + 1, :] * relu
        acc = part if acc is None else acc + part
    # -0.0 (every head at or below zero under a negative weight) and
    # +0.0 are one score.
    return jnp.where(acc == 0.0, 0.0, acc)


def _positions(k0, q0, tk: int, tq: int):
    s_pos = k0 + lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
    t_pos = q0 + lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
    return s_pos, t_pos


def _selected(scores, thr, cut, s_pos, t_pos):
    """The selection rule on one tile of scores ``[keys, queries]``."""
    key = _to_key(scores)
    return (s_pos <= t_pos) & (
        (key > thr) | ((key == thr) & (s_pos <= cut))
    )


def _last_key_tile(qb, tq: int, tk: int):
    """The key tile the diagonal of query tile ``qb`` lies in."""
    return (qb * tq + tq - 1) // tk


def _first_query_tile(kb, tq: int, tk: int):
    """The first query tile that reaches key tile ``kb``."""
    return (kb * tk) // tq


def _piece(tq: int, tk: int, head_dim: int) -> int:
    """Keys of one update of the forward (the loss's second pass keeps
    no running statistics and takes a head's whole tile: ``_kl_kernel``).
    Where ``head_dim`` and both tiles are whole ``_LANES``: ``_LANES``,
    so an update's float32 logits ``[128 keys, 128 queries]`` are
    sixteen vregs that are produced, masked, exponentiated and consumed
    without a store to VMEM. Any other width: the key tile, one update
    a head and tile (the flash forward's small updates ran at HALF
    speed at widths 64 and 192: PERF.md, PR 57)."""
    whole = all(n % _LANES == 0 for n in (tq, tk, head_dim))
    return _LANES if whole else tk


def _abreast(heads: int, kv_heads: int, pieces: int) -> int:
    """Query heads that stand in one straight line of ``_over_heads``:
    the heads of as many kv heads as divide ``kv_heads`` and keep the
    line within ``_LINE_UPDATES`` updates, of one kv head at least."""
    group = heads // kv_heads
    fit = max(1, _LINE_UPDATES // (group * pieces))
    return group * max(
        n for n in range(1, fit + 1) if kv_heads % n == 0
    )


def _over_heads(heads: int, kv_heads: int, pieces: int, head, carry):
    """``carry = head(h, g, carry)`` for the query heads ``h = 0 ..
    heads - 1`` in that order, ``g = h // group`` the head's kv head;
    ``pieces`` updates a head makes. How the forward and the loss's
    second pass walk a tile's heads (``sparse.schedule``: ``head_loop``,
    ``piece``, ``pieces_in_flight``).

    ``_abreast`` heads are straight-line code: as many copies of
    ``head``'s body, in the forward itself a head's updates with the
    tile's pieces of keys (``_piece``), none of them depending on
    another head's. Only inside one straight line does the scheduler
    run one update's matrix products under another's max / exp / sum,
    and each line pays for its head and tail, where nothing does: at
    the published widths (32 heads on 4 kv heads, 4 pieces) all 128
    updates are ONE line with static indices, 10 513 bundles a tile for
    the 10 240 its 2560 ``vmatmul`` need, where a loop over the kv
    heads with a group's 8 heads in its body took 12 700 (PERF.md, PR
    59: the bundle listing). Heads past one line are a loop over lines,
    and then a head's statistics sit at a dynamic index, behind whose
    stores the scheduler moves no product (PERF.md, PR 37)."""
    group = heads // kv_heads
    abreast = _abreast(heads, kv_heads, pieces)

    def line(first, carry):
        for r in range(abreast):
            h = first + r
            carry = head(h, h // group, carry)
        return carry

    if abreast == heads:
        return line(0, carry)
    return lax.fori_loop(
        0, heads // abreast, lambda i, carry: line(i * abreast, carry), carry
    )


# ---- index scores + selection ---------------------------------------


def _select_kernel(
    qi_ref, ki_ref, wt_ref,
    thr_ref, cut_ref, lse_ref, cnt_ref, tie_ref,
    keys_ref, *, topk: int, tk: int,
):
    seq_len, tq = keys_ref.shape
    q0 = pl.program_id(1) * tq
    chunks = (q0 + tq - 1) // tk + 1
    t_row = q0 + lax.broadcasted_iota(jnp.int32, (1, tq), 1)
    k_eff = jnp.minimum(topk, t_row + 1)

    def score(c, top):
        k0 = pl.multiple_of(c * tk, tk)
        scores = _index_scores(ki_ref[0, pl.ds(k0, tk), :], qi_ref, wt_ref)
        s_pos, t_pos = _positions(k0, q0, tk, tq)
        key = jnp.where(s_pos <= t_pos, _to_key(scores), INT_MIN)
        keys_ref[pl.ds(k0, tk), :] = key
        return jnp.maximum(top, jnp.max(key, axis=0, keepdims=True))

    top = lax.fori_loop(
        0, chunks, score, jnp.full((1, tq), INT_MIN, jnp.int32)
    )

    def total(pred, dtype=jnp.int32):
        """Sum over all keys at or below the diagonal of ``pred(keys
        chunk, first key of the chunk)``, a query: [1, tq]."""

        def body(c, acc):
            k0 = pl.multiple_of(c * tk, tk)
            return acc + jnp.sum(
                pred(keys_ref[pl.ds(k0, tk), :], k0).astype(dtype),
                axis=0, keepdims=True,
            )

        return lax.fori_loop(0, chunks, body, jnp.zeros((1, tq), dtype))

    # The k-th largest key, bit by bit from the top, in the unsigned
    # order ``key ^ INT_MIN``: the largest value that at least k keys
    # reach. Keys above the diagonal are INT_MIN (unsigned 0) and are
    # never counted.
    def bisect(i, prefix):
        candidate = prefix | (jnp.int32(1) << (31 - i))
        reach = total(lambda keys, _: keys >= (candidate ^ INT_MIN))
        return jnp.where(reach >= k_eff, candidate, prefix)

    thr = lax.fori_loop(
        0, 32, bisect, jnp.zeros((1, tq), jnp.int32)
    ) ^ INT_MIN
    above = total(lambda keys, _: keys > thr)
    at = total(lambda keys, _: keys == thr)
    need = k_eff - above
    tied = at > need

    def tie_search():
        """The smallest position up to which the keys AT the threshold
        fill what is still needed."""

        def step(_, bounds):
            lo, hi = bounds
            mid = (lo + hi) // 2

            def pred(keys, k0):
                s_pos = k0 + lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
                return (keys == thr) & (s_pos <= mid)

            enough = total(pred) >= need
            return (
                jnp.where(enough, lo, mid + 1),
                jnp.where(enough, mid, hi),
            )

        steps = max(1, (seq_len - 1).bit_length())
        lo, _ = lax.fori_loop(
            0, steps, step,
            (jnp.zeros((1, tq), jnp.int32),
             jnp.full((1, tq), seq_len - 1, jnp.int32)),
        )
        return lo

    cut = lax.cond(
        jnp.max(tied.astype(jnp.int32)) > 0,
        tie_search,
        lambda: jnp.full((1, tq), seq_len, jnp.int32),
    )
    cut = jnp.where(tied, cut, seq_len)
    top_score = _from_key(top)

    def chosen(keys, k0):
        s_pos = k0 + lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
        return (keys > thr) | ((keys == thr) & (s_pos <= cut))

    weight = total(
        lambda keys, k0: jnp.where(
            chosen(keys, k0), jnp.exp(_from_key(keys) - top_score), 0.0
        ),
        jnp.float32,
    )
    thr_ref[0] = thr
    cut_ref[0] = cut
    lse_ref[0] = top_score + jnp.log(weight)
    cnt_ref[0] = total(chosen)
    tie_ref[0] = tied.astype(jnp.int32)


def _vma(*xs):
    out = frozenset()
    for x in xs:
        out = out | jax.typeof(x).vma
    return out


def _params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT
    )


def index_select(qi, ki, wt, topk: int, block_q: int = 128, block_k: int = 512):
    """qi ``[b, heads, seq, di]``, ki ``[b, seq, di]``, wt ``[b,
    heads, seq]`` float32 -> the selection as ``(thr, cut)`` int32
    ``[b, 1, seq]`` (``_selected``), the log-sum-exp of the selected
    scores float32 ``[b, 1, seq]``, and per query the keys selected
    and whether the tie rule decided, int32 ``[b, 1, seq]``."""
    batch, heads, seq_len, di = qi.shape
    tq, tk = _tiles(seq_len, block_q, block_k)
    row = pl.BlockSpec((1, 1, tq), lambda b, i: (b, 0, i))
    vma = _vma(qi, ki, wt)
    shape = lambda dtype: jax.ShapeDtypeStruct(  # noqa: E731
        (batch, 1, seq_len), dtype, vma=vma
    )
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, tk=tk),
        grid=(batch, seq_len // tq),
        in_specs=[
            pl.BlockSpec((1, heads, tq, di), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, seq_len, di), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, heads, tq), lambda b, i: (b, 0, i)),
        ],
        out_specs=[row] * 5,
        out_shape=[
            shape(jnp.int32), shape(jnp.int32), shape(jnp.float32),
            shape(jnp.int32), shape(jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((seq_len, tq), jnp.int32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=_use_interpret(),
        name=SELECT_KERNEL_NAME,
    )(qi, ki, wt)


# ---- attention forward ----------------------------------------------


def _tile_mask(
    ki_ref, qi_ref, wt_ref, thr_ref, cut_ref, k0, q0, relu_ref=None
):
    """(selected [keys, queries], the tile's index scores)."""
    tk, tq = ki_ref.shape[1], qi_ref.shape[2]
    scores = _index_scores(ki_ref[0], qi_ref, wt_ref, relu_ref)
    s_pos, t_pos = _positions(k0, q0, tk, tq)
    return _selected(scores, thr_ref[0], cut_ref[0], s_pos, t_pos), scores


def _fwd_kernel(
    q_ref, k_ref, vt_ref, qi_ref, ki_ref, wt_ref, thr_ref, cut_ref,
    ot_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale: float,
):
    heads, tq, head_dim = q_ref.shape[1:]
    kv_heads, tk = k_ref.shape[1:3]
    piece = _piece(tq, tk, head_dim)
    pieces = [slice(at, at + piece) for at in range(0, tk, piece)]
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kb <= _last_key_tile(qb, tq, tk))
    def _tile():
        sel, _ = _tile_mask(
            ki_ref, qi_ref, wt_ref, thr_ref, cut_ref, kb * tk, qb * tq
        )

        def update(stats, s, vt):
            """One online-softmax update of a head's (max, sum,
            accumulator) with a piece's masked logits ``s``."""
            m_prev, l_prev, acc = stats
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            # One masked copy of the logits serves the maximum and the
            # probabilities: exp(NEG_INF - m) is 0.0, unless the query
            # has selected nothing yet, in this piece or before it, and
            # m is NEG_INF itself (every masked pair would count 1
            # until the first selected key's alpha = 0 wiped it).
            p = jnp.exp(s - jnp.where(m_new == NEG_INF, 0.0, m_new))
            alpha = jnp.exp(m_prev - m_new)
            return (
                m_new,
                alpha * l_prev + jnp.sum(p, axis=0, keepdims=True),
                alpha * acc + _nn(vt, p.astype(vt.dtype)),
            )

        def head(h, g, carry):
            # Read once and written once a tile; between, the head's
            # pieces are as many updates in registers.
            stats = m_ref[h], l_ref[h], acc_ref[h]
            for keys in pieces:
                s = _nt(k_ref[0, g, keys, :], q_ref[0, h]) * scale
                stats = update(
                    stats, jnp.where(sel[keys], s, NEG_INF),
                    vt_ref[0, g, :, keys],
                )
            m_ref[h], l_ref[h], acc_ref[h] = stats
            return carry

        _over_heads(heads, kv_heads, len(pieces), head, 0)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _done():
        ot_ref[0] = (acc_ref[...] / l_ref[...]).astype(ot_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])


def _query_specs(heads, hi, tq, d, di, index):
    """BlockSpecs of what belongs to a query tile; ``index(*grid) ->
    (batch, query tile)``."""

    def spec(block, place):
        return pl.BlockSpec(
            block, lambda *grid: place(*index(*grid))
        )

    return {
        "q": spec((1, heads, tq, d), lambda b, i: (b, 0, i, 0)),
        "ot": spec((1, heads, d, tq), lambda b, i: (b, 0, 0, i)),
        "stat": spec((1, heads, 1, tq), lambda b, i: (b, 0, 0, i)),
        "qi": spec((1, hi, tq, di), lambda b, i: (b, 0, i, 0)),
        "qit": spec((1, hi, di, tq), lambda b, i: (b, 0, 0, i)),
        "wt": spec((1, hi, tq), lambda b, i: (b, 0, i)),
        "row": spec((1, 1, tq), lambda b, i: (b, 0, i)),
    }


def _key_specs(kv_heads, tk, d, di, index):
    """BlockSpecs of what belongs to a key tile; ``index(*grid) ->
    (batch, key tile)``."""

    def spec(block, place):
        return pl.BlockSpec(
            block, lambda *grid: place(*index(*grid))
        )

    return {
        "k": spec((1, kv_heads, tk, d), lambda b, j: (b, 0, j, 0)),
        "kt": spec((1, kv_heads, d, tk), lambda b, j: (b, 0, 0, j)),
        "ki": spec((1, tk, di), lambda b, j: (b, j, 0)),
        "kit": spec((1, di, tk), lambda b, j: (b, 0, j)),
    }


def _by_query(tq, tk):
    """Grid (batch, query tile, key tile), keys innermost and clamped
    to the diagonal: a tile above it is neither fetched nor computed."""
    query = lambda b, i, j: (b, i)  # noqa: E731
    key = lambda b, i, j: (  # noqa: E731
        b, jnp.minimum(j, _last_key_tile(i, tq, tk))
    )
    return query, key


def _by_key(tq, tk):
    """Grid (batch, key tile, query tile), queries innermost, starting
    at the first tile that reaches the keys."""
    query = lambda b, j, i: (  # noqa: E731
        b, jnp.maximum(i, _first_query_tile(j, tq, tk))
    )
    key = lambda b, j, i: (b, j)  # noqa: E731
    return query, key


def _attention_forward(
    q, k, vt, qi, ki, wt, thr, cut, scale, tq, tk, out_dtype=None
):
    return _forward_call(
        q, k, vt, qi, ki, wt, thr, cut, scale, tq, tk, out_dtype,
        _use_interpret(),
    )


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def _forward_call(
    q, k, vt, qi, ki, wt, thr, cut, scale, tq, tk, out_dtype, interpret
):
    """The forward kernel's call, jitted: a model makes it once a
    layer in every program, a line of ``_LINE_UPDATES`` updates is
    thousands of equations, and every call after the first of a
    signature finds the kernel traced. The scope is the custom call's
    name in a compiled program and a device trace."""
    batch, heads, seq_len, d = q.shape
    kv_heads = k.shape[1]
    hi, di = qi.shape[1], qi.shape[3]
    qs = _query_specs(heads, hi, tq, d, di, _by_query(tq, tk)[0])
    ks = _key_specs(kv_heads, tk, d, di, _by_query(tq, tk)[1])
    vma = _vma(q, k, vt, qi, ki, wt)
    with jax.named_scope(FWD_KERNEL_NAME):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale),
            grid=(batch, seq_len // tq, seq_len // tk),
            in_specs=[
                qs["q"], ks["k"], ks["kt"], qs["qi"], ks["ki"], qs["wt"],
                qs["row"], qs["row"],
            ],
            out_specs=[qs["ot"], qs["stat"]],
            out_shape=[
                jax.ShapeDtypeStruct(
                    (batch, heads, d, seq_len), out_dtype or q.dtype,
                    vma=vma,
                ),
                jax.ShapeDtypeStruct(
                    (batch, heads, 1, seq_len), jnp.float32, vma=vma
                ),
            ],
            scratch_shapes=[
                pltpu.VMEM((heads, 1, tq), jnp.float32),
                pltpu.VMEM((heads, 1, tq), jnp.float32),
                pltpu.VMEM((heads, d, tq), jnp.float32),
            ],
            compiler_params=_params("parallel", "parallel", "arbitrary"),
            interpret=interpret,
            name=FWD_KERNEL_NAME,
        )(q, k, vt, qi, ki, wt, thr, cut)


# ---- the indexer's loss, forward ------------------------------------


def _kl_kernel(
    q_ref, k_ref, qi_ref, ki_ref, wt_ref, thr_ref, cut_ref, lse_ref,
    ilse_ref, li_ref, acc_ref, *, scale: float,
):
    heads, tq = q_ref.shape[1:3]
    kv_heads, tk = k_ref.shape[1:3]
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kb <= _last_key_tile(qb, tq, tk))
    def _tile():
        sel, scores = _tile_mask(
            ki_ref, qi_ref, wt_ref, thr_ref, cut_ref, kb * tk, qb * tq
        )

        def head(h, g, total):
            # No running statistics, so no pieces: an update is a head's
            # with the whole key tile, whose logits the compiler hands
            # from the product's results through exp into the sum,
            # vreg by vreg, and which it keeps in registers (the bundle
            # listing: 602 stores in a tile's 6280 bundles).
            s = _nt(k_ref[0, g], q_ref[0, h]) * scale
            return total + jnp.where(sel, jnp.exp(s - lse_ref[0, h]), 0.0)

        p = _over_heads(
            heads, kv_heads, 1, head, jnp.zeros(sel.shape, jnp.float32)
        ) / heads  # p^T [keys, queries]: the mean over the query heads
        term = p * (
            jnp.log(jnp.where(p > 0, p, 1.0)) - scores + ilse_ref[0]
        )
        acc_ref[...] += jnp.sum(
            jnp.where(sel, term, 0.0), axis=0, keepdims=True
        )

    @pl.when(kb == pl.num_programs(2) - 1)
    def _done():
        li_ref[0] = acc_ref[...]


def _index_loss(q, k, qi, ki, wt, thr, cut, lse, ilse, scale, tq, tk):
    return _index_loss_call(
        q, k, qi, ki, wt, thr, cut, lse, ilse, scale, tq, tk,
        _use_interpret(),
    )


@functools.partial(jax.jit, static_argnums=(9, 10, 11, 12))
def _index_loss_call(
    q, k, qi, ki, wt, thr, cut, lse, ilse, scale, tq, tk, interpret
):
    """The second pass's call, jitted as ``_forward_call`` is."""
    batch, heads, seq_len, d = q.shape
    hi, di = qi.shape[1], qi.shape[3]
    qs = _query_specs(heads, hi, tq, d, di, _by_query(tq, tk)[0])
    ks = _key_specs(k.shape[1], tk, d, di, _by_query(tq, tk)[1])
    with jax.named_scope(KL_KERNEL_NAME):
        return pl.pallas_call(
            functools.partial(_kl_kernel, scale=scale),
            grid=(batch, seq_len // tq, seq_len // tk),
            in_specs=[
                qs["q"], ks["k"], qs["qi"], ks["ki"], qs["wt"], qs["row"],
                qs["row"], qs["stat"], qs["row"],
            ],
            out_specs=qs["row"],
            out_shape=jax.ShapeDtypeStruct(
                (batch, 1, seq_len), jnp.float32,
                vma=_vma(q, k, qi, ki, wt),
            ),
            scratch_shapes=[pltpu.VMEM((1, tq), jnp.float32)],
            compiler_params=_params("parallel", "parallel", "arbitrary"),
            interpret=interpret,
            name=KL_KERNEL_NAME,
        )(q, k, qi, ki, wt, thr, cut, lse, ilse)


# ---- backward -------------------------------------------------------


def backward_schedule(
    kv_heads: int, seq_len: int, head_dim: int, index_dim: int
) -> tuple[str, int]:
    """(``"one_kernel"`` / ``"two_kernels"``, bytes): what the
    one-kernel backward holds in VMEM for a whole row — dK and dV
    ``[kv_heads, seq, head_dim]`` and dkI ``[seq, index_dim]`` as
    float32, minor dimensions padded to the 128 lanes — and one kernel
    wherever that fits the budget."""
    lanes = lambda n: -(-n // 128) * 128  # noqa: E731
    held = 4 * seq_len * (2 * kv_heads * lanes(head_dim) + lanes(index_dim))
    return ("one_kernel" if held <= _ROW_BUDGET else "two_kernels"), held


def _zero(*refs):
    for ref in refs:
        ref[...] = jnp.zeros_like(ref)


def _backward_tile(
    q_ref, k_ref, v_ref, dot_ref, qi_ref, ki_ref, wt_ref, thr_ref, cut_ref,
    lse_ref, ilse_ref, delta_ref, dli_ref, relu_ref, k0, q0, scale: float,
    on_head, on_index_head,
):
    """What every backward kernel does on one tile, once: the mask and
    the indexer heads' relu'd products (kept in ``relu_ref``); per kv
    head ``g`` (a loop) its query heads ``h`` in turn (unrolled: the
    scheduler overlaps one head's products with the next one's
    softmax; a head's logits are its own product here, because ONE
    product a group, as the forward took it until PR 59, reads 47.5 ms
    a call for 45.2 where five products a head already fill the MXU:
    PERF.md, PR 37), ``on_head(h, g, p, ds)`` with ``p`` and ``ds``
    ``[keys, queries]`` float32; then ``dL_I / dI`` and per indexer head
    ``on_index_head(j, dI * relu_j summed over keys [1, queries], dI *
    w_j where the head is live [keys, queries])``."""
    heads, kv_heads = q_ref.shape[1], k_ref.shape[1]
    group = heads // kv_heads
    sel, scores = _tile_mask(
        ki_ref, qi_ref, wt_ref, thr_ref, cut_ref, k0, q0, relu_ref
    )

    def heads_of(g, total):
        for r in range(group):
            h = g * group + r
            s = _nt(k_ref[0, g], q_ref[0, h]) * scale
            p = jnp.where(sel, jnp.exp(s - lse_ref[0, h]), 0.0)
            dp = _nn(v_ref[0, g], dot_ref[0, h])
            on_head(h, g, p, p * (dp - delta_ref[0, h]))
            total = total + p
        return total

    p_mean = lax.fori_loop(
        0, kv_heads, heads_of, jnp.zeros(sel.shape, jnp.float32)
    ) / heads
    # dL_I / dI = dli * (softmax_S(I) - p) on the selected pairs.
    d_scores = jnp.where(
        sel,
        dli_ref[0] * (jnp.exp(scores - ilse_ref[0]) - p_mean),
        0.0,
    )
    for j in range(qi_ref.shape[1]):
        relu = relu_ref[j]
        on_index_head(
            j,
            jnp.sum(d_scores * relu, axis=0, keepdims=True),
            jnp.where(relu > 0.0, d_scores * wt_ref[0, j:j + 1, :], 0.0),
        )


def _bwd_kernel(
    q_ref, k_ref, v_ref, kt_ref, do_ref, dot_ref, qi_ref, ki_ref, kit_ref,
    wt_ref, thr_ref, cut_ref, lse_ref, ilse_ref, delta_ref, dli_ref,
    dqt_ref, dqit_ref, dwt_ref, dk_ref, dv_ref, dki_ref,
    dq_acc, dqi_acc, dw_acc, dk_acc, dv_acc, dki_acc, relu_ref,
    *, scale: float,
):
    """Every gradient from one visit of a tile. Grid (batch, query
    tile, key tile): dq / dqI / dw accumulate over a query tile's keys
    as in ``_bwd_q_kernel``; dk / dv / dkI accumulate for the WHOLE ROW
    in VMEM and leave, tile by tile, while the row's last query tile
    (which reaches every key tile) passes."""
    tq, tk = q_ref.shape[2], k_ref.shape[2]
    qb, kb = pl.program_id(1), pl.program_id(2)
    keys = pl.ds(pl.multiple_of(kb * tk, tk), tk)

    @pl.when((qb == 0) & (kb == 0))
    def _init_row():
        _zero(dk_acc, dv_acc, dki_acc)

    @pl.when(kb == 0)
    def _init():
        _zero(dq_acc, dqi_acc, dw_acc)

    @pl.when(kb <= _last_key_tile(qb, tq, tk))
    def _tile():
        def on_head(h, g, p, ds):
            ds = ds.astype(q_ref.dtype)
            dv_acc[g, keys, :] += _nn(p.astype(do_ref.dtype), do_ref[0, h])
            dk_acc[g, keys, :] += _nn(ds, q_ref[0, h]) * scale
            dq_acc[h] += _nn(kt_ref[0, g], ds) * scale

        def on_index_head(j, dw, through):
            through = through.astype(qi_ref.dtype)
            dw_acc[j:j + 1, :] += dw
            dqi_acc[j] += _nn(kit_ref[0], through)
            dki_acc[keys, :] += _nn(through, qi_ref[0, j])

        _backward_tile(
            q_ref, k_ref, v_ref, dot_ref, qi_ref, ki_ref, wt_ref, thr_ref,
            cut_ref, lse_ref, ilse_ref, delta_ref, dli_ref, relu_ref,
            kb * tk, qb * tq, scale, on_head, on_index_head,
        )

    @pl.when(kb == pl.num_programs(2) - 1)
    def _done():
        dqt_ref[0] = dq_acc[...].astype(dqt_ref.dtype)
        dqit_ref[0] = dqi_acc[...]
        dwt_ref[0] = dw_acc[...]

    @pl.when(qb == pl.num_programs(1) - 1)
    def _done_keys():
        dk_ref[0] = dk_acc[:, keys, :].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:, keys, :].astype(dv_ref.dtype)
        dki_ref[0] = dki_acc[keys, :]


def _bwd_q_kernel(
    q_ref, k_ref, v_ref, kt_ref, dot_ref, qi_ref, ki_ref, kit_ref, wt_ref,
    thr_ref, cut_ref, lse_ref, ilse_ref, delta_ref, dli_ref,
    dqt_ref, dqit_ref, dwt_ref,
    dq_acc, dqi_acc, dw_acc, relu_ref, *, scale: float,
):
    tq, tk = q_ref.shape[2], k_ref.shape[2]
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        _zero(dq_acc, dqi_acc, dw_acc)

    @pl.when(kb <= _last_key_tile(qb, tq, tk))
    def _tile():
        def on_head(h, g, p, ds):
            dq_acc[h] += _nn(kt_ref[0, g], ds.astype(kt_ref.dtype)) * scale

        def on_index_head(j, dw, through):
            dw_acc[j:j + 1, :] += dw
            dqi_acc[j] += _nn(kit_ref[0], through.astype(kit_ref.dtype))

        _backward_tile(
            q_ref, k_ref, v_ref, dot_ref, qi_ref, ki_ref, wt_ref, thr_ref,
            cut_ref, lse_ref, ilse_ref, delta_ref, dli_ref, relu_ref,
            kb * tk, qb * tq, scale, on_head, on_index_head,
        )

    @pl.when(kb == pl.num_programs(2) - 1)
    def _done():
        dqt_ref[0] = dq_acc[...].astype(dqt_ref.dtype)
        dqit_ref[0] = dqi_acc[...]
        dwt_ref[0] = dw_acc[...]


def _bwd_kv_kernel(
    q_ref, k_ref, v_ref, do_ref, dot_ref, qi_ref, ki_ref, wt_ref,
    thr_ref, cut_ref, lse_ref, ilse_ref, delta_ref, dli_ref,
    dk_ref, dv_ref, dki_ref,
    dk_acc, dv_acc, dki_acc, relu_ref, *, scale: float,
):
    tq, tk = q_ref.shape[2], k_ref.shape[2]
    kb, qb = pl.program_id(1), pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        _zero(dk_acc, dv_acc, dki_acc)

    @pl.when(qb >= _first_query_tile(kb, tq, tk))
    def _tile():
        def on_head(h, g, p, ds):
            dv_acc[g] += _nn(p.astype(do_ref.dtype), do_ref[0, h])
            dk_acc[g] += _nn(ds.astype(q_ref.dtype), q_ref[0, h]) * scale

        def on_index_head(j, dw, through):
            del dw
            dki_acc[...] += _nn(through.astype(qi_ref.dtype), qi_ref[0, j])

        _backward_tile(
            q_ref, k_ref, v_ref, dot_ref, qi_ref, ki_ref, wt_ref, thr_ref,
            cut_ref, lse_ref, ilse_ref, delta_ref, dli_ref, relu_ref,
            kb * tk, qb * tq, scale, on_head, on_index_head,
        )

    @pl.when(qb == pl.num_programs(2) - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        dki_ref[0] = dki_acc[...]


def _attention_backward(
    q, k, v, qi, ki, wt, thr, cut, lse, ilse, delta, dot, dli,
    scale, tq, tk,
):
    """-> (dq^T [b, h, d, seq], dk, dv [b, kv, seq, d], dqi^T [b, hi,
    di, seq] float32, dki [b, seq, di] float32, dw^T [b, hi, seq])."""
    batch, heads, seq_len, d = q.shape
    kv_heads = k.shape[1]
    hi, di = qi.shape[1], qi.shape[3]
    vma = _vma(q, k, v, qi, ki, wt, dot, dli)
    kt = jnp.swapaxes(k, 2, 3)
    kit = jnp.swapaxes(ki, 1, 2)
    do = jnp.swapaxes(dot, 2, 3)
    grid_q = (batch, seq_len // tq, seq_len // tk)
    qs = _query_specs(heads, hi, tq, d, di, _by_query(tq, tk)[0])
    ks = _key_specs(kv_heads, tk, d, di, _by_query(tq, tk)[1])
    query_out = [
        jax.ShapeDtypeStruct((batch, heads, d, seq_len), q.dtype, vma=vma),
        jax.ShapeDtypeStruct((batch, hi, di, seq_len), jnp.float32, vma=vma),
        jax.ShapeDtypeStruct((batch, hi, seq_len), jnp.float32, vma=vma),
    ]
    key_out = [
        jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
        jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
        jax.ShapeDtypeStruct(ki.shape, jnp.float32, vma=vma),
    ]
    query_scratch = [
        pltpu.VMEM((heads, d, tq), jnp.float32),
        pltpu.VMEM((hi, di, tq), jnp.float32),
        pltpu.VMEM((hi, tq), jnp.float32),
    ]
    relu = pltpu.VMEM((hi, tk, tq), jnp.float32)
    if backward_schedule(kv_heads, seq_len, d, di)[0] == "one_kernel":
        # The key-side outputs leave under the row's LAST query tile,
        # the one that reaches every key tile; until then their block
        # stays put and nothing is written back.
        last = seq_len // tq - 1
        leaving = _key_specs(
            kv_heads, tk, d, di,
            lambda b, i, j: (b, jnp.where(i == last, j, 0)),
        )
        dqt, dqit, dwt, dk, dv, dki = pl.pallas_call(
            functools.partial(_bwd_kernel, scale=scale),
            grid=grid_q,
            in_specs=[
                qs["q"], ks["k"], ks["k"], ks["kt"], qs["q"], qs["ot"],
                qs["qi"], ks["ki"], ks["kit"], qs["wt"], qs["row"],
                qs["row"], qs["stat"], qs["row"], qs["stat"], qs["row"],
            ],
            out_specs=[
                qs["ot"], qs["qit"], qs["wt"], leaving["k"], leaving["k"],
                leaving["ki"],
            ],
            out_shape=query_out + key_out,
            scratch_shapes=query_scratch + [
                pltpu.VMEM((kv_heads, seq_len, d), jnp.float32),
                pltpu.VMEM((kv_heads, seq_len, d), jnp.float32),
                pltpu.VMEM((seq_len, di), jnp.float32),
                relu,
            ],
            compiler_params=_params("parallel", "arbitrary", "arbitrary"),
            interpret=_use_interpret(),
            name=BWD_KERNEL_NAME,
        )(
            q, k, v, kt, do, dot, qi, ki, kit, wt, thr, cut, lse, ilse,
            delta, dli,
        )
        return dqt, dk, dv, dqit, dki, dwt
    dqt, dqit, dwt = pl.pallas_call(
        functools.partial(_bwd_q_kernel, scale=scale),
        grid=grid_q,
        in_specs=[
            qs["q"], ks["k"], ks["k"], ks["kt"], qs["ot"], qs["qi"],
            ks["ki"], ks["kit"], qs["wt"], qs["row"], qs["row"],
            qs["stat"], qs["row"], qs["stat"], qs["row"],
        ],
        out_specs=[qs["ot"], qs["qit"], qs["wt"]],
        out_shape=query_out,
        scratch_shapes=query_scratch + [relu],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=_use_interpret(),
        name=BWD_Q_KERNEL_NAME,
    )(q, k, v, kt, dot, qi, ki, kit, wt, thr, cut, lse, ilse, delta, dli)
    qs = _query_specs(heads, hi, tq, d, di, _by_key(tq, tk)[0])
    ks = _key_specs(kv_heads, tk, d, di, _by_key(tq, tk)[1])
    dk, dv, dki = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, scale=scale),
        grid=(batch, seq_len // tk, seq_len // tq),
        in_specs=[
            qs["q"], ks["k"], ks["k"], qs["q"], qs["ot"], qs["qi"],
            ks["ki"], qs["wt"], qs["row"], qs["row"], qs["stat"],
            qs["row"], qs["stat"], qs["row"],
        ],
        out_specs=[ks["k"], ks["k"], ks["ki"]],
        out_shape=key_out,
        scratch_shapes=[
            pltpu.VMEM((kv_heads, tk, d), jnp.float32),
            pltpu.VMEM((kv_heads, tk, d), jnp.float32),
            pltpu.VMEM((tk, di), jnp.float32),
            relu,
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=_use_interpret(),
        name=BWD_KV_KERNEL_NAME,
    )(q, k, v, do, dot, qi, ki, wt, thr, cut, lse, ilse, delta, dli)
    return dqt, dk, dv, dqit, dki, dwt


# ---- the membership itself, for checks --------------------------------


def _pairs_kernel(
    qi_ref, ki_ref, wt_ref, thr_ref, cut_ref, pairs_ref, scores_ref
):
    tk, tq = ki_ref.shape[1], qi_ref.shape[2]
    sel, scores = _tile_mask(
        ki_ref, qi_ref, wt_ref, thr_ref, cut_ref,
        pl.program_id(2) * tk, pl.program_id(1) * tq,
    )
    pairs_ref[0] = sel.astype(pairs_ref.dtype).T
    scores_ref[0] = scores.T


def selected_pairs(
    qi, ki, w, topk: int, block_q: int = 128, block_k: int = 512
):
    """The membership the attention kernels apply and the index scores
    it is taken from, written out ``[batch, seq (query), seq (key)]``
    (int32, 1 where the key is selected; float32) by the same tile
    code on the same tiles (``_tile_mask``); keys after the query read
    0 and whatever their scores are. For tests and the benchmark's
    comparisons: the training path never builds either. Returns
    ``(pairs, scores, count [batch, seq], tied [batch, seq])``."""
    batch, hi, seq_len, di = qi.shape
    tq, tk = _tiles(seq_len, block_q, block_k)
    wt = jnp.swapaxes(w.astype(jnp.float32), 1, 2)
    thr, cut, _, count, tied = index_select(qi, ki, wt, topk, tq, tk)
    qs = _query_specs(1, hi, tq, 1, di, lambda b, i, j: (b, i))
    ks = _key_specs(1, tk, 1, di, lambda b, i, j: (b, j))
    tile = pl.BlockSpec((1, tq, tk), lambda b, i, j: (b, i, j))
    vma = _vma(qi, ki, w)
    pairs, scores = pl.pallas_call(
        _pairs_kernel,
        grid=(batch, seq_len // tq, seq_len // tk),
        in_specs=[qs["qi"], ks["ki"], qs["wt"], qs["row"], qs["row"]],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct(
                (batch, seq_len, seq_len), jnp.int32, vma=vma
            ),
            jax.ShapeDtypeStruct(
                (batch, seq_len, seq_len), jnp.float32, vma=vma
            ),
        ],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=_use_interpret(),
        name="sparse_index_pairs",
    )(qi, ki, wt, thr, cut)
    return pairs, scores, count[:, 0], tied[:, 0]


# ---- the op ---------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _sparse_attention(
    q, k, v, qi, ki, w, topk, scale, block_q, block_k, out_dtype
):
    return _forward(
        q, k, v, qi, ki, w, topk, scale, block_q, block_k, out_dtype
    )[0]


def _forward(q, k, v, qi, ki, w, topk, scale, tq, tk, out_dtype):
    """-> ((out, index loss, count, tied), residuals). What the
    backward reads of what the kernels produced is NAMED before
    anything reads it, so that a ``jax.checkpoint`` policy around the
    call can keep it."""
    wt = jnp.swapaxes(w.astype(jnp.float32), 1, 2)
    thr, cut, ilse, count, tied = index_select(qi, ki, wt, topk, tq, tk)
    thr = checkpoint_name(thr, SAVED_NAMES[2])
    cut = checkpoint_name(cut, SAVED_NAMES[3])
    ilse = checkpoint_name(ilse, SAVED_NAMES[4])
    out_t, lse = _attention_forward(
        q, k, jnp.swapaxes(v, 2, 3), qi, ki, wt, thr, cut, scale, tq, tk,
        out_dtype,
    )
    out_t = checkpoint_name(out_t, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    index_loss = checkpoint_name(
        _index_loss(q, k, qi, ki, wt, thr, cut, lse, ilse, scale, tq, tk),
        SAVED_NAMES[5],
    )
    residuals = (q, k, v, qi, ki, wt, thr, cut, lse, ilse, out_t)
    outputs = (
        jnp.swapaxes(out_t, 2, 3), index_loss[:, 0], count[:, 0],
        tied[:, 0],
    )
    return outputs, residuals


def _vjp_bwd(topk, scale, tq, tk, out_dtype, residuals, cotangents):
    del topk, out_dtype
    q, k, v, qi, ki, wt, thr, cut, lse, ilse, out_t = residuals
    d_out, d_loss = cotangents[:2]
    dot = jnp.swapaxes(d_out, 2, 3).astype(q.dtype)
    delta = jnp.sum(
        dot.astype(jnp.float32) * out_t.astype(jnp.float32),
        axis=2, keepdims=True,
    )
    dqt, dk, dv, dqit, dki, dwt = _attention_backward(
        q, k, v, qi, ki, wt, thr, cut, lse, ilse, delta, dot,
        d_loss.astype(jnp.float32)[:, None, :], scale, tq, tk,
    )
    return (
        jnp.swapaxes(dqt, 2, 3),
        dk,
        dv,
        jnp.swapaxes(dqit, 2, 3).astype(qi.dtype),
        dki.astype(ki.dtype),
        jnp.swapaxes(dwt, 1, 2),
    )


_sparse_attention.defvjp(_forward, _vjp_bwd)


def sparse_attention(
    q, k, v, qi, ki, w, topk: int, *, scale: float | None = None,
    block_q: int = 128, block_k: int = 512, out_dtype=None,
):
    """Attention over the ``topk`` keys a query's index scores select.

    Args:
      q: ``[batch, heads, seq, head_dim]``; k, v: ``[batch, kv_heads,
        seq, head_dim]`` (query head ``i`` on kv head ``i // group``),
        in the compute dtype.
      qi: ``[batch, index_heads, seq, index_dim]``; ki: ``[batch, seq,
        index_dim]`` (one key head), compute dtype; w: ``[batch, seq,
        index_heads]`` float32, every scale folded in.
      topk: keys a query keeps (all earlier ones while it has fewer).
      block_q / block_k: tile sizes (``min(block, seq)`` is used; must
        divide seq).
      out_dtype: what ``out`` leaves the forward kernel as (default:
        q's dtype). float32 shows the kernel's float32 accumulator
        without its last rounding: a comparison then sees a lower
        precision INSIDE the kernel that the rounding would mask.

    Returns ``(out [batch, heads, seq, head_dim], index_loss float32
    [batch, seq], count, tied)``: per query the keys selected and
    whether keys at the threshold were split by position, int32
    ``[batch, seq]``, no gradient.
    """
    batch, heads, seq_len, head_dim = q.shape
    scale = head_dim**-0.5 if scale is None else float(scale)
    tq, tk = _tiles(seq_len, block_q, block_k)
    backward, held = backward_schedule(
        k.shape[1], seq_len, head_dim, qi.shape[3]
    )
    piece = _piece(tq, tk, head_dim)
    trace.event(
        "sparse.schedule",
        rows=batch,
        seq_len=seq_len,
        heads=heads,
        kv_heads=k.shape[1],
        head_dim=head_dim,
        index_heads=qi.shape[1],
        index_dim=qi.shape[3],
        topk=topk,
        block_q=tq,
        block_k=tk,
        keys_visited=keys_visited(seq_len, tq, tk),
        dtype=q.dtype.name,
        path=PATH,
        backward=backward,
        backward_vmem_bytes=held,
        head_loop=HEAD_LOOP,
        group=heads // k.shape[1],
        # An update of the forward: one head's with ``piece`` keys;
        # ``pieces_in_flight`` heads stand abreast in one straight
        # line, each with the tile's pieces.
        piece=piece,
        pieces_in_flight=_abreast(heads, k.shape[1], tk // piece),
    )
    return _sparse_attention(
        q, k, v, qi, ki, w, topk, scale, tq, tk, out_dtype
    )
