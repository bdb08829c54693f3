"""The indexer's selection and sparse attention
(``adaptdl_tpu/ops/sparse_attention.py``, the ``SparseAttention`` mixer;
PRs 34-37, which the keye-vl-2.0-30b-a3b configuration forced) at small
sizes in float32 against the configuration's own plain reference
(``benchmark/configs/keye-vl-2.0-30b-a3b.py``, which imports nothing
from ``adaptdl_tpu``): the selection's edges, a row shorter than
``topk``, the two backward schedules, the forward for every group, the
schedule's event and the kernels' names. (The whole model, the softmax
router and the share, the wider heads: ``tests/test_sparse_lm.py``.)"""

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adaptdl_tpu import trace
from adaptdl_tpu.models.transformer import (
    GroupedQueryAttention,
    SparseAttention,
)
from adaptdl_tpu.ops import sparse_attention as sparse

NAME = "keye-vl-2.0-30b-a3b"


# ---- the selection ----------------------------------------------------


def _index_inputs(seq, heads=3, dim=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (
        jax.random.normal(keys[0], (1, heads, seq, dim)),
        jax.random.normal(keys[1], (1, seq, dim)),
        jax.random.normal(keys[2], (1, seq, heads)) * 0.3,
    )


def _plain_sets(qi, ki, w, topk):
    config = configurations.module(NAME)
    scores = config.reference_scores(
        jnp.swapaxes(qi[0], 0, 1) * qi.shape[-1] ** 0.5, ki[0],
        w[0] * qi.shape[1] ** 0.5,
    )
    return scores, config.reference_select(scores, 0, topk)[0]


@pytest.mark.parametrize(
    "case", ["fewer_than_topk", "exactly_topk_after", "ties_to_lower_key",
             "several_tiles"],
)
def test_selection_edges(case):
    seq, topk, blocks = 64, 8, (16, 32)
    qi, ki, w = _index_inputs(seq)
    if case == "ties_to_lower_key":
        # Every score is 0: a query keeps its LOWEST topk keys.
        ki = jnp.zeros_like(ki)
    if case == "several_tiles":
        blocks = (16, 16)
    pairs, scores, count, tied = sparse.selected_pairs(
        qi, ki, w, topk, *blocks
    )
    member = np.asarray(pairs[0]).astype(bool)
    want_scores, want = _plain_sets(qi, ki, w, topk)
    np.testing.assert_array_equal(member, np.asarray(want))
    causal = np.tril(np.ones((seq, seq), bool))
    np.testing.assert_allclose(
        np.where(causal, scores[0], 0), np.where(causal, want_scores, 0),
        atol=1e-5,
    )
    np.testing.assert_array_equal(
        count[0], np.minimum(np.arange(seq) + 1, topk)
    )
    if case == "fewer_than_topk":
        np.testing.assert_array_equal(member[:topk], causal[:topk])
        assert not np.any(np.asarray(tied[0, :topk]))
    elif case == "exactly_topk_after":
        assert np.all(member[topk:].sum(-1) == topk)
        assert not np.any(member & ~causal)
    elif case == "ties_to_lower_key":
        lowest = np.arange(seq)[None, :] < topk
        np.testing.assert_array_equal(member[topk:], (lowest & causal)[topk:])
        assert np.all(np.asarray(tied[0, topk:]) == 1)


def test_a_row_shorter_than_topk_is_grouped_query_attention():
    """With at most ``topk`` keys a query every earlier key is kept:
    the sparse mixer equals ``GroupedQueryAttention`` on the same
    weights to rounding, and its gradient to the input too."""
    cfg = configurations.module(NAME).model_config(
        configurations.sizes(
            NAME,
            sa_config={**configurations.TINY[NAME]["sa_config"], "topk": 64},
        )
    )
    x = jax.random.normal(jax.random.key(1), (2, 32, 64))
    positions = jnp.arange(32)
    mixer = SparseAttention(cfg)
    params = mixer.init(jax.random.key(2), x, positions)["params"]
    dense = {k: v for k, v in params.items() if k != "indexer"}

    def sparse_out(x):
        return mixer.apply(
            {"params": params}, x, positions,
            mutable=["indexer_loss", "sparse_select"],
        )[0]

    def dense_out(x):
        return GroupedQueryAttention(cfg).apply(
            {"params": dense}, x, positions
        )

    np.testing.assert_allclose(sparse_out(x), dense_out(x), atol=2e-5)
    np.testing.assert_allclose(
        jax.grad(lambda x: jnp.sum(sparse_out(x) ** 2))(x),
        jax.grad(lambda x: jnp.sum(dense_out(x) ** 2))(x),
        atol=2e-4,
    )


def test_the_sparse_mixer_raises_under_a_sequence_axis():
    import dataclasses

    cfg = dataclasses.replace(
        configurations.module(NAME).model_config(configurations.sizes(NAME)),
        seq_axis="seq",
    )
    with pytest.raises(ValueError, match="sequence-parallel"):
        SparseAttention(cfg).init(
            jax.random.key(0), jnp.zeros((1, 32, 64)), jnp.arange(32)
        )


def _operands(
    case, seq=64, dim=32, index_heads=3, index_dim=16, kv_heads=2
):
    """float32 operands of ``sparse_attention`` on several tiles
    (blocks of 16 queries and 32 keys) and the ``topk`` to run them
    with."""
    rows = 2 if case == "two_rows" else 1
    heads = 8 if case == "grouped" else 4
    keys = jax.random.split(jax.random.key(11), 6)
    q = jax.random.normal(keys[0], (rows, heads, seq, dim))
    k = jax.random.normal(keys[1], (rows, kv_heads, seq, dim))
    v = jax.random.normal(keys[2], (rows, kv_heads, seq, dim))
    qi = jax.random.normal(keys[3], (rows, index_heads, seq, index_dim))
    ki = jax.random.normal(keys[4], (rows, seq, index_dim))
    w = jax.random.normal(keys[5], (rows, seq, index_heads)) * 0.3
    if case == "ties_at_the_threshold":
        # Eight distinct index keys, each at eight positions: scores
        # tie, and the position decides which of the tied keys stay.
        ki = jnp.tile(ki[:, :8], (1, seq // 8, 1))
    if case == "only_late_keys":
        # Index scores that grow with the key's position: a query
        # selects the keys just before it and none further back.
        qi, w = jnp.abs(qi), jnp.abs(w) + 0.1
        ki = jnp.ones_like(ki) * (
            (1.0 + jnp.arange(seq))[None, :, None] * 4 / seq
        )
    return (q, k, v, qi, ki, w), 128 if case == "shorter_than_topk" else 8


@pytest.mark.parametrize(
    "case",
    ["grouped", "ties_at_the_threshold", "shorter_than_topk", "two_rows"],
)
def test_the_two_backward_schedules_agree(case, monkeypatch):
    """On several query and key tiles the one-kernel backward (dK, dV,
    dkI accumulated for the whole row, zeroed and written once a ROW)
    and the two kernels give the same six gradients of both outputs,
    and both the plain reference's, row by row."""
    config = configurations.module(NAME)
    operands, topk = _operands(case)
    rows, heads, seq, dim = operands[0].shape
    assert heads > operands[1].shape[1]  # grouped: kv heads are shared
    weights = jax.random.split(jax.random.key(12), 2)
    d_out = jax.random.normal(weights[0], (rows, heads, seq, dim))
    d_loss = jax.random.normal(weights[1], (rows, seq))

    def objective(*operands):
        out, index_loss, _, tied = sparse.sparse_attention(
            *operands, topk, block_q=16, block_k=32
        )
        return jnp.sum(out * d_out) + jnp.sum(index_loss * d_loss), tied

    def grads(schedule):
        assert sparse.backward_schedule(
            operands[1].shape[1], seq, dim, operands[3].shape[3]
        )[0] == schedule
        return jax.grad(objective, argnums=tuple(range(6)), has_aux=True)(
            *operands
        )

    one, tied = grads("one_kernel")
    monkeypatch.setattr(sparse, "_ROW_BUDGET", 0)
    two, _ = grads("two_kernels")
    if case == "ties_at_the_threshold":
        assert np.asarray(tied)[:, 16:].mean() > 0.5
    for a, b in zip(one, two):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(a, b, atol=1e-6 * float(jnp.abs(b).max()))

    sizes = {"sa_config": {"topk": topk}}
    for row in range(rows):
        alone = tuple(x[row:row + 1] for x in operands)

        def reference(operands):
            out, index_loss = config.reference_attend(operands, sizes)
            out = jnp.swapaxes(out.reshape(seq, heads, dim), 0, 1)
            return jnp.sum(out * d_out[row]) + jnp.sum(
                index_loss * d_loss[row]
            )

        want = jax.grad(reference)(config.as_reference_operands(alone, sizes))
        folded = alone[3].shape[1] ** -0.5 * alone[3].shape[3] ** -0.5
        in_system_layout = (
            jnp.swapaxes(want["q"].reshape(seq, heads, dim), 0, 1),
            jnp.swapaxes(want["k"], 0, 1), jnp.swapaxes(want["v"], 0, 1),
            jnp.swapaxes(want["qi"], 0, 1), want["ki"], want["w"] / folded,
        )
        for got, b in zip(one, in_system_layout):
            np.testing.assert_allclose(
                got[row], b, atol=3e-5 * float(jnp.abs(b).max())
            )


# (seq, head_dim, queries a tile, keys a tile): the small tiles of the
# tests above (head_dim 32: an update is a head's whole key tile); the
# published tile and head_dim, where an update is one of the tile's
# four pieces of 128 keys; head_dim 64 on the published tile, which
# must take the whole tile again.
_TILES = {
    "tiles_of_16_x_32": (64, 32, 16, 32),
    "four_pieces_a_tile": (1024, 128, 128, 512),
    "head_dim_64": (1024, 64, 128, 512),
    # ... and the same with a kv head's group a line of its own: more
    # heads than one straight line holds, a loop over lines.
    "four_pieces_a_tile_in_lines": (1024, 128, 128, 512),
}


@pytest.mark.parametrize("tiles", list(_TILES))
@pytest.mark.parametrize("picks", ["scattered", "only_late_keys"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_the_forward_equals_the_reference_for_every_group(
    group, picks, tiles, monkeypatch
):
    """``out``, ``lse`` and ``L_I`` of the forward kernels alone, four
    query heads on 4, 2 and 1 kv heads, on several tiles against the
    plain reference, and ``count`` / ``tied`` and the mask the kernels
    apply against its selection exactly. ``only_late_keys``: index
    scores that grow with the key's position, so a query past the
    first key tile selects nothing in it — nor, on a tile of four
    pieces, in the pieces before its own — and meets its first selected
    key in a LATE piece with its running maximum still at NEG_INF,
    where the forward's one masked copy of the logits reads ``NEG_INF
    - NEG_INF``; ``scattered`` leaves whole pieces empty for some
    queries and not for their neighbours."""
    config = configurations.module(NAME)
    seq, dim, tq, tk = _TILES[tiles]
    operands, topk = _operands(picks, seq=seq, dim=dim, kv_heads=4 // group)
    q, k, v, qi, ki, w = operands
    heads = q.shape[1]
    scores, member = _plain_sets(qi, ki, w, topk)
    late = np.asarray(member)[tk + topk:, :tk]
    assert late.any() == (picks == "scattered")

    piece = sparse._piece(tq, tk, dim)
    assert piece == (128 if tiles.startswith("four_pieces") else tk)
    if tiles.endswith("in_lines"):
        monkeypatch.setattr(sparse, "_LINE_UPDATES", group * tk // piece)
    assert sparse._abreast(heads, 4 // group, tk // piece) == (
        group if tiles.endswith("in_lines") else heads
    )
    empty = ~np.asarray(member).reshape(seq, seq // piece, piece).any(-1)
    causal = np.arange(seq)[:, None] >= np.arange(seq // piece) * piece
    if piece < tk:
        # Some query finds a piece at or before its own empty, and,
        # with ``only_late_keys``, every piece before the last two.
        assert (empty & causal).any()
        assert picks == "scattered" or empty[seq - 1, :-2].all()

    scale = dim**-0.5
    wt = jnp.swapaxes(w, 1, 2)
    thr, cut, ilse, count, tied = sparse.index_select(
        qi, ki, wt, topk, tq, tk
    )
    out_t, lse = sparse._attention_forward(
        q, k, jnp.swapaxes(v, 2, 3), qi, ki, wt, thr, cut, scale, tq, tk
    )
    index_loss = sparse._index_loss(
        q, k, qi, ki, wt, thr, cut, lse, ilse, scale, tq, tk
    )

    # The selection, which no piece moved: the mask the kernels apply
    # (``_tile_mask`` on the whole tile), the keys a query and whether
    # position split the keys at its threshold.
    pairs, _, _, _ = sparse.selected_pairs(qi, ki, w, topk, tq, tk)
    np.testing.assert_array_equal(np.asarray(pairs[0]) == 1, member)
    np.testing.assert_array_equal(count[0, 0], member.sum(-1))
    visible = np.tri(seq, dtype=bool)
    kth = np.where(member, scores, np.inf).min(-1, keepdims=True)
    at_threshold = (np.asarray(scores) == kth) & visible
    np.testing.assert_array_equal(
        np.asarray(tied[0, 0]) == 1,
        at_threshold.sum(-1) > (at_threshold & member).sum(-1),
    )

    sizes = {"sa_config": {"topk": topk}}
    want_out, want_loss = config.reference_attend(
        config.as_reference_operands(operands, sizes), sizes
    )
    want_out = jnp.swapaxes(want_out.reshape(seq, heads, dim), 0, 1)
    logits = jnp.einsum(
        "htd,hsd->hts", q[0], jnp.repeat(k[0], group, axis=0),
        precision="highest",
    ) * scale
    want_lse = jax.nn.logsumexp(
        jnp.where(member[None], logits, -jnp.inf), axis=-1
    )
    np.testing.assert_allclose(
        jnp.swapaxes(out_t[0], 1, 2), want_out,
        atol=2e-5 * float(jnp.abs(want_out).max()),
    )
    np.testing.assert_allclose(lse[0, :, 0], want_lse, atol=2e-5)
    np.testing.assert_allclose(index_loss[0, 0], want_loss, atol=2e-5)


def test_schedule_event_and_kernel_names():
    """One ``sparse.schedule`` event a traced call site, and the names
    the benchmark's readers find the kernels by."""
    qi, ki, w = _index_inputs(32)
    q = jax.random.normal(jax.random.key(3), (1, 4, 32, 32))
    kv = jax.random.normal(jax.random.key(4), (1, 2, 32, 32))
    trace.reset_for_tests() if hasattr(trace, "reset_for_tests") else None
    before = len(
        [r for r in trace.snapshot_spans() if r["name"] == "sparse.schedule"]
    )
    sparse.sparse_attention(q, kv, kv, qi, ki, w, 8)
    events = [
        r for r in trace.snapshot_spans() if r["name"] == "sparse.schedule"
    ]
    assert len(events) == before + 1
    attrs = events[-1]["attrs"]
    assert attrs["path"] == "causal_tiles_masked"
    assert (attrs["topk"], attrs["heads"], attrs["head_dim"]) == (8, 4, 32)
    assert attrs["keys_visited"] == 32 * 32
    # How the forward-side kernels walk the 4 query heads on 2 kv
    # heads: all four abreast in one straight line, and at head_dim 32
    # an update is a head's whole key tile; at the published widths it
    # is 128 keys, and the 32 heads' 4 pieces each are one line.
    assert (attrs["head_loop"], attrs["group"]) == (
        "heads_abreast_in_pieces", 2
    )
    assert (attrs["piece"], attrs["pieces_in_flight"]) == (32, 4)
    assert sparse._piece(128, 512, 128) == 128
    assert sparse._abreast(32, 4, 4) == 32
    assert sparse._abreast(64, 8, 4) == 32  # two lines of 4 kv heads
    # dK and dV [2, 32, 32 -> 128 lanes] and dkI [32, 16 -> 128] in
    # float32 fit; the published widths at a row of 16 384 do too, a
    # row of 32 768 or 8 kv heads do not.
    assert attrs["backward"] == "one_kernel"
    assert attrs["backward_vmem_bytes"] == 4 * 32 * (2 * 2 * 128 + 128)
    assert sparse.backward_schedule(4, 16384, 128, 64) == (
        "one_kernel", 72 * 2**20
    )
    assert sparse.backward_schedule(4, 32768, 128, 64)[0] == "two_kernels"
    assert sparse.backward_schedule(8, 16384, 128, 64)[0] == "two_kernels"
    assert sparse.SELECT_KERNEL_NAME.startswith("sparse_index")
    for name in (
        sparse.FWD_KERNEL_NAME, sparse.KL_KERNEL_NAME,
        sparse.BWD_KERNEL_NAME, sparse.BWD_Q_KERNEL_NAME,
        sparse.BWD_KV_KERNEL_NAME,
    ):
        assert name.startswith("sparse_attn")
