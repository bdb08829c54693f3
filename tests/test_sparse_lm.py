"""What the keye-vl-2.0-30b-a3b configuration forced (PR 34), at small
sizes in float32 against the configuration's own plain reference
(``benchmark/configs/keye-vl-2.0-30b-a3b.py``, which imports nothing
from ``adaptdl_tpu``): the indexer's selection, sparse attention and
its backward, the indexer's own loss, the softmax top-k router, heads
wider than ``d_model / heads`` and the untied output table."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adaptdl_tpu import trace
from adaptdl_tpu.models import moe
from adaptdl_tpu.models.transformer import (
    GroupedQueryAttention,
    SparseAttention,
    TransformerConfig,
    TransformerLM,
    routed_lm_loss_fn,
    sparse_select_counters,
)
from adaptdl_tpu.ops import grouped_matmul as gmm
from adaptdl_tpu.ops import sparse_attention as sparse

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {
    "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "router_width": 8,
    "experts_held": 8, "num_experts": 8, "num_local_experts": 8,
    "num_experts_per_tok": 2, "vocab_size": 97, "sequence_length": 32,
    "num_hidden_layers": 1, "compute_dtype": "float32",
    "sa_config": {
        "indexer_head_dim": 16, "indexer_num_heads": 3,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 8,
    },
}


@functools.cache
def _config_module():
    from benchmark import manifest

    return manifest.load_module(
        os.path.join(ROOT, "benchmark", "configs", "keye-vl-2.0-30b-a3b.py")
    )


def _sizes(**changes):
    with open(
        os.path.join(
            ROOT, "benchmark", "configs", "keye-vl-2.0-30b-a3b.json"
        )
    ) as f:
        sizes = json.load(f)
    sizes.update(TINY)
    sizes.update(changes)
    return sizes


def _model(sizes, seed=3):
    cfg = _config_module().model_config(sizes)
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.key(seed),
        jnp.zeros((1, sizes["sequence_length"]), jnp.int32), train=False,
    )["params"]
    return model, params


def _row(sizes, seed=5):
    data = _config_module().make_dataset(sizes, seed, 4)
    return {k: jnp.asarray(v[:1]) for k, v in data.items()}


@pytest.fixture(params=["one_kernel", "two_kernels"])
def backward(request, monkeypatch):
    """Both schedules of the backward on the same small shapes: the
    row-wide accumulators of a test's row always fit, so the two
    kernels are reached by taking the budget away."""
    if request.param == "two_kernels":
        monkeypatch.setattr(sparse, "_ROW_BUDGET", 0)
    assert sparse.backward_schedule(2, 32, 32, 16)[0] == request.param


# ---- the whole model against the plain reference --------------------


def test_losses_and_every_gradient_equal_the_reference(backward):
    """L_LM, L_I and the gradient of their sum to every parameter, the
    system (kernels interpreted, remat on) against ``jax.grad`` of the
    plain reference on the same weights and row."""
    config = _config_module()
    sizes = _sizes()
    model, params = _model(sizes)
    batch = _row(sizes)
    loss_fn = routed_lm_loss_fn(model)
    (loss, counters), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, jax.random.key(0)
    )

    def reference(weights):
        lm, index, _ = config.reference_loss(
            weights, batch["inputs"][0], batch["targets"][0], sizes
        )
        return lm + index, (lm, index)

    weights = config.reference_weights(params, sizes)
    (total, (lm, index)), want = jax.value_and_grad(
        reference, has_aux=True
    )(weights)
    np.testing.assert_allclose(loss, total, rtol=2e-6)
    np.testing.assert_allclose(
        counters["indexer.loss"]["loss"].mean(), index, rtol=2e-5
    )
    assert float(index) > 0 and float(lm) > float(index)
    got = config.reference_weights(grads, sizes)
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == 3 + 15
    for (path, a), b in zip(flat_got, flat_want):
        assert float(jnp.abs(b).max()) > 0, path
        np.testing.assert_allclose(
            a, b, atol=3e-5 * float(jnp.abs(b).max()), err_msg=str(path)
        )
    select = counters["sparse.select"]
    row = config.selected_pairs_per_row(32, 8)
    np.testing.assert_array_equal(select["keys_selected"], [row])
    np.testing.assert_array_equal(select["queries"], [32])
    np.testing.assert_array_equal(
        select["keys_visited"], [sparse.keys_visited(32)]
    )


def test_no_gradient_crosses_between_the_two_losses(backward):
    """L_LM reaches everything but the indexer's parameters, L_I
    reaches them and nothing else: exact zeros on the other side."""
    sizes = _sizes()
    model, params = _model(sizes)
    batch = _row(sizes)
    cfg = model.config

    def losses(params):
        logits, sown = model.apply(
            {"params": params}, batch["inputs"], train=True,
            mutable=["moe_load", "indexer_loss", "sparse_select"],
        )
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits), batch["targets"][..., None], -1
        )
        return -picked.mean(), sparse_select_counters(cfg, sown)[
            "indexer.loss"
        ]["loss"].mean()

    of_lm = jax.grad(lambda p: losses(p)[0])(params)
    of_index = jax.grad(lambda p: losses(p)[1])(params)
    for path, leaf in jax.tree_util.tree_flatten_with_path(of_lm)[0]:
        inside = any(getattr(k, "key", "") == "indexer" for k in path)
        other = of_index
        for k in path:
            other = other[k.key]
        if inside:
            assert not np.any(np.asarray(leaf)), path
            assert np.any(np.asarray(other)), path
        else:
            assert not np.any(np.asarray(other)), path
            assert np.any(np.asarray(leaf)), path


# ---- the selection ----------------------------------------------------


def _index_inputs(seq, heads=3, dim=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (
        jax.random.normal(keys[0], (1, heads, seq, dim)),
        jax.random.normal(keys[1], (1, seq, dim)),
        jax.random.normal(keys[2], (1, seq, heads)) * 0.3,
    )


def _plain_sets(qi, ki, w, topk):
    config = _config_module()
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
    cfg = _config_module().model_config(
        _sizes(sa_config={**TINY["sa_config"], "topk": 64})
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
        _config_module().model_config(_sizes()), seq_axis="seq"
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
        ki = jnp.ones_like(ki) * (1.0 + jnp.arange(seq))[None, :, None] / 16
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
    config = _config_module()
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


@pytest.mark.parametrize("picks", ["scattered", "only_late_keys"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_the_forward_equals_the_reference_for_every_group(group, picks):
    """``out``, ``lse`` and ``L_I`` of the forward kernels alone, four
    query heads on 4, 2 and 1 kv heads (``heads == kv_heads`` is one
    head a step of ``_over_heads``), on several tiles against the plain
    reference. ``only_late_keys``: index scores that grow with the
    key's position, so a query past the first key tile selects nothing
    in it and meets its first selected key with its running maximum
    still at NEG_INF, where the forward's one masked copy of the
    logits reads ``NEG_INF - NEG_INF``."""
    config = _config_module()
    tq, tk = 16, 32
    operands, topk = _operands(picks, kv_heads=4 // group)
    q, k, v, qi, ki, w = operands
    _, heads, seq, dim = q.shape
    _, member = _plain_sets(qi, ki, w, topk)
    late = np.asarray(member)[tk + topk:, :tk]
    assert late.any() == (picks == "scattered")

    scale = dim**-0.5
    wt = jnp.swapaxes(w, 1, 2)
    thr, cut, ilse, _, _ = sparse.index_select(qi, ki, wt, topk, tq, tk)
    out_t, lse = sparse._attention_forward(
        q, k, jnp.swapaxes(v, 2, 3), qi, ki, wt, thr, cut, scale, tq, tk
    )
    index_loss = sparse._index_loss(
        q, k, qi, ki, wt, thr, cut, lse, ilse, scale, tq, tk
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
    # How the forward-side kernels walk the 4 query heads on 2 kv heads.
    assert (attrs["head_loop"], attrs["group"]) == ("kv_groups_unrolled", 2)
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


# ---- the softmax router and the share ---------------------------------


def _layer(seed=7, tokens=64, d=32, f=24, experts=8):
    keys = jax.random.split(jax.random.key(seed), 5)
    return {
        "x": jax.random.normal(keys[0], (tokens, d)),
        "router": jax.random.normal(keys[1], (d, experts)) * 0.5,
        "w1": jax.random.normal(keys[2], (experts, d, f)) * d**-0.5,
        "w3": jax.random.normal(keys[3], (experts, d, f)) * d**-0.5,
        "w2": jax.random.normal(keys[4], (experts, f, d)) * f**-0.5,
    }


def test_softmax_router_equals_a_plain_one():
    layer = _layer()
    sizes = _sizes()
    experts, weights = moe.softmax_top_k(
        layer["x"], layer["router"], 2, 1e-20, 1.0
    )
    config = _config_module()
    want = config.reference_router(layer, layer["x"], sizes)
    got = config.in_expert_order(experts, weights)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    # Softmax is not the sigmoid router: the weights differ.
    _, sigmoid = moe.sigmoid_top_k(
        layer["x"], layer["router"], jnp.zeros(8), 2, 1e-20, 1.0
    )
    assert float(jnp.abs(jnp.sort(sigmoid) - jnp.sort(weights)).max()) > 1e-3
    with pytest.raises(ValueError, match="router_kind"):
        moe.routed_experts(
            layer["x"], layer["router"], None, layer["w1"], layer["w3"],
            layer["w2"], experts_total=8, first_expert=0, top_k=2,
            router_kind="argmax",
        )


@pytest.mark.parametrize("shares", [1, 4])
def test_the_shares_of_a_softmax_layer_add_up_to_the_uncut_layer(shares):
    """``shares`` chips, each told which ``8 / shares`` experts it
    holds: their partial results, summed, are the whole layer's as the
    uncut reference gives it."""
    layer = _layer()
    sizes = _sizes()
    held = 8 // shares
    with jax.default_matmul_precision("highest"):
        whole, counts = _config_module().reference_routed_ffn(
            layer, layer["x"], sizes, first_expert=0
        )
    total, rows = jnp.zeros_like(whole), []
    for chip in range(shares):
        at = slice(chip * held, (chip + 1) * held)
        y, load = moe.routed_experts(
            layer["x"], layer["router"], None, layer["w1"][at],
            layer["w3"][at], layer["w2"][at], experts_total=8,
            first_expert=chip * held, top_k=2, router_kind="softmax",
        )
        assert int(load["dropped"]) == 0
        assert int(load["held_rows"].sum() + load["left_out"]) == 64 * 2
        rows.append(np.asarray(load["held_rows"]))
        total = total + y
    np.testing.assert_allclose(total, whole, atol=5e-5)
    np.testing.assert_array_equal(np.concatenate(rows), np.asarray(counts))


def _softmax_value_and_gradients(layer, first, held, top_k):
    """The softmax layer's output, load and the gradients of a fixed
    functional of it by input, router and the three expert leaves."""
    cot = jax.random.normal(jax.random.key(23), layer["x"].shape)
    at = slice(first, first + held)

    def of(x, router, w1, w3, w2):
        y, load = moe.routed_experts(
            x, router, None, w1, w3, w2,
            experts_total=layer["router"].shape[1], first_expert=first,
            top_k=top_k, router_kind="softmax",
        )
        return (y * cot).sum(), (y, load)

    (_, (y, load)), grads = jax.value_and_grad(of, range(5), has_aux=True)(
        layer["x"], layer["router"], layer["w1"][at], layer["w3"][at],
        layer["w2"][at],
    )
    return y, grads, load


@pytest.mark.parametrize(
    "tokens, experts, first, held, top_k",
    [(64, 16, 4, 2, 2), (128, 32, 0, 4, 8)],
)
def test_the_bounded_softmax_layer_equals_the_worst_case_bit_for_bit(
    tokens, experts, first, held, top_k, monkeypatch
):
    """A softmax router's share small enough to bound (the cell's own,
    16 of 128, is not: ``rows_bound``): the glue walks ``rows_bound``
    rows, and output and all five gradients are those of the layer
    that walks ``rows_capacity``."""
    layer = _layer(seed=tokens, tokens=tokens, experts=experts)
    tile = gmm.tile_rows(tokens * min(top_k, held))
    bound = moe.rows_bound(tokens, top_k, held, experts, tile)
    capacity = moe.rows_capacity(tokens, top_k, held, tile)
    assert bound < capacity
    bounded = _softmax_value_and_gradients(layer, first, held, top_k)
    assert int(bounded[2]["fell_back"]) == 0
    assert int(bounded[2]["rows_walked"]) == bound
    assert int(bounded[2]["dropped"]) == 0
    monkeypatch.setattr(moe, "ROWS_BOUND_FACTOR", 1e9)
    worst = _softmax_value_and_gradients(layer, first, held, top_k)
    assert int(worst[2]["rows_walked"]) == capacity
    np.testing.assert_array_equal(bounded[0], worst[0])
    for name, a, b in zip(
        ("x", "router", "w1", "w3", "w2"), bounded[1], worst[1]
    ):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize(
    "held_pair, active", [(32, 4), (48, 6), (64, 8)]
)
def test_a_softmax_plan_past_the_bound_falls_back_and_drops_nothing(
    held_pair, active
):
    """64 tokens, top 2 of 16, experts 0 and 1 held, tiles of 16 rows:
    the glue walks 80 rows where the worst case has 160.
    ``held_pair`` tokens choose the held pair and
    the rest two experts held elsewhere: inside the bound, past it,
    and every token on the pair."""
    layer = _layer(tokens=64, experts=16)
    d = layer["x"].shape[1]
    on_pair = jnp.arange(64) < held_pair
    x = jnp.where(on_pair[:, None], jnp.eye(d)[0], jnp.eye(d)[1])
    router = (
        jnp.full((d, 16), -6.0)
        .at[0, 0].set(4.0).at[0, 1].set(3.0)
        .at[1, 5].set(4.0).at[1, 6].set(3.0)
    )
    steered = dict(layer, x=x, router=router)
    at = slice(0, 2)
    y, load = moe.routed_experts(
        x, router, None, layer["w1"][at], layer["w3"][at], layer["w2"][at],
        experts_total=16, first_expert=0, top_k=2, router_kind="softmax",
    )
    with jax.default_matmul_precision("highest"):
        want, counts = _config_module().reference_routed_ffn(
            {**steered, "w1": layer["w1"][at], "w3": layer["w3"][at],
             "w2": layer["w2"][at]},
            x, _sizes(num_experts=16), first_expert=0,
        )
    assert moe.rows_bound(64, 2, 2, 16, 16) == 80
    assert int(load["dropped"]) == 0
    assert int(load["fell_back"]) == int(active * 16 > 80)
    assert int(load["rows_active"]) == active * 16
    assert int(load["rows_walked"]) == (160 if active * 16 > 80 else 80)
    np.testing.assert_array_equal(load["held_rows"], [held_pair] * 2)
    np.testing.assert_array_equal(load["held_rows"], counts[:2])
    np.testing.assert_allclose(y, want, atol=5e-5)


# ---- heads of their own width, the untied table -----------------------


def _tree(params):
    return {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }


@pytest.mark.parametrize("family", ["gpt2", "lfm2"])
def test_the_new_options_keep_the_older_parameter_trees(family):
    """``head_dim`` and ``tie_embeddings`` left alone build the trees
    the two older configurations' checkpoints hold (a checkpoint of
    either restores): every leaf by path and shape."""
    if family == "gpt2":
        cfg = TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
            max_seq_len=16, dtype=jnp.float32,
        )
        want = {
            "embed/embedding": (64, 16),
            "LayerNorm_0/scale": (16,),
            "layer_0/LayerNorm_0/scale": (16,),
            "layer_0/LayerNorm_1/scale": (16,),
            "layer_0/attention/qkv/kernel": (16, 3, 2, 8),
            "layer_0/attention/out/kernel": (16, 16),
            "layer_0/ff_up/kernel": (16, 32),
            "layer_0/ff_down/kernel": (32, 16),
        }
    else:
        cfg = TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=2,
            d_model=16, d_ff=32, max_seq_len=16, dtype=jnp.float32,
            norm="rmsnorm", ffn="swiglu", qk_norm=True,
            layer_types=("full_attention",), experts_total=4,
            experts_held=2, experts_top_k=2, d_expert=8,
        )
        want = {
            "embed/embedding": (64, 16),
            "RMSNorm_0/scale": (16,),
            "layer_0/RMSNorm_0/scale": (16,),
            "layer_0/RMSNorm_1/scale": (16,),
            "layer_0/attention/q/kernel": (16, 4, 4),
            "layer_0/attention/kv/kernel": (16, 2, 2, 4),
            "layer_0/attention/q_norm/scale": (4,),
            "layer_0/attention/k_norm/scale": (4,),
            "layer_0/attention/out/kernel": (16, 16),
            "layer_0/moe/router": (16, 4),
            "layer_0/moe/expert_bias": (4,),
            "layer_0/moe/w_gate": (2, 16, 8),
            "layer_0/moe/w_up": (2, 16, 8),
            "layer_0/moe/w_down": (2, 8, 16),
        }
    assert cfg.head_dim is None and cfg.tie_embeddings
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False
    )["params"]
    assert _tree(params) == want


def test_wider_heads_and_the_untied_table():
    """Heads of 32 on a hidden size of 64 (4 x 32 = 128 columns), an
    output table of its own, and no bias buffer under the softmax
    router."""
    sizes = _sizes()
    _, params = _model(sizes)
    tree = _tree(params)
    assert tree["layer_0/attention/q/kernel"] == (64, 4, 32)
    assert tree["layer_0/attention/kv/kernel"] == (64, 2, 2, 32)
    assert tree["layer_0/attention/out/kernel"] == (128, 64)
    assert tree["layer_0/attention/indexer/index_q/kernel"] == (64, 3, 16)
    assert tree["layer_0/attention/indexer/index_k/kernel"] == (64, 16)
    assert tree["layer_0/attention/indexer/index_w/kernel"] == (64, 3)
    assert tree["lm_head"] == (97, 64) == tree["embed/embedding"]
    assert "layer_0/moe/expert_bias" not in tree
    assert not np.array_equal(params["lm_head"], params["embed"]["embedding"])
