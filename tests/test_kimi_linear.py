"""What the kimi-linear-48b-a3b configuration forced (PR 46), at small
sizes against the configuration's own plain reference
(``benchmark/configs/kimi-linear-48b-a3b.py``, which imports nothing
from ``adaptdl_tpu``): the chunked gated delta rule and its Pallas
kernels, latent attention at a q/k width that is not v's, the shared
expert, the share of an expert-parallel layer, and that the four
configurations the benchmark had are the programs of before."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adaptdl_tpu import trace
from adaptdl_tpu.models.transformer import (
    KDA,
    LatentAttention,
    RoutedFFN,
    TransformerConfig,
    causal_attention,
)
from adaptdl_tpu.ops import kda as kda_op
from adaptdl_tpu.ops.flash_attention import flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kimi-linear-48b-a3b"
TINY = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "linear_attn_config": {
        "full_attn_layers": [4], "head_dim": 8, "kda_layers": [1, 2, 3, 5],
        "num_heads": 2, "short_conv_kernel_size": 4,
    },
    "kv_lora_rank": 12, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "router_width": 16, "experts_held": 4,
    "num_experts": 4, "num_experts_per_token": 2, "num_experts_per_tok": 2,
    "vocab_size": 97, "sequence_length": 64, "kda_gate_rank": 8,
    "kda_chunk": 16, "head_chunk_rows": 32, "compute_dtype": "float32",
}


@pytest.fixture(autouse=True)
def _rows_of_several_chunks(monkeypatch):
    """The rule's chunk is a constant of ``ops/kda.py`` (64); the
    models of these tests run rows of 64 tokens, several chunks at
    TINY's."""
    monkeypatch.setattr(kda_op, "CHUNK", TINY["kda_chunk"])


@functools.cache
def _config_module():
    from benchmark import manifest

    return manifest.load_module(
        os.path.join(ROOT, "benchmark", "configs", NAME + ".py")
    )


def _sizes(**changes):
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        sizes = json.load(f)
    sizes.update(TINY)
    sizes.update(changes)
    return sizes


def _built(monkeypatch, sizes, seed=3):
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    geometry = {"global_batch": 4, "atomic_bsz": 2, "accum_steps": 1}
    return _config_module().build(sizes, geometry, seed)


# ---- the chunked delta rule -------------------------------------------


def _kda_inputs(seed, batch=2, seq=40, heads=2, dk=8, dv=8,
                dtype=jnp.float32, decay=0.5):
    keys = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(keys[0], (batch, seq, heads, dk))
    k = jax.random.normal(keys[1], (batch, seq, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, seq, heads, dv))
    g = -decay * jnp.exp(jax.random.normal(keys[3], (batch, seq, heads, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, heads)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _weighted(fn, shape):
    cotangent = jnp.cos(jnp.arange(np.prod(shape))).reshape(shape)
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cotangent)


def _grads(fn, shape, args):
    """Every operand's gradient of ``fn`` under ``_weighted``'s
    cotangent, as one compiled program (op by op, a chunk's hundreds
    of small operations are each dispatched by themselves)."""
    return jax.jit(jax.grad(_weighted(fn, shape), (0, 1, 2, 3, 4)))(*args)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize(
    "chunk,seq", [(8, 40), (16, 40), (32, 64), (64, 128), (64, 100)]
)
def test_chunked_kda_is_the_recurrence(chunk, seq, use_kernel):
    """Forward and the gradient of every operand against the
    recurrence token by token, at chunk lengths that do and do not
    divide the row, through the Pallas kernels (interpret mode) and
    through the scan."""
    args = _kda_inputs(0, seq=seq)
    want = kda_op.kda_recurrent(*args)
    run = functools.partial(kda_op.kda, chunk=chunk, use_kernel=use_kernel)
    assert _rel(jax.jit(run)(*args), want) < 1e-5
    got = _grads(run, want.shape, args)
    ref = _grads(kda_op.kda_recurrent, want.shape, args)
    for a, b in zip(got, ref):
        assert _rel(a, b) < 2e-5


def test_kda_kernels_equal_the_scan_on_bf16_operands():
    """The kernels against the ``jax.numpy`` chunked form on bfloat16
    operands: the same arithmetic, so nearly the same bits; and both
    within bfloat16's rounding of the float32 recurrence."""
    args = _kda_inputs(1, seq=64, dtype=jnp.bfloat16)
    want = kda_op.kda_recurrent(*args)
    outs, grads = {}, {}
    for use_kernel in (True, False):
        run = functools.partial(kda_op.kda, chunk=16, use_kernel=use_kernel)
        outs[use_kernel] = jax.jit(run)(*args)
        grads[use_kernel] = _grads(run, want.shape, args)
    assert outs[True].dtype == jnp.bfloat16
    assert _rel(outs[True], outs[False]) < 1e-2
    assert _rel(outs[True], want) < 3e-2
    for a, b in zip(grads[True], grads[False]):
        assert _rel(a, b) < 2e-2
    ref = _grads(kda_op.kda_recurrent, want.shape, args)
    for a, b in zip(grads[True], ref):
        assert _rel(a, b) < 6e-2


def test_kda_survives_a_decay_no_float32_inverse_holds():
    """A decay of e^-40 a token: ``e^{-G}`` of a chunk would overflow
    float32; no exponent here is positive."""
    args = _kda_inputs(2, seq=64, decay=40.0)
    run = functools.partial(kda_op.kda, chunk=64)
    got = jax.jit(run)(*args)
    assert bool(jnp.isfinite(got).all())
    assert _rel(got, kda_op.kda_recurrent(*args)) < 1e-5
    grads = _grads(run, got.shape, args)
    assert all(bool(jnp.isfinite(x).all()) for x in grads)


def test_kda_in_head_groups_is_kda(monkeypatch):
    """One head at a time (what a long row forces) gives what all
    heads at once give, forward and backward."""
    args = _kda_inputs(3, seq=40, heads=4)
    run = functools.partial(kda_op.kda, chunk=16)
    whole = jax.jit(run)(*args)
    whole_grads = _grads(run, whole.shape, args)
    monkeypatch.setattr(kda_op, "_GROUP_ELEMENTS", 2 * 40 * 8)
    assert kda_op.head_groups(80, 4, 8) == 4
    np.testing.assert_allclose(
        jax.jit(lambda *a: run(*a))(*args), whole, rtol=1e-6, atol=1e-7
    )
    grads = _grads(lambda *a: run(*a), whole.shape, args)
    for a, b in zip(grads, whole_grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_kda_mixer_in_head_groups_is_the_mixer(monkeypatch):
    """The module's own work on a head (convolutions, norms, the
    decay) done a group of heads at a time, as a long row forces:
    what all heads at once give, forward and backward."""
    config, sizes = _config_module(), _sizes()
    cfg = config.model_config(sizes)
    u = jax.random.normal(jax.random.key(9), (2, 64, 32))
    module = KDA(cfg)
    params = module.init(jax.random.key(1), u, None)["params"]

    def run(params, u):
        return module.apply({"params": params}, u, None)

    def loss(params, u):
        return jnp.sum(run(params, u) * jnp.cos(u))

    whole, whole_grads = run(params, u), jax.grad(loss, (0, 1))(params, u)
    monkeypatch.setattr(kda_op, "_GROUP_ELEMENTS", 2 * 64 * 8)
    assert kda_op.head_groups(2 * 64, 2, 8) == 2
    np.testing.assert_allclose(run(params, u), whole, rtol=1e-5, atol=1e-6)
    grads = jax.grad(loss, (0, 1))(params, u)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(whole_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("decay", ["channel", "head"])
def test_kda_schedule_is_journalled(decay):
    before = len(
        [r for r in trace.snapshot_spans() if r["name"] == "kda.schedule"]
    )
    q, k, v, g, beta = _kda_inputs(4, seq=40)
    kda_op.kda(q, k, v, g if decay == "channel" else g[..., 0], beta, chunk=16)
    events = [
        r for r in trace.snapshot_spans() if r["name"] == "kda.schedule"
    ]
    assert len(events) == before + 1
    attrs = events[-1]["attrs"]
    assert attrs["decay"] == decay
    # How the chunk's inverse is formed (the per-channel body: levels
    # of block products above a sub-block; the one-decay body: forward
    # substitution over the whole chunk), that the forward rule writes
    # it out for the backward, chunks in one basic block of the two
    # chunk kernels (three chunks a grid step here: one).
    assert (attrs["inverse"], attrs["inverse_kept"]) == (
        "levels" if decay == "channel" else "substituted", True
    )
    assert (attrs["chunks_abreast"], attrs["chunks_abreast_bwd"]) == (1, 1)
    # Two chunks a grid step: the one-decay pair walks them abreast,
    # the per-channel pair its backward alone.
    kda_op.kda(*(x[:, :32] for x in (q, k, v, g, beta)), chunk=16)
    kda_op.kda(*(x[:, :32] for x in (q, k, v, g[..., 0], beta)), chunk=16)
    channel, head = [
        r["attrs"] for r in trace.snapshot_spans()
        if r["name"] == "kda.schedule"
    ][-2:]
    assert (channel["chunks_abreast"], channel["chunks_abreast_bwd"]) == (1, 2)
    assert (head["chunks_abreast"], head["chunks_abreast_bwd"]) == (2, 2)
    assert "as the forward rule wrote it out" in attrs["backward"]
    fallback = kda_op.kda(q, k, v, g, beta, chunk=16, use_kernel=False)
    assert fallback.shape == v.shape
    xla = [
        r for r in trace.snapshot_spans() if r["name"] == "kda.schedule"
    ][-1]["attrs"]
    assert (xla["inverse"], xla["inverse_kept"], xla["chunks_abreast"],
            xla["chunks_abreast_bwd"]) == ("levels", False, 0, 0)
    assert (attrs["heads"], attrs["head_dim"], attrs["chunk"]) == (2, 8, 16)
    assert attrs["chunks"] == 3 and attrs["padded"] == 8
    short = kda_op.kda(*_kda_inputs(4, seq=12), chunk=64)  # chunks of 8
    assert _rel(short, kda_op.kda_recurrent(*_kda_inputs(4, seq=12))) < 1e-5
    assert attrs["path"] == "kernel" and "kda_fwd" in attrs["product"]
    assert attrs["saved_names"] == "kda_out"
    assert attrs["head_groups"] == 1
    # What a grid step of the state kernels holds: here everything,
    # both of the batch's rows x both heads and all three chunks.
    assert (attrs["state_heads_a_step"], attrs["state_chunks_a_step"],
            attrs["state_chunks_a_step_bwd"]) == (4, 3, 3)
    assert attrs["state_grid_steps"] == 1


# ---- what a grid step of the state kernels holds ----------------------


def _state_operands(bh, chunks, dtype, chunk=16, width=8):
    """What the chunks' own work hands the state kernels for ``bh``
    heads of ``chunks`` chunks, and an output's cotangent."""
    args = _kda_inputs(
        11, batch=1, seq=chunks * chunk, heads=bh, dk=width, dv=width,
        dtype=dtype,
    )
    operands = jax.jit(lambda *a: _prepare_blocks(a, chunk))(
        *_chunked(args, chunk)
    )
    d_o = jnp.cos(jnp.arange(operands[3].size, dtype=jnp.float32))
    return operands, d_o.reshape(operands[3].shape).astype(dtype)


def _state_kernels(operands, d_o):
    """-> (output, chunk states, the six gradients) of the kernels as
    ``_state_how`` now schedules them."""
    out, states = jax.jit(lambda *a: kda_op._fwd_pallas(*a))(*operands)
    grads = jax.jit(lambda *a: kda_op._bwd_pallas(*a))(*operands, states, d_o)
    return (out, states) + tuple(grads)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize(
    "bh,chunks", [(4, 16), (4, 12), (3, 8), (1, 1), (2, 7), (8, 32)]
)
def test_state_kernels_in_blocks_are_the_kernels_a_chunk_a_step(
    monkeypatch, bh, chunks, dtype
):
    """Several chunks and several heads a grid step (blocks of both,
    of either, of neither; a prime chunk count; a head count that 4
    does not divide) give, BIT FOR BIT, what one chunk of one head a
    step gives: output, every chunk state, all six gradients; and
    what the scans over the same two functions give."""
    operands, d_o = _state_operands(bh, chunks, dtype)
    # A sixteenth of the kernels' VMEM, so that these tiny blocks do
    # not all fit one grid step.
    monkeypatch.setattr(kda_op, "_BLOCKS_SHARE", 1 / 16)
    size = jnp.dtype(dtype).itemsize
    held, held_bwd = (
        kda_op._state_how(bh, chunks, 16, 8, 8, size, backward)
        for backward in (False, True)
    )
    assert bh % held.heads == 0 and chunks % held.chunks == 0
    assert held_bwd.chunks <= held.chunks
    assert (held == (1, 1)) == ((bh, chunks) == (1, 1))
    if (bh, chunks) == (4, 16):  # the state crosses grid steps
        assert (held, held_bwd.chunks) == ((4, 4), 2 if size == 4 else 4)
    if (bh, chunks, size) == (2, 7, 4):  # seven do not fit: one
        assert (held, held_bwd) == ((2, 7), (2, 1))
    blocked = _state_kernels(operands, d_o)
    monkeypatch.setattr(
        kda_op, "_state_how", lambda *a: kda_op._Held(1, 1)
    )
    for got, want in zip(blocked, _state_kernels(operands, d_o)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32)
        )
    out, states = jax.jit(kda_op._fwd_scan)(*operands)
    grads = jax.jit(kda_op._bwd_scan)(*operands, states, d_o)
    assert _rel(blocked[0], out) < 1e-2 and _rel(blocked[1], states) < 1e-2
    for got, want in zip(blocked[2:], grads):
        assert _rel(got, want) < 2e-2


def test_kda_in_blocks_on_a_padded_row_is_the_recurrence(monkeypatch):
    """``kda`` end to end on a row whose last chunk is padded, the
    state kernels in blocks of four heads and four (backward: two)
    chunks: the recurrence token by token, forward and gradient."""
    monkeypatch.setattr(kda_op, "_BLOCKS_SHARE", 1 / 16)
    args = _kda_inputs(12, batch=1, seq=120, heads=4)
    run = functools.partial(kda_op.kda, chunk=16)
    want = kda_op.kda_recurrent(*args)
    assert _rel(jax.jit(run)(*args), want) < 1e-5
    got = _grads(run, want.shape, args)
    for a, b in zip(got, _grads(kda_op.kda_recurrent, want.shape, args)):
        assert _rel(a, b) < 2e-5
    attrs = [
        r for r in trace.snapshot_spans() if r["name"] == "kda.schedule"
    ][-1]["attrs"]
    assert (attrs["chunks"], attrs["padded"]) == (8, 8)
    assert (attrs["state_heads_a_step"], attrs["state_chunks_a_step"],
            attrs["state_chunks_a_step_bwd"]) == (4, 4, 2)
    assert attrs["state_grid_steps"] == 2


def test_the_state_schedule_is_a_pure_function_of_its_shapes():
    """At the cell's shapes (four heads of 128, 256 chunks of 64,
    bf16) a grid step holds the group's four heads and eight chunks;
    wider operands take fewer, the backward never more than the
    forward; where nothing larger divides or fits, (1, 1)."""
    how, held = kda_op._state_how, kda_op._Held
    cell = (4, 256, 64, 128, 128)
    for _ in range(2):
        assert how(*cell, 2, False) == held(4, 8) == how(*cell, 2, True)
    assert how(*cell, 4, False) == held(4, 4) == how(*cell, 4, True)
    assert how(4, 256, 64, 256, 256, 2, False) == held(4, 4)
    assert how(4, 256, 64, 256, 256, 2, True) == held(4, 2)
    assert how(6, 256, 64, 128, 128, 2, False) == held(3, 16)
    assert how(6, 256, 64, 128, 128, 2, True) == held(3, 8)
    assert how(32, 12, 64, 128, 128, 2, False) == held(4, 12)
    assert how(1, 1, 64, 128, 128, 2, False) == held(1, 1)
    assert how(1, 7, 64, 128, 128, 2, True) == held(1, 7)
    for backward in (False, True):  # neither 5 nor 257 has a divisor
        assert how(5, 257, 64, 128, 128, 2, backward) == held(1, 1)


# ---- the chunks' own work as a kernel pair ----------------------------


def _chunked(args, chunk):
    """``kda``'s operands as its two stages take them: [b * h, chunks,
    C, w] blocks (beta [b * h, chunks, C]), the row padded with tokens
    that leave the state as it is."""
    q = args[0]
    batch, seq, heads, _ = q.shape
    chunks = -(-seq // chunk)

    def rows(x):
        x = jnp.swapaxes(x, 1, 2)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, chunks * chunk - seq), (0, 0)))
        return x.reshape(batch * heads, chunks, chunk, x.shape[-1])

    q, k, v, g, beta = args
    return rows(q), rows(k), rows(v), rows(g), rows(beta[..., None])[..., 0]


def _prepare_blocks(operands, chunk, scale=0.3):
    """The XLA ``_prepare`` on ``_chunked``'s blocks, its six results
    as the state kernels take them: [b * h, chunks, ...]."""
    bh, chunks = operands[0].shape[:2]
    outs = kda_op._prepare(
        *(x.reshape((bh * chunks,) + x.shape[2:]) for x in operands),
        chunk, scale,
    )
    return tuple(
        x.reshape((bh, chunks) + x.shape[1:]) for x in outs[:5]
    ) + (outs[5].reshape(bh, chunks, 1, -1),)


def _own_work_both_ways(args, chunk, scale=0.3, xla=True):
    """-> ((results, gradients) of the kernel pair, of ``_prepare``
    unless ``xla`` is false), the gradients under random cotangents of
    all six results."""
    q, k, v, g, beta = _chunked(args, chunk)

    def kernels(q, k, v, g, beta):
        return kda_op._own_work(scale, q, k, v, g, beta[:, :, None, :])

    def prepare(*operands):
        return _prepare_blocks(operands, chunk, scale)

    cotangents = [
        jax.random.normal(jax.random.key(30 + i), x.shape)
        for i, x in enumerate(jax.eval_shape(kernels, q, k, v, g, beta))
    ]

    def both(fn):
        def loss(*operands):
            return sum(
                jnp.sum(x.astype(jnp.float32) * c)
                for x, c in zip(fn(*operands), cotangents)
            )

        return jax.jit(
            lambda *operands: (
                fn(*operands), jax.grad(loss, tuple(range(5)))(*operands)
            )
        )(q, k, v, g, beta)

    return both(kernels), both(prepare) if xla else None


@pytest.mark.parametrize(
    "dtype,chunk,seq,limit",
    [
        ("float32", 32, 64, 2e-5),  # two sub-blocks, the chunk divides
        ("float32", 64, 100, 2e-5),  # four, the last chunk padded
        ("bfloat16", 32, 64, 2e-2),
        ("bfloat16", 64, 100, 2e-2),
        # An odd number of chunks (one chunk a basic block), the last
        # one padded.
        ("float32", 16, 40, 2e-5),
        ("bfloat16", 16, 40, 2e-2),
        ("float32", 64, 150, 2e-5),
    ],
)
def test_chunk_kernels_equal_the_xla_own_work(dtype, chunk, seq, limit):
    """``delta_chunk_fwd`` / ``delta_chunk_bwd`` (interpret mode)
    against ``_prepare`` and its autodiff: every result, and every
    operand's gradient under random cotangents of all six — the
    backward through the inverse and ``A`` that the forward rule wrote
    out, where ``_prepare``'s autodiff keeps its own. In bfloat16
    the forward rounds where ``_prepare`` rounds (nearly the same
    bits); the hand-written backward keeps float32 where autodiff
    rounds a cotangent to the operand's bfloat16."""
    args = _kda_inputs(5, batch=1, seq=seq, dtype=jnp.dtype(dtype))
    (got, got_grads), (want, want_grads) = _own_work_both_ways(args, chunk)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert _rel(a, b) < (1e-5 if dtype == "float32" else 4e-3)
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert _rel(a, b) < limit


@pytest.mark.parametrize(
    "case", ["decay_e-40_a_token", "beta_near_0", "beta_near_1"]
)
def test_chunk_kernels_at_the_edges(case):
    """A decay of e^-40 a token (``e^{-G}`` of a chunk overflows
    float32; no exponent in the kernels is positive), a step ``beta``
    of 1e-6 (``T`` ~ 0) and of 1 - 1e-6 (the inverse at its
    largest): finite, and what ``_prepare`` gives."""
    args = _kda_inputs(6, batch=1, seq=64,
                       decay=40.0 if case.startswith("decay") else 0.5)
    if case.startswith("beta"):
        near = 1e-6 if case == "beta_near_0" else 1.0 - 1e-6
        args = args[:4] + (jnp.full_like(args[4], near),)
    (got, got_grads), (want, want_grads) = _own_work_both_ways(args, 32)
    assert all(bool(jnp.isfinite(x).all()) for x in got + got_grads)
    # (At e^-40 a token what is left of a product is differences of
    # terms many times its size, in either program.)
    loose = 50 if case.startswith("decay") else 1
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-5 * loose
    for a, b in zip(got_grads, want_grads):
        assert _rel(a, b) < 2e-5 * loose


@pytest.mark.parametrize(
    "dtype,chunk,decay",
    [
        ("float32", 16, 0.5), ("float32", 32, 0.5), ("float32", 64, 0.5),
        ("bfloat16", 16, 0.5), ("bfloat16", 32, 0.5), ("bfloat16", 64, 0.5),
        ("float32", 64, 40.0),  # no float32 inverse of e^G holds
    ],
)
def test_the_forward_rule_writes_out_the_inverse(
    monkeypatch, dtype, chunk, decay
):
    """What ``_own_work``'s forward rule keeps for ``delta_chunk_bwd``:
    ``X = (I + Diag(beta) A)^-1`` and ``A``, float32 whatever the
    operands' dtype — the matrices the XLA ``_prepare`` hands its
    ``_unit_lower_inverse`` and gets back on the same chunks, the last
    chunk padded."""
    args = _kda_inputs(
        13, batch=1, seq=chunk + chunk // 2, dtype=jnp.dtype(dtype),
        decay=decay,
    )
    q, k, v, g, beta = _chunked(args, chunk)
    seen = {}
    block_products = kda_op._unit_lower_inverse

    def spy(lower):
        seen["lower"], seen["inv"] = lower, block_products(lower)
        return seen["inv"]

    monkeypatch.setattr(kda_op, "_unit_lower_inverse", spy)
    want = _prepare_blocks((q, k, v, g, beta), chunk)
    results, saved = kda_op._own_work_fwd(
        0.3, q, k, v, g, beta[:, :, None, :]
    )
    assert len(results) == 6 and len(saved) == 7
    # (At e^-40 a token what is left of a product is differences of
    # terms many times its size, in either program.)
    loose = 50 if decay > 1 else 1
    for a, b in zip(results, want):
        assert _rel(a, b) < (1e-5 * loose if dtype == "float32" else 4e-3)
    inv, a_full = saved[5:]
    shape = q.shape[:2] + (chunk, chunk)
    assert inv.dtype == a_full.dtype == jnp.float32
    assert inv.shape == a_full.shape == shape
    limit = 1e-5 if dtype == "float32" else 2e-3
    assert _rel(inv, seen["inv"].reshape(shape)) < limit
    lower = beta[..., None] * a_full
    assert _rel(lower, seen["lower"].reshape(shape)) < limit * loose
    # Unit lower triangular, and the inverse of what it is said to be.
    upper = np.triu(np.ones((chunk, chunk), bool), 1)
    assert not np.asarray(inv)[..., upper].any()
    assert (np.diagonal(inv, axis1=-2, axis2=-1) == 1.0).all()
    both = jnp.matmul(
        inv, jnp.eye(chunk) + lower, precision=jax.lax.Precision.HIGHEST
    )
    assert _rel(both, jnp.broadcast_to(jnp.eye(chunk), shape)) < 1e-5


def _pallas_calls(fn, *args):
    """The ``pallas_call`` equations of ``fn``'s jaxpr by kernel name,
    jitted functions and custom rules opened."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.setdefault(eqn.params["name"], []).append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("what", ["primal", "gradient"])
def test_only_the_forward_rule_writes_the_inverse_out(what):
    """The primal ``_own_work`` (a group's run in the forward pass) is
    one ``delta_chunk_fwd`` of six results: it writes no ``X``. A
    gradient's program holds the keeping forward (eight: ``X`` and
    ``A`` float32 [bh, chunks, C, C] beside the six) and a
    ``delta_chunk_bwd`` that takes both among its operands and
    nothing else of the forward's."""
    q, k, v, g, beta = _chunked(_kda_inputs(14, batch=1, seq=64), 32)
    operands = (q, k, v, g, beta[:, :, None, :])
    matrix = q.shape[:2] + (32, 32)

    def primal(*a):
        return kda_op._own_work(0.3, *a)

    def loss(*a):
        return sum(x.astype(jnp.float32).sum() for x in primal(*a))

    if what == "primal":
        calls = _pallas_calls(primal, *operands)
        assert set(calls) == {"delta_chunk_fwd"}
        (call,) = calls["delta_chunk_fwd"]
        assert len(call.outvars) == 6
        assert [x.aval.shape for x in call.outvars].count(matrix) == 1
        return
    calls = _pallas_calls(jax.grad(loss, tuple(range(5))), *operands)
    assert set(calls) == {"delta_chunk_fwd", "delta_chunk_bwd"}
    (forward,), (backward,) = calls["delta_chunk_fwd"], calls["delta_chunk_bwd"]
    kept = [x.aval for x in forward.outvars[6:]]
    assert len(forward.outvars) == 8
    assert [(x.shape, x.dtype) for x in kept] == [(matrix, jnp.float32)] * 2
    # The five operands, X and A, the six cotangents.
    assert len(backward.invars) == 13
    assert [x.aval for x in backward.invars[5:7]] == kept


def test_the_unrolled_walk_of_a_sub_block_is_the_loop(monkeypatch):
    """Compiled, the kernels walk a sub-block's tokens by unrolled
    code, a tile of rows at a time and past the tiles before the
    token (``_unrolled``: static rows and lanes, what
    ``tests/test_chip_compile.py`` lowers for the chip); interpreted,
    by a loop over all rows. One body, the same numbers."""
    args = _kda_inputs(9, batch=1, heads=1, seq=32)
    rolled, _ = _own_work_both_ways(args, 32, xla=False)
    monkeypatch.setattr(kda_op, "_unrolled", lambda: True)
    unrolled, _ = _own_work_both_ways(args, 32, xla=False)
    for a, b in zip(jax.tree.leaves(unrolled), jax.tree.leaves(rolled)):
        assert _rel(a, b) < 1e-6


def test_kda_runs_its_own_work_in_the_kernels(monkeypatch):
    """On the kernel path ``kda`` never calls the XLA ``_prepare``,
    and is still the recurrence token by token, forward and
    gradient; the ``kda.schedule`` event says what ran."""
    def refuse(*_):
        raise AssertionError("the XLA _prepare on the kernel path")

    monkeypatch.setattr(kda_op, "_prepare", refuse)
    args = _kda_inputs(7, seq=40)
    run = functools.partial(kda_op.kda, chunk=32)
    want = kda_op.kda_recurrent(*args)
    assert _rel(jax.jit(run)(*args), want) < 1e-5
    got = _grads(run, want.shape, args)
    ref = _grads(kda_op.kda_recurrent, want.shape, args)
    for a, b in zip(got, ref):
        assert _rel(a, b) < 2e-5
    attrs = [
        r for r in trace.snapshot_spans() if r["name"] == "kda.schedule"
    ][-1]["attrs"]
    assert attrs["own_work"] == "pallas:delta_chunk_fwd,delta_chunk_bwd"
    assert "delta_chunk_bwd" in attrs["backward"]


def test_kda_falls_back_where_the_kernels_do_not_fit(monkeypatch):
    """Widths that are not whole lane tiles on the chip: neither
    kernel pair is built; the scan and the XLA ``_prepare`` run."""
    def refuse(*_, **__):
        raise AssertionError("a kernel where the widths do not fit")

    monkeypatch.setattr(kda_op, "_use_interpret", lambda: False)
    assert not kda_op.kernel_fits(8, 8, 16)
    assert kda_op.kernel_fits(128, 128, 64)
    for name in ("_own_work", "_fwd_pallas", "_bwd_pallas"):
        monkeypatch.setattr(kda_op, name, refuse)
    args = _kda_inputs(8, seq=40)
    run = functools.partial(kda_op.kda, chunk=16)
    want = kda_op.kda_recurrent(*args)
    assert _rel(jax.jit(run)(*args), want) < 1e-5
    _grads(run, want.shape, args)
    attrs = [
        r for r in trace.snapshot_spans() if r["name"] == "kda.schedule"
    ][-1]["attrs"]
    assert (attrs["path"], attrs["own_work"]) == ("fallback", "xla")
    assert attrs["state_grid_steps"] == attrs["state_heads_a_step"] == 0


# ---- latent attention -------------------------------------------------


@pytest.mark.parametrize("seq,qk,v", [(256, 24, 16), (128, 192, 128)])
def test_flash_kernels_take_a_v_narrower_than_q(seq, qk, v):
    keys = jax.random.split(jax.random.key(0), 4)
    shape = (1, 2, seq)
    q = jax.random.normal(keys[0], shape + (qk,))
    k = jax.random.normal(keys[1], shape + (qk,))
    val = jax.random.normal(keys[2], shape + (v,))
    run = functools.partial(
        flash_attention, causal=True, scale=None, block_q=128, block_k=128
    )
    want = causal_attention(q, k, val)
    got = run(q, k, val)
    assert got.shape == shape + (v,)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    cotangent = jax.random.normal(keys[3], got.shape)
    grads = jax.grad(lambda *a: jnp.sum(run(*a) * cotangent), (0, 1, 2))(
        q, k, val
    )
    ref = jax.grad(
        lambda *a: jnp.sum(causal_attention(*a) * cotangent), (0, 1, 2)
    )(q, k, val)
    for a, b in zip(grads, ref):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def _mixer_case(monkeypatch, kind):
    config, sizes = _config_module(), _sizes()
    built = _built(monkeypatch, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    at = config.checked_mixers(sizes)[kind]
    layer = config.reference_weights(params, sizes)["layers"][at][kind]
    u = jax.random.normal(jax.random.key(7), (2, 64, 32))
    return config, sizes, built, params[f"layer_{at}"][kind], layer, u


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_mixer_equals_the_reference(monkeypatch, kind):
    """The system's mixer alone (kda: convolutions, norms, gates and
    the chunked rule through the kernels; mla: the latent projections
    and the flash kernels at 12 / 8) against the reference's, forward
    and the gradient of every leaf and of the input."""
    config, sizes, built, mixer_params, layer, u = _mixer_case(
        monkeypatch, kind
    )
    cfg = config.model_config(
        sizes, functools.partial(flash_attention, block_q=64, block_k=64)
    )
    module = {"kda": KDA, "mla": LatentAttention}[kind](cfg)
    got = module.apply({"params": mixer_params}, u, None)
    want = config.reference_mixer(kind, layer, u, sizes)
    token, rms = config.layer_error(got, want)
    assert float(token) < 1e-5 and float(rms) < 1e-5
    errors = config.mixer_grad_errors(
        kind,
        built["mixer_vjp"](kind, mixer_params, u, u),
        config.reference_mixer_vjp(kind, layer, u, u, sizes),
    )
    assert all(float(e) < 2e-5 for e in errors.values()), errors


@pytest.mark.parametrize("variant", ["bf16_state", "bf16_decay"])
def test_a_lower_precision_reference_differs(monkeypatch, variant):
    """What ``kimi_precision.py`` reads on the chip is not a no-op."""
    config, sizes, _, _, layer, u = _mixer_case(monkeypatch, "kda")
    want = config.reference_mixer("kda", layer, u, sizes)
    low = config.reference_mixer("kda", layer, u, sizes, variant)
    assert float(config.layer_error(low, want)[1]) > 1e-4


def test_mla_schedule_is_journalled(monkeypatch):
    config, sizes, _, mixer_params, _, u = _mixer_case(monkeypatch, "mla")
    before = len(
        [r for r in trace.snapshot_spans() if r["name"] == "mla.schedule"]
    )
    LatentAttention(config.model_config(sizes)).apply(
        {"params": mixer_params}, u, None
    )
    events = [
        r for r in trace.snapshot_spans() if r["name"] == "mla.schedule"
    ]
    assert len(events) == before + 1
    attrs = events[-1]["attrs"]
    assert (attrs["qk_width"], attrs["v_width"], attrs["latent_rank"]) == (
        12, 8, 12
    )
    assert attrs["positions"] == "none"


@pytest.mark.parametrize(
    "seq,want",
    [(1024, 32), (8192, 8), (16384, 4), (32768, 2)],
)
def test_heads_a_call_follow_the_flash_schedule(seq, want):
    """32 heads of q/k 192 and v 128 in bf16: all in one call while a
    head's K and V stay in VMEM; past that a call's float32 dQ
    partials (one a key chunk) are held to the bytes of q — four heads
    at the cell's 16 384 keys, the schedule the chip runs measured."""
    import importlib

    flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")
    assert flash_mod.heads_a_call(32, seq, 192, 128, 2) == want
    made = flash_mod.make_flash_attention(block_q=128, block_k=128)
    assert made.heads_a_call(32, seq, 192, 128, 2) == want


@pytest.mark.parametrize("made", [True, False])
def test_any_attention_fn_is_asked_how_many_heads_a_call(monkeypatch, made):
    """A ``functools.partial`` of the kernel (what the cells' builders
    pass) says nothing of itself and is asked through the module's
    rule all the same: with all heads in one call the cell's step is
    refused by the chip's compiler (PR 46)."""
    import importlib

    flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")
    config, sizes, _, mixer_params, _, u = _mixer_case(monkeypatch, "mla")
    # K and V of a head (12 + 8 wide, float32, double-buffered) past
    # the budget at 16 keys: four key chunks a row of 64.
    monkeypatch.setattr(flash_mod, "_KV_VMEM_BUDGET", 2 * (12 + 8) * 4 * 16)
    monkeypatch.setattr(flash_mod, "_TILE_ROWS", 16)
    attn = (
        flash_mod.make_flash_attention(block_q=16, block_k=16) if made
        else functools.partial(flash_attention, block_q=16, block_k=16)
    )
    got = LatentAttention(config.model_config(sizes, attn)).apply(
        {"params": mixer_params}, u, None
    )
    attrs = [
        r for r in trace.snapshot_spans() if r["name"] == "mla.schedule"
    ][-1]["attrs"]
    assert (attrs["heads"], attrs["heads_a_call"]) == (2, 1)
    want = LatentAttention(config.model_config(sizes)).apply(
        {"params": mixer_params}, u, None
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# ---- the shared expert and the share ----------------------------------


def test_shared_expert_is_added_unweighted(monkeypatch):
    config, sizes = _config_module(), _sizes()
    built = _built(monkeypatch, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    moe = params["layer_1"]["moe"]
    layer = config.routed_weights(moe)
    x = jax.random.normal(jax.random.key(5), (96, 32))
    cfg = config.model_config(sizes)
    y, sown = RoutedFFN(cfg).apply(
        {"params": moe}, x, mutable=["moe_load", "moe_routing"]
    )
    with jax.default_matmul_precision("highest"):
        want, _ = config.reference_routed_ffn(layer, x, sizes)
        routed_only, _ = config.reference_routed_ffn(
            layer, x, sizes, shared=False
        )
        shared = config._gated(x, layer["s1"], layer["s3"], layer["s2"])
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(routed_only + shared, want, rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(shared).max()) > 0.1
    assert int(sown["moe_load"]["shared_rows"][0]) == 96
    no_shared = RoutedFFN(
        config.model_config(_sizes(num_shared_experts=0))
    ).apply(
        {"params": {k: v for k, v in moe.items() if k != "shared"}}, x,
        mutable=["moe_load", "moe_routing"],
    )[1]
    assert "shared_rows" not in no_shared["moe_load"]


@pytest.mark.parametrize(
    "cell,shape,bound,planned",
    [
        # (tokens a micro-batch, top_k, held, total) at the cells' REAL
        # sizes; the bounds are what the commit before PR 46 gives
        # there (its ``rows_capacity`` where it walked the whole plan).
        ("lfm2-8b-a1b-steady", (16384, 4, 8, 32), 45056, 69632),
        ("keye-vl-2.0-30b-a3b-steady", (16384, 8, 16, 128), 139264, 139264),
        ("kimi-linear-48b-a3b-steady", (16384, 8, 8, 256), 14336, 143360),
        # PR 49: 320 rows an even router sends a group are under three
        # quarters of a tile of 512, so the tile is 256 (in tiles of
        # 512 the bound was 41 984 rows).
        ("qwen3-next-80b-a3b-steady", (16384, 10, 32, 512), 33792, 202752),
    ],
)
def test_the_row_bounds_of_the_routed_cells_at_their_real_sizes(
    cell, shape, bound, planned
):
    """The piece-walk's threshold moves neither routed cell the
    benchmark had: lfm2's two passes and keye's one are the rows of
    before; only a plan of four bounds or more is cut in pieces. Nor
    does the tile that follows the rows a group (PR 49) move them."""
    from adaptdl_tpu.models import moe
    from adaptdl_tpu.ops import grouped_matmul as gmm

    tokens, top_k, held, total = shape
    tile = gmm.tile_rows(tokens * min(top_k, held), tokens * top_k / total)
    assert tile == (256 if cell.startswith("qwen3") else 512)
    assert tile == gmm.tile_rows(tokens * min(top_k, held)) or tile == 256
    assert moe.rows_bound(tokens, top_k, held, total, tile) == bound
    assert moe.rows_planned(tokens, top_k, held, total, tile) == planned


@pytest.mark.parametrize("boost", [0.0, 50.0])
def test_a_plan_many_times_its_bound_is_walked_in_pieces(boost):
    """2 of 256 experts held, top 4 of 8192 tokens: the worst case is
    many bounds long, so the plan is pieces of the bound and the usual
    step walks one; a router that sends every token to the held
    experts walks as many as hold its rows, drops nothing, and gives
    the same layer."""
    from adaptdl_tpu.models import moe
    from adaptdl_tpu.ops import grouped_matmul as gmm

    tokens, d, f, total, held, top_k = 8192, 16, 8, 256, 2, 4
    tile = gmm.tile_rows(tokens * min(top_k, held), tokens * top_k / total)
    capacity = moe.rows_capacity(tokens, top_k, held, tile)
    bound = moe.rows_bound(tokens, top_k, held, total, tile)
    planned = moe.rows_planned(tokens, top_k, held, total, tile)
    assert capacity >= moe.ROWS_PIECES_FROM * bound
    assert planned % bound == 0 and capacity <= planned < capacity + bound
    keys = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(keys[0], (tokens, d))
    router = jax.random.normal(keys[1], (d, total))
    w_gate = jax.random.normal(keys[2], (held, d, f))
    w_up = jax.random.normal(keys[3], (held, d, f))
    w_down = jax.random.normal(keys[4], (held, f, d))
    bias = jnp.zeros((total,)).at[:held].set(boost)

    def layer(x, w_gate, w_up, w_down):
        return moe.routed_experts(
            x, router, bias, w_gate, w_up, w_down, experts_total=total,
            first_expert=0, top_k=top_k,
        )

    y, load = layer(x, w_gate, w_up, w_down)
    experts, weights = load["experts"], load["weights"]

    def plain(x, w_gate, w_up, w_down):
        y = jnp.zeros_like(x)
        for e in range(held):
            weight = jnp.where(experts == e, weights, 0).sum(-1, keepdims=True)
            y = y + weight * (
                (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
            )
        return y

    np.testing.assert_allclose(
        y, plain(x, w_gate, w_up, w_down), rtol=1e-4, atol=1e-4
    )
    assert int(load["dropped"]) == 0
    pieces = -(-int(load["rows_active"]) // bound)
    assert int(load["rows_walked"]) == max(pieces, 1) * bound
    # (Every token on both held experts: tokens x held rows; 8 pieces
    # in tiles of 512, 19 since PR 49 gave this shape's 128 rows a
    # group tiles of 128 and a bound of 896.)
    assert pieces == (1 if boost == 0 else -(-tokens * held // bound))
    assert int(load["fell_back"]) == (boost > 0)
    grads = jax.grad(
        lambda *a: jnp.sum(layer(*a)[0] ** 2), (0, 1, 2, 3)
    )(x, w_gate, w_up, w_down)
    want = jax.grad(
        lambda *a: jnp.sum(plain(*a) ** 2), (0, 1, 2, 3)
    )(x, w_gate, w_up, w_down)
    # (The input's gradient also passes through the router's weights,
    # which ``plain`` holds fixed: the experts' leaves are compared.)
    for a, b in zip(grads[1:], want[1:]):
        assert _rel(a, b) < 1e-4


def test_the_shares_add_up_to_the_whole_layer(monkeypatch):
    """A 32-expert layer cut into 4 shares of 8: what the four chips
    compute of the routed result, with the shared expert (which every
    chip computes alike) counted ONCE, adds up to the uncut
    reference's layer."""
    config = _config_module()
    sizes = _sizes(router_width=32, experts_held=8, num_experts=8,
                   num_experts_per_token=4, num_experts_per_tok=4)
    keys = jax.random.split(jax.random.key(11), 9)
    d, f = 32, 16
    whole = {
        "router": 0.5 * jax.random.normal(keys[0], (d, 32)),
        "bias": 0.1 * jax.random.normal(keys[1], (32,)),
        "w1": jax.random.normal(keys[2], (32, d, f)) / d**0.5,
        "w3": jax.random.normal(keys[3], (32, d, f)) / d**0.5,
        "w2": jax.random.normal(keys[4], (32, f, d)) / f**0.5,
        "s1": jax.random.normal(keys[5], (d, f)) / d**0.5,
        "s3": jax.random.normal(keys[6], (d, f)) / d**0.5,
        "s2": jax.random.normal(keys[7], (f, d)) / f**0.5,
    }
    x = jax.random.normal(keys[8], (64, d))
    with jax.default_matmul_precision("highest"):
        want, counts = config.reference_routed_ffn(
            whole, x, {**sizes, "first_expert": 0}
        )
    assert int(counts.sum()) == 64 * 4
    total = jnp.zeros_like(x)
    for share in range(4):
        first = 8 * share
        cfg = config.model_config({**sizes, "first_expert": first})
        held = slice(first, first + 8)
        y, sown = RoutedFFN(cfg).apply(
            {"params": {
                "router": whole["router"], "expert_bias": whole["bias"],
                "w_gate": whole["w1"][held], "w_up": whole["w3"][held],
                "w_down": whole["w2"][held],
                "shared": {
                    "ff_gate": {"kernel": whole["s1"]},
                    "ff_up": {"kernel": whole["s3"]},
                    "ff_down": {"kernel": whole["s2"]},
                },
            }},
            x, mutable=["moe_load", "moe_routing"],
        )
        np.testing.assert_array_equal(
            sown["moe_load"]["held_rows"][0], counts[held]
        )
        total = total + y
    with jax.default_matmul_precision("highest"):
        shared = config._gated(x, whole["s1"], whole["s3"], whole["s2"])
    np.testing.assert_allclose(
        total - 3 * shared, want, rtol=2e-5, atol=2e-5
    )


# ---- the whole model ---------------------------------------------------


def test_loss_and_gradients_equal_the_reference(monkeypatch):
    """Five layers of the cell's pattern (kda + dense FFN, kda, kda,
    mla, kda; four routed with a shared expert), remat on, the flash
    kernels, the delta rule's kernels, a share of 4 of 16 experts, the
    untied head."""
    config, sizes = _config_module(), _sizes()
    built = _built(monkeypatch, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    data = config.make_dataset(sizes, 5, 4)
    batch = {k: jnp.asarray(v[:2]) for k, v in data.items()}

    def system(params):
        return built["loss_fn"](params, batch, jax.random.key(0))[0]

    def reference(params):
        return config.reference_loss(
            config.reference_weights(params, sizes),
            batch["inputs"], batch["targets"], sizes,
        )[0]

    loss, grads = jax.value_and_grad(system)(params)
    want, want_grads = jax.value_and_grad(reference)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        if "expert_bias" in jax.tree_util.keystr(path):
            continue  # a buffer: no gradient reaches it on either side
        scale = max(float(jnp.abs(ref).max()), 1e-6)
        assert float(jnp.abs(got - ref).max()) / scale < 5e-4, (
            jax.tree_util.keystr(path)
        )
    report = config.reference_check(built, params, data, sizes)
    assert report["ok"], report


def _loader_stub(atomic, accum):
    class Loader:
        current_atomic_bsz = atomic
        current_accum_steps = accum

    return Loader()


def test_run_step_save_restore_round_trip(tmp_path, monkeypatch):
    """One ``ElasticTrainer.run_step`` of the tiny model, ``moe.load``
    journalled with ``shared_rows``, a save through ``checkpoint.py``,
    and a restore into a fresh trainer that steps on bit-equal."""
    from adaptdl_tpu import checkpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    config, sizes = _config_module(), _sizes()
    data = config.make_dataset(sizes, 5, 8)
    batch = {k: v[:4] for k, v in data.items()}
    built = _built(monkeypatch, sizes)
    trainer = built["trainer"]
    holder = {"state": trainer.init_state()}
    ck = trainer.make_checkpoint_state(
        lambda: holder["state"], lambda s: holder.__setitem__("state", s)
    )
    trainer._calibrated.add(2)
    holder["state"], metrics = trainer.run_step(
        holder["state"], batch, _loader_stub(2, 1)
    )
    assert np.isfinite(float(metrics["loss"]))
    load = metrics["counters"]["moe.load"]
    np.testing.assert_array_equal(load["shared_rows"], [4 * 64] * 4)
    attrs = [
        r for r in trace.snapshot_spans() if r["name"] == "moe.load"
    ][-1]["attrs"]
    assert attrs["shared_rows"] == [256] * 4
    checkpoint.save_all_states()
    saved = jax.tree.map(np.asarray, trainer.params_tree(holder["state"]))
    holder["state"], after = trainer.run_step(
        holder["state"], batch, _loader_stub(2, 1)
    )
    ck.unregister()

    again = _built(monkeypatch, sizes, seed=11)["trainer"]
    holder2 = {"state": again.init_state()}
    ck2 = again.make_checkpoint_state(
        lambda: holder2["state"], lambda s: holder2.__setitem__("state", s)
    )
    assert checkpoint.load_state(ck2)
    for a, b in zip(
        jax.tree.leaves(saved),
        jax.tree.leaves(again.params_tree(holder2["state"])),
    ):
        np.testing.assert_array_equal(a, np.asarray(b))
    again._calibrated.add(2)
    holder2["state"], resumed = again.run_step(
        holder2["state"], batch, _loader_stub(2, 1)
    )
    assert float(resumed["loss"]) == float(after["loss"])
    for a, b in zip(
        jax.tree.leaves(trainer.params_tree(holder["state"])),
        jax.tree.leaves(again.params_tree(holder2["state"])),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ck2.unregister()


# ---- what a config may not ask for --------------------------------------


_BASE = dict(
    vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
    dtype=jnp.float32, head_dim=8,
)


@pytest.mark.parametrize(
    "options,field",
    [
        (dict(layer_types=("kda",), kda_gate_rank=4, rope=False,
              seq_axis="seq"), "seq_axis"),
        (dict(layer_types=("kda",), rope=False), "kda_gate_rank"),
        (dict(layer_types=("mla",), kv_lora_rank=8, qk_nope_head_dim=8,
              v_head_dim=8), "rope"),
        (dict(layer_types=("mla",), rope=False, qk_nope_head_dim=8,
              v_head_dim=8), "kv_lora_rank"),
        (dict(d_shared_expert=16), "d_shared_expert"),
        (dict(layer_types=("sparse_attention",), rope=False), "rope"),
    ],
)
def test_config_refuses_at_build_with_the_fields_name(options, field):
    with pytest.raises(ValueError, match=field):
        TransformerConfig(**_BASE, **options)


def test_a_remat_block_keeps_the_rules_output_by_name(monkeypatch):
    """``block_remat`` of a model with kda layers saves ``kda_out``
    beside the flash kernel's names; a model without them does not."""
    from adaptdl_tpu.models import transformer

    def saved(config):
        before = len(trace.snapshot_spans())
        transformer.block_remat(config, (2, 64))
        events = [
            r for r in trace.snapshot_spans()[before:]
            if r["name"] == "remat.policy"
        ]
        return events[-1]["attrs"]["saved_names"].split(",")

    config = _config_module().model_config(_sizes())
    assert saved(config) == ["flash_out", "flash_lse", "kda_out"]
    plain = TransformerConfig(**_BASE, layer_types=("full_attention",))
    assert saved(plain) == ["flash_out", "flash_lse"]


# ---- the configurations of before --------------------------------------


@functools.cache
def _digests_now():
    import step_digests

    return step_digests.digests()


with open(os.path.join(ROOT, "tests", "data", "step_digests.json")) as _f:
    _DIGESTS = json.load(_f)


@pytest.mark.parametrize("case", sorted(_DIGESTS))
def test_the_configurations_of_before_are_untouched(case):
    """Parameter tree and lowered gradient program of each of the four
    configurations the benchmark had, at its tiny size, against what
    the parent commit gives (``tests/step_digests.py``): no AOT-cache
    key and no ``trace_lower_s`` of an accepted cell moves."""
    import sys

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    assert _digests_now()[case] == _DIGESTS[case]
