"""The gated delta rule of ``adaptdl_tpu/ops/kda.py`` (PRs 46-48, which
the kimi-linear-48b-a3b configuration forced) at small sizes: the
chunked rule and its Pallas kernels against the recurrence token by
token, what a grid step of the state kernels holds, the chunks' own work
as a kernel pair and its forward rule, and the fallbacks. (The mixers,
the routed share and the whole model: ``tests/test_kimi_linear.py``;
the rule with one decay a head: ``tests/test_qwen3_next_rule.py``,
``tests/test_delta_chunk_head.py``.)"""

import functools

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from configurations import rel

from adaptdl_tpu import trace
from adaptdl_tpu.ops import kda as kda_op


@pytest.fixture(autouse=True)
def _rows_of_several_chunks():
    """The rule's chunk is a constant of ``ops/kda.py`` (64); these
    tests run under the kimi-linear-48b-a3b configuration's tiny one,
    as its model's tests do."""
    with configurations.rows_of_several_chunks("kimi-linear-48b-a3b"):
        yield


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
    assert rel(jax.jit(run)(*args), want) < 1e-5
    got = _grads(run, want.shape, args)
    ref = _grads(kda_op.kda_recurrent, want.shape, args)
    for a, b in zip(got, ref):
        assert rel(a, b) < 2e-5


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
    assert rel(outs[True], outs[False]) < 1e-2
    assert rel(outs[True], want) < 3e-2
    for a, b in zip(grads[True], grads[False]):
        assert rel(a, b) < 2e-2
    ref = _grads(kda_op.kda_recurrent, want.shape, args)
    for a, b in zip(grads[True], ref):
        assert rel(a, b) < 6e-2


def test_kda_survives_a_decay_no_float32_inverse_holds():
    """A decay of e^-40 a token: ``e^{-G}`` of a chunk would overflow
    float32; no exponent here is positive."""
    args = _kda_inputs(2, seq=64, decay=40.0)
    run = functools.partial(kda_op.kda, chunk=64)
    got = jax.jit(run)(*args)
    assert bool(jnp.isfinite(got).all())
    assert rel(got, kda_op.kda_recurrent(*args)) < 1e-5
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
    assert rel(short, kda_op.kda_recurrent(*_kda_inputs(4, seq=12))) < 1e-5
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
    assert rel(blocked[0], out) < 1e-2 and rel(blocked[1], states) < 1e-2
    for got, want in zip(blocked[2:], grads):
        assert rel(got, want) < 2e-2


def test_kda_in_blocks_on_a_padded_row_is_the_recurrence(monkeypatch):
    """``kda`` end to end on a row whose last chunk is padded, the
    state kernels in blocks of four heads and four (backward: two)
    chunks: the recurrence token by token, forward and gradient."""
    monkeypatch.setattr(kda_op, "_BLOCKS_SHARE", 1 / 16)
    args = _kda_inputs(12, batch=1, seq=120, heads=4)
    run = functools.partial(kda_op.kda, chunk=16)
    want = kda_op.kda_recurrent(*args)
    assert rel(jax.jit(run)(*args), want) < 1e-5
    got = _grads(run, want.shape, args)
    for a, b in zip(got, _grads(kda_op.kda_recurrent, want.shape, args)):
        assert rel(a, b) < 2e-5
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
        assert rel(a, b) < (1e-5 if dtype == "float32" else 4e-3)
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert rel(a, b) < limit


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
        assert rel(a, b) < 1e-5 * loose
    for a, b in zip(got_grads, want_grads):
        assert rel(a, b) < 2e-5 * loose


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
        assert rel(a, b) < (1e-5 * loose if dtype == "float32" else 4e-3)
    inv, a_full = saved[5:]
    shape = q.shape[:2] + (chunk, chunk)
    assert inv.dtype == a_full.dtype == jnp.float32
    assert inv.shape == a_full.shape == shape
    limit = 1e-5 if dtype == "float32" else 2e-3
    assert rel(inv, seen["inv"].reshape(shape)) < limit
    lower = beta[..., None] * a_full
    assert rel(lower, seen["lower"].reshape(shape)) < limit * loose
    # Unit lower triangular, and the inverse of what it is said to be.
    upper = np.triu(np.ones((chunk, chunk), bool), 1)
    assert not np.asarray(inv)[..., upper].any()
    assert (np.diagonal(inv, axis1=-2, axis2=-1) == 1.0).all()
    both = jnp.matmul(
        inv, jnp.eye(chunk) + lower, precision=jax.lax.Precision.HIGHEST
    )
    assert rel(both, jnp.broadcast_to(jnp.eye(chunk), shape)) < 1e-5


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
        assert rel(a, b) < 1e-6


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
    assert rel(jax.jit(run)(*args), want) < 1e-5
    got = _grads(run, want.shape, args)
    ref = _grads(kda_op.kda_recurrent, want.shape, args)
    for a, b in zip(got, ref):
        assert rel(a, b) < 2e-5
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
    assert rel(jax.jit(run)(*args), want) < 1e-5
    _grads(run, want.shape, args)
    attrs = [
        r for r in trace.snapshot_spans() if r["name"] == "kda.schedule"
    ][-1]["attrs"]
    assert (attrs["path"], attrs["own_work"]) == ("fallback", "xla")
    assert attrs["state_grid_steps"] == attrs["state_heads_a_step"] == 0
