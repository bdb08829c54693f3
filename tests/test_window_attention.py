"""A lower bound on the keys in both flash kernels (PR 54): the band
schedule of ``flash_attention(..., window=)`` in interpret mode against
a masked plain softmax, forward and gradients, across windows shorter
than, equal to and longer than a tile, windows that are no multiple of
the update's piece, windows that reach the row's start (today's
program), groups of 6 and 8 query heads a key/value head — repeated on
the way in, and (PR 55) handed over once a kv head and indexed inside
the kernels —, float32 and bfloat16; and the schedule's static counts
against brute force."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adaptdl_tpu import trace

# ``import adaptdl_tpu.ops.flash_attention as m`` yields the FUNCTION
# the package re-exports under the module's name.
fm = importlib.import_module("adaptdl_tpu.ops.flash_attention")


def _plain(q, k, v, window):
    """Masked softmax in float32: ``j <= i`` and ``i - j < window``."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    i = jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(q.shape[2])[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _operands(shape, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return tuple(jax.random.normal(k, shape, dtype) for k in keys)


def _both(fn, q, k, v, g):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out, *vjp(g.astype(out.dtype)))


@pytest.fixture
def tiles(monkeypatch):
    """Set the band schedule's tile and piece (constants of the
    module, chosen on the chip for rows of 16 384) to sizes a CPU test
    can walk several of."""
    def set_(tile, piece):
        monkeypatch.setattr(fm, "_WINDOW_TILE", tile)
        monkeypatch.setattr(fm, "_WINDOW_PIECE", piece)
        monkeypatch.setattr(fm, "_WINDOW_PIECE_BWD", piece)

    return set_


# (tile, piece, seq, block): tiles of 32 are walked whole (no lane-
# aligned piece divides them); tiles of 256 in pieces of 128.
SMALL = (32, 32, 128, 16)
PIECES = (256, 128, 1024, 128)


@pytest.mark.parametrize(
    "shape, window",
    [
        (SMALL, 5),  # shorter than a tile
        (SMALL, 32),  # a tile
        (SMALL, 33),  # a tile and one key: three blocks a query tile
        (SMALL, 70),  # longer than two tiles
        (SMALL, 127),  # all but the first key of the row's last query
        (PIECES, 100),  # shorter than a piece
        (PIECES, 128),  # a piece
        (PIECES, 200),  # no multiple of the piece: two edge blocks
        (PIECES, 256),  # a tile
        (PIECES, 300),  # longer than a tile, no multiple of the piece
    ],
)
def test_band_kernels_equal_the_masked_softmax(tiles, shape, window):
    tile, piece, seq, block = shape
    tiles(tile, piece)
    q, k, v, g = _operands((1, 2, seq, 16), jnp.float32)
    got = _both(
        lambda q, k, v: fm.flash_attention(
            q, k, v, True, None, block, block, window
        ),
        q, k, v, g,
    )
    want = _both(lambda q, k, v: _plain(q, k, v, window), q, k, v, g)
    sched = fm._band_schedule(seq, window, block, block, piece)
    assert sched.tile == tile and sched.before == -(-(window - 1) // tile)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 128, 500])
def test_a_window_that_reaches_the_rows_start_is_todays_program(window):
    """``window >= seq_len`` and None take the schedule of before: the
    same lowered text, and no band kernel in it."""
    q, k, v, _ = _operands((1, 2, 128, 16), jnp.float32)

    def text(*window_arg):
        fn = lambda q, k, v: fm.flash_attention(  # noqa: E731
            q, k, v, True, None, 32, 32, *window_arg
        ).sum()
        return jax.jit(jax.grad(fn, (0, 1, 2))).lower(q, k, v).as_text()

    before = len(trace.snapshot_spans())
    assert text(window) == text()
    names = [r["name"] for r in trace.snapshot_spans()[before:]]
    assert "window.keys" not in names and "flash.schedule" in names


@pytest.mark.parametrize("repeated", [True, False])
@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_groups_of_query_heads_on_repeated_kv_heads(
    tiles, group, dtype, repeated
):
    """Laguna's groups (6 on a full layer, 8 on a sliding one): each
    kv head repeated for its group on the way in, as the model handed
    them over before PR 55, dK and dV summed over the group by
    autodiff; and handed over ONCE a kv head (``repeated`` False), the
    group's heads indexed and dK / dV summed inside the kernels."""
    tiles(32, 32)
    dtype = jnp.dtype(dtype)
    seq, window = 96, 40
    keys = jax.random.split(jax.random.key(group), 4)
    q = jax.random.normal(keys[0], (1, 2 * group, seq, 16), dtype)
    k, v = (jax.random.normal(x, (1, 2, seq, 16), dtype) for x in keys[1:3])
    g = jax.random.normal(keys[3], q.shape, dtype)

    def attend(fn):
        def run(q, k, v):
            return fn(
                q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
            )

        return run

    def flash(q, k, v):
        return fm.flash_attention(q, k, v, True, None, 16, 16, window)

    got = _both(attend(flash) if repeated else flash, q, k, v, g)
    want = _both(attend(lambda q, k, v: _plain(q, k, v, window)), q, k, v, g)
    assert got[0].dtype == dtype and got[2].shape == k.shape
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32), rtol=tol, atol=tol
        )


@pytest.mark.parametrize(
    "window, group, dtype",
    # Three K/V blocks before a tile's own (a ring of four), and one.
    [(70, n, t) for n in (1, 2, 4, 8) for t in ("float32", "bfloat16")]
    + [(5, 4, "float32")],
)
def test_kv_heads_are_indexed_inside_the_band_kernels(
    tiles, window, group, dtype
):
    """k and v ``kv_heads`` wide against the same kernels on repeated
    operands (float32: to 1e-5): a group's query heads pass under one
    tile's K/V blocks, and the backward's ring gathers all of them
    before a block's dK / dV are written, rounded once."""
    tiles(32, 32)
    dtype = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.key(window + group), 4)
    q = jax.random.normal(keys[0], (2, 2 * group, 128, 16), dtype)
    k, v = (jax.random.normal(x, (2, 2, 128, 16), dtype) for x in keys[1:3])
    g = jax.random.normal(keys[3], q.shape, dtype)

    def flash(q, k, v):
        return fm.flash_attention(q, k, v, True, None, 16, 16, window)

    before = len(trace.snapshot_spans())
    got = _both(flash, q, k, v, g)
    events = {}
    for rec in trace.snapshot_spans()[before:]:
        events.setdefault(rec["name"], rec["attrs"])
    want = _both(
        lambda q, k, v: flash(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        ),
        q, k, v, g,
    )
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == dtype
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32), rtol=tol, atol=tol
        )
    blocks_before = -(-(window - 1) // 32)
    for name, steps in (
        ("flash.schedule", 4), ("flash.schedule_bwd", 4 + blocks_before)
    ):
        attrs = events[name]
        assert (attrs["kv_group"], attrs["kv_heads"]) == (group, 4)
        assert attrs["grid_steps"] == 4 * group * steps
    # batch x QUERY heads, whatever the kv heads: what the reader of
    # ``window_keys_visited_over_window`` weighs by.
    assert events["window.keys"]["batch_heads"] == 4 * group


@pytest.mark.parametrize(
    "before, group",
    # smallthinker-21b-a3b's walk (PR 60): a window of 4096 keys at
    # tiles of 1024 is FOUR K/V blocks before the tile's own (a ring
    # of five) under groups of SEVEN query heads; every cell before it
    # had one block before and groups of 6 or 8.
    [(before, group) for before in (2, 4) for group in (1, 4, 7)],
)
def test_band_kernels_past_one_block_of_reach(tiles, before, group):
    """The band kernels at ``before`` K/V blocks ahead of a tile's
    own, k and v handed over once a kv head for ``group`` query heads,
    against plain ``causal_attention(window=)`` on repeated operands:
    forward and dq / dk / dv, dK / dV of a block gathered in the ring
    over ``before + 1`` tiles and the group's heads."""
    from adaptdl_tpu.models.transformer import causal_attention

    tiles(32, 32)
    window = 32 * before  # the last key of the block ``before`` back
    keys = jax.random.split(jax.random.key(10 * before + group), 4)
    q = jax.random.normal(keys[0], (1, 2 * group, 256, 16))
    k, v = (jax.random.normal(x, (1, 2, 256, 16)) for x in keys[1:3])
    g = jax.random.normal(keys[3], q.shape)
    sched = fm._band_schedule(256, window, 16, 16, 32)
    assert (sched.tile, sched.before) == (32, before)
    got = _both(
        lambda q, k, v: fm.flash_attention(
            q, k, v, True, None, 16, 16, window
        ),
        q, k, v, g,
    )
    want = _both(
        lambda q, k, v: causal_attention(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
            window=window,
        ),
        q, k, v, g,
    )
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "heads, kv_heads", [(28, 4), (48, 8), (16, 2), (64, 8)]
)
@pytest.mark.parametrize("seq, chunks", [(8192, 1), (16384, 2), (32768, 4)])
def test_a_run_of_heads_is_whole_groups_or_a_divisor_of_one(
    heads, kv_heads, seq, chunks
):
    """``heads_a_call`` under ``group`` query heads a kv head gives a
    divisor of the heads that is whole groups or divides one, whatever
    the K-blocked backward's chunks (a group of 7 at four chunks: 1,
    where the bytes alone would say 2, which straddles two groups)."""
    group = heads // kv_heads
    sched = fm._schedule(
        seq, 128, 2, 128, 128, diag_rows=fm._BWD_DIAG_ROWS, v_dim=128
    )
    assert seq // sched.chunk_k == chunks
    run = fm.heads_a_call(heads, seq, 128, 128, 2, group=group)
    assert heads % run == 0 and (run % group == 0 or group % run == 0)
    free = fm.heads_a_call(heads, seq, 128, 128, 2)
    assert run <= free
    if free % group == 0 or group % free == 0:
        assert run == free  # the rule moves no run that was whole
    if chunks == 1:
        assert run == heads
    if (heads, chunks) == (28, 2):
        assert run == 7  # the cell's full layer: one kv head's group
    if (heads, chunks) == (28, 4):
        assert (free, run) == (2, 1)


def _brute(seq, piece, window):
    i = np.arange(seq)[:, None]
    j = np.arange(seq)[None, :]
    seen = (j <= i) & (i - j < window)
    blocks = seen.reshape(seq // piece, piece, seq // piece, piece)
    return int(blocks.any(axis=(1, 3)).sum()), int(seen.sum())


@pytest.mark.parametrize(
    "tile, piece, seq, window",
    [
        (32, 32, 128, 5), (32, 32, 128, 32), (32, 32, 128, 33),
        (32, 32, 128, 100), (256, 128, 1024, 100), (256, 128, 1024, 128),
        (256, 128, 1024, 200), (256, 128, 1024, 513),
        (512, 256, 4096, 512), (512, 128, 4096, 512),
        (512, 512, 4096, 512), (1024, 256, 4096, 512),
    ],
)
def test_tiles_visited_against_a_count_by_brute_force(
    tile, piece, seq, window
):
    """The blocks the band's walk computes are exactly those that hold
    a pair of the band, and the pairs are ``sum_i min(i + 1, W)``."""
    sched = fm._BandSchedule(tile, piece, -(-(window - 1) // tile))
    visited, in_band = fm._band_tiles(sched, seq, window)
    blocks, pairs = _brute(seq, piece, window)
    assert visited == in_band == blocks
    assert fm.keys_in_window(seq, window) == pairs
    # Every update's queries see at least one of its keys, every
    # visible pair is in exactly one update: the walk is the band.
    covered = np.zeros((tile, (sched.before + 1) * tile), bool)
    for block, at, first, stop in fm._band_updates(sched, window):
        keys = slice((sched.before - block) * tile + at,
                     (sched.before - block) * tile + at + piece)
        assert not covered[first:stop, keys].any()
        covered[first:stop, keys] = True
    i = np.arange(tile)[:, None]
    j = np.arange(-sched.before * tile, tile)[None, :]
    assert covered[(j <= i) & (i - j < window)].all()


def test_the_schedule_is_journalled(tiles):
    tiles(32, 32)
    q, k, v, g = _operands((2, 3, 128, 16), jnp.float32)
    before = len(trace.snapshot_spans())
    _both(
        lambda q, k, v: fm.flash_attention(q, k, v, True, None, 16, 16, 40),
        q, k, v, g,
    )
    events = {}
    for rec in trace.snapshot_spans()[before:]:
        events.setdefault(rec["name"], rec["attrs"])
    blocks, pairs = _brute(128, 32, 40)
    for name in ("flash.schedule", "flash.schedule_bwd"):
        attrs = events[name]
        assert (attrs["window"], attrs["tile"], attrs["diag_tile"]) == (
            40, 32, 32
        )
        assert attrs["tiles_visited"] == attrs["tiles_in_band"] == blocks
        assert attrs["kv_blocks"] == 3 and not attrs["kv_resident"]
        assert (attrs["kv_group"], attrs["kv_heads"]) == (1, 6)
    assert events["flash.schedule"]["grid_steps"] == 6 * 4
    assert events["flash.schedule_bwd"]["grid_steps"] == 6 * (4 + 2)
    keys = events["window.keys"]
    assert keys["keys_in_window"] == pairs and keys["batch_heads"] == 6
    assert keys["keys_visited"] == blocks * 32 * 32
    assert keys["keys_visited_fwd"] == keys["keys_visited_bwd"]


def test_heads_a_call_under_a_window():
    """No chunk and no partial under a window, and (PR 55) nothing
    repeated for a call: all the heads, whatever the row. (Until PR 55:
    as many as kept one operand within 64 MiB, 16 at 16 384 keys of 128
    in bfloat16, because the caller repeated k and v for a call.)"""
    assert fm.heads_a_call(64, 16384, 128, 128, 2, window=512) == 64
    assert fm.heads_a_call(48, 16384, 128, 128, 2, window=512) == 48
    assert fm.heads_a_call(6, 16384, 128, 128, 2, window=512) == 6
    assert fm.heads_a_call(64, 4096, 128, 128, 2, window=512) == 64
    # A window that reaches the row's start is the full schedule's
    # answer: the K-blocked backward's partials.
    assert fm.heads_a_call(48, 16384, 128, 128, 2, window=16384) == (
        fm.heads_a_call(48, 16384, 128, 128, 2)
    ) == 12
    made = fm.make_flash_attention(block_q=16, block_k=16)
    assert made.heads_a_call(64, 16384, 128, 128, 2, window=512) == 64


def test_a_window_is_causal_only():
    q, k, v, _ = _operands((1, 1, 64, 16), jnp.float32)
    with pytest.raises(ValueError, match="CAUSAL"):
        fm.flash_attention(q, k, v, False, None, 16, 16, 8)
