"""The chip path, guarded without a chip: the KERNELS compiled by the
TPU's own compiler for a DESCRIBED (not attached) v5e (``conftest.py``:
``v5e``, ``chip_compile``) at the widths the cells call them with —
the flash kernels, their band and grouped forms, the delta rule's state
and chunk kernels, sparse attention. What interpret mode cannot show —
tiling, fast-memory limits, partitioning under ``shard_map`` — costs
about two seconds a case here and no chip time. A compile that passes
is not a chip run and says nothing about results or speed. (The layers
around the kernels: ``tests/test_chip_compile_layers.py``; whole steps
and the smoke: ``tests/test_chip_compile.py``.)"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

# ``import adaptdl_tpu.ops.flash_attention as m`` yields the FUNCTION
# (the package re-exports it under the module's name).
flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")


FLAGSHIP = (8, 12, 512, 64)  # examples/transformer_lm.py at batch 8
LONG = (4, 8, 2048, 64)
CELL = (16, 12, 1024, 64)  # the benchmark's gpt2-124m micro-batch
HEAD_128 = (2, 8, 4096, 128)
LOOPED_CELL = (1, 16, 8192, 128)  # ouro-2.6b-steady's micro-batch
K_BLOCKED = (1, 2, 32768, 128)  # K and V of a head past the VMEM budget


def _attend(q, k, v):
    return flash_mod.flash_attention(q, k, v, True, None, 128, 128)


def _attend_loss(q, k, v):
    return _attend(q, k, v).astype(jnp.float32).sum()


@pytest.mark.parametrize(
    "what, shape, ndev",
    [
        ("fwd", FLAGSHIP, 0),
        ("grad", FLAGSHIP, 0),
        ("fwd", LONG, 0),
        ("grad", LONG, 0),
        ("shard_map", FLAGSHIP, 1),
        ("shard_map", FLAGSHIP, 4),
        ("shard_map_grad", FLAGSHIP, 4),
        ("fwd", CELL, 0),
        ("grad", CELL, 0),
        ("shard_map", CELL, 4),
        ("shard_map_grad", CELL, 4),
        ("fwd", HEAD_128, 0),
        ("grad", HEAD_128, 0),
        ("fwd", LOOPED_CELL, 0),
        ("grad", LOOPED_CELL, 0),
        ("fwd", K_BLOCKED, 0),
        ("grad", K_BLOCKED, 0),
    ],
)
def test_flash_kernel_compiles_for_v5e(v5e, chip_compile, what, shape, ndev):
    """bf16, blocks 128: forward, ``jax.grad`` through the custom vjp,
    and both under ``jax.shard_map`` over a ``data`` mesh (where the
    kernel's outputs must declare their varying axes) — each compiled
    program must contain the Mosaic custom call, i.e. the kernel was
    compiled, not interpreted and not replaced."""
    fn = _attend if what in ("fwd", "shard_map") else jax.grad(
        _attend_loss, argnums=(0, 1, 2)
    )
    if ndev:
        mesh = Mesh(np.array(v5e.devices[:ndev]), ("data",))
        shape = (shape[0] * ndev,) + shape[1:]
        fn = jax.shard_map(
            fn, mesh=mesh, in_specs=P("data"), out_specs=P("data")
        )
        sharding = NamedSharding(mesh, P("data"))
    else:
        sharding = SingleDeviceSharding(v5e.devices[0])
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    compiled = jax.jit(fn).lower(arg, arg, arg).compile()
    text = compiled.as_text()
    assert flash_mod.MOSAIC_CALL in text
    # The backward kernel is in a gradient's program under its own
    # name, which is how a device trace tells it from the forward.
    named = re.findall(
        rf"%[\w\-]*{flash_mod.BWD_KERNEL_NAME}[\w\-]*[.\d]* = "
        rf".*{flash_mod.MOSAIC_CALL}",
        text,
    )
    assert len(named) == (1 if "grad" in what else 0)
    resident = flash_mod._schedule(*shape[2:], 2, 128, 128).chunk_k == shape[2]
    assert resident == (shape != K_BLOCKED)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("qk,v", [(192, 128), (256, 256)])
@pytest.mark.parametrize("what", ["fwd", "grad"])
def test_flash_kernels_at_unequal_widths_compile_for_v5e(
    v5e, chip_compile, what, qk, v
):
    """Latent attention's call at the published widths (four heads a
    call, q and k 192 wide, v 128, 16 384 keys, bf16): past the VMEM
    budget, so the K-blocked schedule, forward and the one backward
    kernel. And the gated attention's of PR 49: heads of 256, twice
    the VMEM a key (four chunks of 4096 keys), two heads a call."""
    heads, a_call = (32, 4) if v == 128 else (16, 2)
    assert flash_mod.heads_a_call(heads, 16384, qk, v, 2) == a_call
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(width):
        return jax.ShapeDtypeStruct(
            (1, 4, 16384, width), jnp.bfloat16, sharding=one
        )

    fn = jax.grad(_attend_loss, argnums=(0, 1, 2)) if what == "grad" else _attend
    compiled = jax.jit(fn).lower(arg(qk), arg(qk), arg(v)).compile()
    text = compiled.as_text()
    assert text.count(flash_mod.MOSAIC_CALL) == (2 if what == "grad" else 1)
    if what == "grad":
        assert flash_mod.BWD_KERNEL_NAME in text
        grads = jax.eval_shape(fn, arg(qk), arg(qk), arg(v))
        assert [g.shape[-1] for g in grads] == [qk, qk, v]


SLIDING_RUN = (1, 16, 16384, 128)  # laguna-xs.2: a run of a sliding layer's heads


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("what", ["fwd", "grad"])
def test_band_kernels_compile_for_v5e(v5e, chip_compile, what, dtype):
    """The sliding layers' call of the laguna-xs.2 cell (16 heads of
    128 a call, 16 384 keys, a window of 512): the band schedule, K
    and V as blocks that follow the query tile, forward and the one
    backward kernel with its ring of dK / dV slots — under names of
    their own, which neither ``%attention`` nor ``flash_bwd`` reads. In
    float32 too: the cell's reference check runs the kernels so."""
    one = SingleDeviceSharding(v5e.devices[0])
    arg = jax.ShapeDtypeStruct(SLIDING_RUN, jnp.dtype(dtype), sharding=one)

    def attend(q, k, v):
        return flash_mod.flash_attention(q, k, v, True, None, 128, 128, 512)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if what == "grad" else attend
    text = jax.jit(fn).lower(arg, arg, arg).compile().as_text()

    def named(name):
        return re.findall(
            rf"%[\w\-]*{name}[\w\-]*[.\d]* = .*{flash_mod.MOSAIC_CALL}", text
        )

    assert len(named(flash_mod.WINDOW_FWD_NAME)) == 1
    assert len(named(flash_mod.WINDOW_BWD_NAME)) == (what == "grad")
    assert text.count(flash_mod.MOSAIC_CALL) == (2 if what == "grad" else 1)
    assert not named(flash_mod.BWD_KERNEL_NAME)
    assert not re.findall(rf"%attention[.\d]* = .*{flash_mod.MOSAIC_CALL}", text)
    # The schedule the cell's shape gets: what the chip run measured.
    sched = flash_mod._band_schedule(16384, 512, 128, 128, 256)
    assert sched == (flash_mod._WINDOW_TILE, flash_mod._WINDOW_PIECE, 1)
    visited, in_band = flash_mod._band_tiles(sched, 16384, 512)
    assert visited == in_band
    assert visited * sched.piece**2 / flash_mod.keys_in_window(
        16384, 512
    ) < 3.0


# (q heads, kv heads, keys, head width, window) of ONE call as the
# grouped-query cells make it since PR 55: a sliding layer's 64 heads
# on 8 kv heads, a full layer's run of 12 on 2 (K-blocked, two
# chunks), qwen3-next's run of 2 on 1 at head 256 (four chunks),
# lfm2's 32 on 8 at head 64 (K / V resident).
GROUPED_CALLS = {
    "laguna_sliding": (64, 8, 16384, 128, 512),
    "laguna_full": (12, 2, 16384, 128, None),
    "qwen3_next": (2, 1, 16384, 256, None),
    "lfm2": (32, 8, 8192, 64, None),
}


@pytest.mark.parametrize("case", list(GROUPED_CALLS))
def test_kernels_with_fewer_kv_heads_compile_for_v5e(v5e, chip_compile, case):
    """k and v ``kv_heads`` wide, indexed by ``query head // group``
    inside all four kernels (the group's heads one more grid axis of
    the backwards and of the band forward): forward and backward
    compile at the cells' shapes, and the gradients of k and v come
    back ``kv_heads`` wide."""
    heads, kv_heads, seq, width, window = GROUPED_CALLS[case]
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(n):
        return jax.ShapeDtypeStruct(
            (1, n, seq, width), jnp.bfloat16, sharding=one
        )

    def loss(q, k, v):
        out = flash_mod.flash_attention(q, k, v, True, None, 128, 128, window)
        return out.astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2))
    args = (arg(heads), arg(kv_heads), arg(kv_heads))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count(flash_mod.MOSAIC_CALL) == 2
    names = (
        (flash_mod.WINDOW_FWD_NAME, flash_mod.WINDOW_BWD_NAME)
        if window else ("attention", flash_mod.BWD_KERNEL_NAME)
    )
    assert all(name in text for name in names)
    assert [g.shape[1] for g in jax.eval_shape(fn, *args)] == [
        heads, kv_heads, kv_heads
    ]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("what", ["fwd", "grad"])
def test_kda_kernels_compile_for_v5e(
    v5e, chip_compile, monkeypatch, what, dtype
):
    """The gated delta rule at the cell's real shape (a group of four
    heads of 128, 256 chunks of 64 — a row of 16 384 —, bf16; and
    float32 operands, whose blocks are twice as large): the state
    kernels are in the program under the names a device trace shows,
    ``kda_fwd`` and, in a gradient's, ``kda_bwd``, and the chip's
    compiler takes the VMEM of the blocks ``_state_how`` chose (the
    group's heads abreast, several chunks a grid step)."""
    kda = importlib.import_module("adaptdl_tpu.ops.kda")
    trace = importlib.import_module("adaptdl_tpu.trace")
    monkeypatch.setattr(kda, "_use_interpret", lambda: False)
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(shape, dtype=jnp.dtype(dtype)):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (
        arg((1, 16384, 4, 128)), arg((1, 16384, 4, 128)),
        arg((1, 16384, 4, 128)), arg((1, 16384, 4, 128), jnp.float32),
        arg((1, 16384, 4), jnp.float32),
    )

    def forward(*a):
        return kda.kda(*a, chunk=64)

    def loss(*a):
        return forward(*a).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=tuple(range(5))) if what == "grad" else forward
    text = jax.jit(fn).lower(*args).compile().as_text()
    found = set(re.findall(r"%[\w\-]*?(kda_(?:fwd|bwd))[\w\-]*[.\d]* = ", text))
    assert found == ({"kda_fwd", "kda_bwd"} if what == "grad" else {"kda_fwd"})
    attrs = [
        r for r in trace.snapshot_spans() if r["name"] == "kda.schedule"
    ][-1]["attrs"]
    held = 8 if dtype == "bfloat16" else 4
    assert (attrs["state_heads_a_step"], attrs["state_chunks_a_step"],
            attrs["state_chunks_a_step_bwd"]) == (4, held, held)
    assert attrs["state_grid_steps"] == 256 // held  # 1024 at (1, 1)


@pytest.mark.parametrize("what", ["fwd", "grad"])
def test_delta_chunk_head_kernels_compile_for_v5e(
    v5e, chip_compile, monkeypatch, what
):
    """The chunks' own work where a head has ONE decay, at the
    qwen3-next cell's call (four value heads of 128, 256 chunks of 64,
    bf16, g and beta ``[bh, chunks, 1, C]`` float32): the pair lowers
    through Mosaic under names that hold ``delta_chunk`` (what the
    accepted readers match) and ``_head_``, the gradient's program
    holds the forward too (it writes the inverse out for the
    backward), and g's gradient comes back a head's."""
    kda = importlib.import_module("adaptdl_tpu.ops.kda")
    monkeypatch.setattr(kda, "_use_interpret", lambda: False)
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(width, dtype=jnp.bfloat16, rows=64):
        return jax.ShapeDtypeStruct(
            (4, 256, rows, width), dtype, sharding=one
        )

    args = (
        arg(128), arg(128), arg(128), arg(64, jnp.float32, rows=1),
        arg(64, jnp.float32, rows=1),
    )

    def forward(*a):
        return kda._head_work(128**-0.5, *a)

    def loss(*a):
        return sum(x.astype(jnp.float32).sum() for x in forward(*a))

    fn = jax.grad(loss, argnums=tuple(range(5))) if what == "grad" else forward
    text = jax.jit(fn).lower(*args).compile().as_text()
    found = set(
        re.findall(r"%[\w\-]*?(delta_chunk_\w+?)[.\d]* = ", text)
    )
    assert found == {"delta_chunk_head_fwd"} | (
        {"delta_chunk_head_bwd"} if what == "grad" else set()
    )
    assert "kda_" not in (
        kda.OWN_HEAD_FWD_KERNEL_NAME + kda.OWN_HEAD_BWD_KERNEL_NAME
    )
    if what == "grad":
        grads = jax.eval_shape(fn, *args)
        assert [(g.shape, g.dtype) for g in grads] == [
            (a.shape, a.dtype) for a in args
        ]


@pytest.mark.parametrize("what", ["fwd", "grad"])
def test_delta_chunk_kernels_compile_for_v5e(
    v5e, chip_compile, monkeypatch, what
):
    """The chunks' own work of the gated delta rule at the published
    widths (four heads of 128, 256 chunks of 64 — a row of 16 384 —,
    bf16): the kernel pair lowers through Mosaic under the names a
    device trace shows, ``delta_chunk_fwd`` and, in a gradient's, both:
    the forward rule's kernel writes the inverse and ``A`` out (eight
    results) and ``delta_chunk_bwd`` takes them among its operands;
    neither name holds ``kda_`` (the state kernels' readers match
    that)."""
    kda = importlib.import_module("adaptdl_tpu.ops.kda")
    monkeypatch.setattr(kda, "_use_interpret", lambda: False)
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(width, dtype=jnp.bfloat16, rows=64):
        return jax.ShapeDtypeStruct(
            (4, 256, rows, width), dtype, sharding=one
        )

    args = (
        arg(128), arg(128), arg(128), arg(128, jnp.float32),
        arg(64, jnp.float32, rows=1),
    )

    def forward(*a):
        return kda._own_work(128**-0.5, *a)

    def loss(*a):
        return sum(x.astype(jnp.float32).sum() for x in forward(*a))

    assert kda.kernel_fits(128, 128, 64)
    fn = jax.grad(loss, argnums=tuple(range(5))) if what == "grad" else forward
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = dict(
        re.findall(
            r"%[\w\-]*?(delta_chunk_(?:fwd|bwd))[\w\-]*[.\d]* = (.*)", text
        )
    )
    assert set(calls) == (
        {"delta_chunk_fwd", "delta_chunk_bwd"} if what == "grad"
        else {"delta_chunk_fwd"}
    )
    assert "kda_" not in kda.OWN_FWD_KERNEL_NAME + kda.OWN_BWD_KERNEL_NAME
    kept = "f32[4,256,64,64]"  # the inverse, and A beside it
    results = calls["delta_chunk_fwd"].split(" custom-call(")[0]
    assert results.count(kept) == (2 if what == "grad" else 0)
    assert results.count("[4,256,") == (8 if what == "grad" else 6)
    if what == "grad":
        operands = calls["delta_chunk_bwd"].split(" custom-call(")[1]
        assert operands.count(kept) == 2
        grads = jax.eval_shape(fn, *args)
        assert [(g.shape, g.dtype) for g in grads] == [
            (a.shape, a.dtype) for a in args
        ]


@pytest.mark.parametrize(
    "what, seq, kv_heads",
    [
        ("fwd", 4096, 4),
        ("grad", 4096, 4),
        ("fwd_f32_out", 4096, 4),
        # The benchmark cell's row: dK, dV and dkI of the WHOLE row are
        # float32 VMEM scratch of the one backward kernel (72 MiB).
        ("grad", 16384, 4),
        # Past the budget, by the row and by the kv heads: two kernels.
        ("grad", 32768, 4),
        ("grad", 16384, 8),
        # The cell's row through the loss's second pass, which is in no
        # gradient's program: a group's 8 heads unrolled over one
        # product of logits [512, 8 x 128] (PR 37).
        ("fwd", 16384, 4),
    ],
)
def test_sparse_attention_kernels_compile_for_v5e(
    v5e, chip_compile, what, seq, kv_heads
):
    """The indexer's selection and sparse attention at the published
    widths (32 / 4 heads of 128, an indexer of 16 x 64, topk 2048) in
    bf16: every kernel is in the program under the name a device trace
    shows, the forward's four and, in a gradient's, the backward in
    place of the loss's (whose value a gradient does not need): ONE
    kernel where the row's accumulators fit the VMEM budget — the
    cell's row of 16 384 does, under the module's own limit —, the two
    kernels that hold a tile's each where they do not."""
    sparse = importlib.import_module("adaptdl_tpu.ops.sparse_attention")
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (
        arg((1, 32, seq, 128)), arg((1, kv_heads, seq, 128)),
        arg((1, kv_heads, seq, 128)), arg((1, 16, seq, 64)),
        arg((1, seq, 64)), arg((1, seq, 16), jnp.float32),
    )

    def forward(*a):
        return sparse.sparse_attention(
            *a, 2048,
            out_dtype=jnp.float32 if what == "fwd_f32_out" else None,
        )

    def loss(*a):
        out, index_loss, _, _ = forward(*a)
        return out.astype(jnp.float32).sum() + index_loss.sum()

    fn = (
        jax.grad(loss, argnums=tuple(range(6))) if what == "grad" else forward
    )
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    found = set(
        re.findall(
            r"%[\w\-]*?(sparse_(?:attn|index)_[a-z_]*[a-z])_*[.\d]* = "
            rf".*{flash_mod.MOSAIC_CALL}",
            text,
        )
    )
    schedule, held = sparse.backward_schedule(kv_heads, seq, 128, 64)
    assert (schedule == "one_kernel") == (
        seq <= 16384 and kv_heads == 4
    ) == (held <= sparse._ROW_BUDGET < sparse._VMEM_LIMIT)
    want = {sparse.SELECT_KERNEL_NAME, sparse.FWD_KERNEL_NAME}
    if what != "grad":
        want |= {sparse.KL_KERNEL_NAME}
    elif schedule == "one_kernel":
        want |= {sparse.BWD_KERNEL_NAME}
    else:
        want |= {sparse.BWD_Q_KERNEL_NAME, sparse.BWD_KV_KERNEL_NAME}
    assert found == want, found
    assert text.count(flash_mod.MOSAIC_CALL) >= len(want)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
