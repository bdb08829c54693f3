"""The chip path, guarded without a chip.

1. The main path's kernel compiled by the TPU's own compiler for a
   DESCRIBED (not attached) v5e at the flagship widths: what interpret
   mode cannot show — tiling, fast-memory limits, partitioning under
   ``shard_map`` — costs about two seconds a case here and no chip
   time. A compile that passes is not a chip run and says nothing
   about results or speed.
2. ``chip_smoke.py``'s two incarnations end to end on the CPU at a tiny
   size: wrong paths, arguments and control flow in the smoke are found
   here, not on the chip.
"""

import importlib
import os
import re
import sys

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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ``import adaptdl_tpu.ops.flash_attention as m`` yields the FUNCTION
# (the package re-exports it under the module's name).
flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")


@pytest.fixture
def chip_compile(monkeypatch):
    """Steer the code the way the chip would (the program itself asks
    ``jax.default_backend()``, which is the CPU here), and keep the
    persistent compile cache out of it: a compile for a described chip
    is written to the cache but cannot be read back without a chip, so
    the next one would warn and compile again."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(flash_mod, "_use_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


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


@pytest.mark.parametrize(
    "held, total, top_k, d_expert, router, bound, capacity",
    [
        (8, 32, 4, 1792, "sigmoid", 45056, 69632),  # lfm2-8b-a1b
        # keye-vl-2.0-30b-a3b: the rest would be longer than the bound,
        # so one pass over the worst case and no loop.
        (16, 128, 8, 768, "softmax", 139264, 139264),
    ],
)
def test_routed_layer_walks_the_bounded_buffer_on_v5e(
    v5e, chip_compile, monkeypatch, held, total, top_k, d_expert, router,
    bound, capacity,
):
    """A routed layer of either cell and its gradients, 16 384 tokens
    of 2048 in bf16. Where the layer bounds its buffer, the glue XLA
    generates has ``rows_bound`` rows in the usual pass and the plan's
    other rows in the loop that runs where the plan passes the bound,
    no array of the worst case's length is left in the program (the
    int32 row plan apart), and the program's temporaries are under the
    1.37 GiB the worst-case buffer took; the grouped products are in
    every pass under their names."""
    gmm = importlib.import_module("adaptdl_tpu.ops.grouped_matmul")
    moe = importlib.import_module("adaptdl_tpu.models.moe")
    monkeypatch.setattr(gmm, "_use_interpret", lambda: False)
    one = SingleDeviceSharding(v5e.devices[0])
    tokens, d = 16384, 2048
    assert moe.rows_bound(tokens, top_k, held, total, 512) == bound
    assert moe.rows_capacity(tokens, top_k, held, 512) == capacity

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(x, router_w, w_gate, w_up, w_down):
        y, load = moe.routed_experts(
            x, router_w,
            jnp.zeros((total,)) if router == "sigmoid" else None,
            w_gate, w_up, w_down, experts_total=total, first_expert=0,
            top_k=top_k, router_kind=router,
        )
        return y.astype(jnp.float32).sum(), load["fell_back"]

    compiled = jax.jit(
        jax.grad(loss, argnums=tuple(range(5)), has_aux=True)
    ).lower(
        arg((tokens, d), jnp.bfloat16), arg((d, total)),
        arg((held, d, d_expert)), arg((held, d, d_expert)),
        arg((held, d_expert, d)),
    ).compile()
    text = compiled.as_text()
    assert " conditional(" not in text
    assert bound == capacity or " while(" in text
    for rows in {bound, capacity - bound} - {0}:
        assert re.search(rf"(bf16|f32)\[{rows},", text), rows
    if bound < capacity:
        assert not re.search(rf"(bf16|f32)\[{capacity},", text)
    # Forward and both transposes, of each pass.
    passes = 2 if bound < capacity else 1
    assert text.count(f"%{gmm.GMM_KERNEL_NAME}") >= 6 * passes
    assert text.count(f"%{gmm.TGMM_KERNEL_NAME}") >= 3 * passes
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1.25 if bound < capacity else 2.5
    ) * 2**30


def test_glm_mixer_and_pieces_compile_for_v5e(v5e, chip_compile, monkeypatch):
    """glm-4.7-flash's two shapes no other cell has (PR 56). (1) Its
    latent-attention mixer and gradients at the cell's widths on one
    row of 16 384 (20 heads of q / k 256 of which 64 lanes are rotated,
    v 256, the query bottleneck of 768): the heads go two a call, ten
    forward and ten backward kernels under the names a device trace
    shows, and ``mla.schedule`` says so. (2) A routed layer of 8 held
    of 64, top 4, width 1536, told ``pieces_from`` 2.5 as the
    configuration tells it: the plan is walked in pieces of the bound
    (24 576 rows), no array of the worst case's 69 632 is left (the
    int32 row plan apart), where the layer's own threshold would keep
    the one pass."""
    import functools

    from adaptdl_tpu import trace
    from adaptdl_tpu.models.transformer import (
        LatentAttention,
        TransformerConfig,
    )

    gmm = importlib.import_module("adaptdl_tpu.ops.grouped_matmul")
    moe = importlib.import_module("adaptdl_tpu.models.moe")
    monkeypatch.setattr(gmm, "_use_interpret", lambda: False)
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = TransformerConfig(
        vocab_size=19360, num_layers=1, num_heads=20, d_model=2048,
        d_ff=10240, dtype=jnp.bfloat16, norm="rmsnorm", norm_eps=1e-5,
        rope=True, rope_theta=1e6, layer_types=("mla",), q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256,
        attention_fn=functools.partial(
            flash_mod.flash_attention, block_q=128, block_k=128
        ),
    )
    mixer = LatentAttention(cfg)
    x = arg((1, 16384, 2048), jnp.bfloat16)
    params = jax.tree.map(
        lambda leaf: arg(leaf.shape, leaf.dtype),
        jax.eval_shape(
            lambda: mixer.init(
                jax.random.key(0), jnp.zeros((1, 128, 2048), jnp.bfloat16),
                jnp.arange(128),
            )["params"]
        ),
    )

    def mixed(params, x):
        out = mixer.apply({"params": params}, x, jnp.arange(16384))
        return out.astype(jnp.float32).sum()

    since = len(trace.snapshot_spans())
    text = jax.jit(jax.grad(mixed, argnums=(0, 1))).lower(
        params, x
    ).compile().as_text()
    attrs = [
        r["attrs"] for r in trace.snapshot_spans()[since:]
        if r["name"] == "mla.schedule"
    ][-1]
    assert (
        attrs["heads"], attrs["heads_a_call"], attrs["qk_width"],
        attrs["v_width"], attrs["q_lora_rank"], attrs["rotary_dims"],
    ) == (20, 2, 256, 256, 768, 64)
    assert len(re.findall(r"^\s*%attention[.\d]* = ", text, re.M)) == 10
    assert len(
        re.findall(rf"^\s*%{flash_mod.BWD_KERNEL_NAME}[.\d]* = ", text, re.M)
    ) == 10

    tokens, d, held, total, top_k, f = 16384, 2048, 8, 64, 4, 1536
    assert moe.rows_bound(tokens, top_k, held, total, 512) == 69632
    assert moe.rows_bound(tokens, top_k, held, total, 512, 2.5) == 24576
    assert moe.rows_planned(tokens, top_k, held, total, 512, 2.5) == 73728

    def loss(x, router_w, w_gate, w_up, w_down):
        y, load = moe.routed_experts(
            x, router_w, jnp.zeros((total,)), w_gate, w_up, w_down,
            experts_total=total, first_expert=0, top_k=top_k,
            pieces_from=2.5,
        )
        return y.astype(jnp.float32).sum(), load["fell_back"]

    compiled = jax.jit(
        jax.grad(loss, argnums=tuple(range(5)), has_aux=True)
    ).lower(
        arg((tokens, d), jnp.bfloat16), arg((d, total)),
        arg((held, d, f)), arg((held, d, f)), arg((held, f, d)),
    ).compile()
    text = compiled.as_text()
    assert " while(" in text and " conditional(" not in text
    assert re.search(r"(bf16|f32)\[24576,", text)
    assert not re.search(r"(bf16|f32)\[(69632|73728),", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * 2**30


def _router_products(text, tokens, experts):
    """How the compiler tiles each float32 "highest" product with a
    ``[tokens, experts]`` result in an optimized program: the
    ``window_config`` of the fusion that holds it (its bounds say how
    the contraction is split, so which sums are taken in which
    order)."""
    found = []
    bodies = dict(
        re.findall(r"^(%[\w.\-]+) \(.*?\) -> .*? \{\n(.*?)^\}", text,
                   re.S | re.M)
    )
    for line in text.splitlines():
        called = re.search(r" fusion\(.*calls=(%[\w.\-]+)", line)
        if called and re.search(
            rf"f32\[{tokens},{experts}\]\S* convolution\(.*"
            r"operand_precision=\{highest,highest\}",
            bodies.get(called.group(1), ""),
        ):
            found.append(
                re.search(r'"window_config":\{(.*?)"estimated_cycles"',
                          line).group(1)
            )
    return found


def test_lfm2_check_and_system_tile_their_routers_alike_on_v5e(
    v5e, chip_compile, monkeypatch
):
    """``lfm2-8b-a1b-steady``'s own check holds every routed layer's
    output to a reference that routes for itself, token by token, with
    no allowance for a token whose 4th and 5th scores tie to the last
    bit (``benchmark/configs/lfm2-8b-a1b.py:routed_check``): such a
    token passes only while the reference's router product is summed
    in the system's order, and the compiler decides that per program
    from what else the program holds. With the row buffer at twice the
    rows expected the check's program split the contraction in four
    and one seed in eleven failed on the chip (PERF.md section 6, PR
    40). Until a benchmark PR lets the comparison skip disputed
    tokens, a change to the routed layer's shapes has to keep the two
    products tiled alike: compiled here for a described v5e, as the
    chip's compiler does it."""
    from benchmark import manifest
    from adaptdl_tpu.parallel import mesh as mesh_mod

    gmm = importlib.import_module("adaptdl_tpu.ops.grouped_matmul")
    monkeypatch.setattr(gmm, "_use_interpret", lambda: False)
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    one = SingleDeviceSharding(v5e.devices[0])
    mesh = Mesh(np.array(v5e.devices[:1]), ("data",))
    monkeypatch.setattr(
        mesh_mod, "create_mesh_from_topology", lambda **kw: mesh
    )
    cell = manifest.load_cell("lfm2-8b-a1b-steady")
    config = manifest.load_module(cell.config_py)
    sizes = cell.sizes
    # Abstract weights: nothing can be placed on a described device.
    real_jit = jax.jit
    monkeypatch.setattr(
        jax, "jit",
        lambda f, **kw: (lambda *a: jax.eval_shape(f, *a))
        if getattr(f, "__name__", "") == "<lambda>" else real_jit(f, **kw),
    )
    built = config.build(sizes, dict(cell.workload["geometry"]), 0)
    monkeypatch.setattr(jax, "jit", real_jit)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree,
        )

    params = on_chip(built["trainer"]._abstract_state().params)
    rows, seq = config.REFERENCE_SEQUENCES, sizes["sequence_length"]
    batch = {
        k: jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one)
        for k in ("inputs", "targets")
    }
    system = jax.jit(built["head_io"]).lower(
        params, batch, on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    ).compile().as_text()
    at = sizes["num_dense_layers"]
    layer = on_chip(
        jax.eval_shape(
            lambda p: config.reference_weights(p, sizes)["layers"][at],
            params,
        )
    )
    x = jax.ShapeDtypeStruct(
        (rows * seq, sizes["hidden_size"]), jnp.bfloat16, sharding=one
    )
    check = jax.jit(config.routed_check(built, sizes)).lower(
        layer, params[f"layer_{at}"]["moe"], x, x
    ).compile().as_text()
    experts = sizes["num_experts"]
    of_system = _router_products(system, rows * seq, experts)
    of_reference = _router_products(check, rows * seq, experts)
    assert len(of_system) == sizes["num_hidden_layers"] - at
    assert len(of_reference) == 1
    assert set(of_system) == set(of_reference), (of_system, of_reference)


def test_block_keeps_the_projections_layout_on_v5e(v5e, chip_compile):
    """One remat ``Block`` of the benchmark's model, forward and
    gradient at the cell's micro-batch, compiled for the described
    v5e: ``Attention``'s swaps and the kernels' own cancel against
    the layout XLA gives the projections (sequence minor-most), so
    every operand of both kernels is a bitcast of what a fusion wrote:
    q, k, v and out are never copied into ``[b, h, s, d]``, into a
    flat ``[b * h, s, d]`` or into the kernels' ``[b * h, d, s]``, and
    rotary makes no gather and no pair-shaped array (PERF.md, PR 27).
    The kernels keep the names the benchmark's readers find them by:
    the forward ONE Mosaic call ``%attention.<n>``, the backward
    ``flash_bwd``."""
    import functools

    import flax.linen as nn

    from adaptdl_tpu.models.transformer import Block, TransformerConfig

    batch, heads, seq, head_dim = CELL
    cfg = TransformerConfig(
        vocab_size=50257, num_layers=1, num_heads=heads,
        d_model=heads * head_dim, d_ff=4 * heads * head_dim,
        max_seq_len=seq, dtype=jnp.bfloat16, remat=True,
        attention_fn=functools.partial(
            flash_mod.flash_attention, block_q=128, block_k=128
        ),
    )
    block = nn.remat(Block, static_argnums=())(cfg)
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip
            ),
            tree,
        )

    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.bfloat16)
    positions = jnp.arange(seq)
    params = jax.eval_shape(
        lambda: block.init(
            jax.random.key(0), jnp.zeros(x.shape, x.dtype), positions, None
        )
    )

    def loss(params, x):
        out = block.apply(params, x, positions, None)
        return out.astype(jnp.float32).sum()

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1)))
        .lower(on_chip(params), on_chip(x))
        .compile()
        .as_text()
    )
    mosaic = flash_mod.MOSAIC_CALL
    # The forward kernel, under the module's scope name alone (first
    # pass and remat are one call here: XLA merges the two of a lone
    # block).
    assert re.findall(rf"^\s*%attention[.\d]* = .*{mosaic}", text, re.M)
    assert len(re.findall(
        rf"^\s*%{flash_mod.BWD_KERNEL_NAME}[.\d]* = .*{mosaic}", text, re.M
    )) == 1
    calls = re.findall(
        rf"^\s*%(?:attention|{flash_mod.BWD_KERNEL_NAME})[.\d]* = "
        rf".*? custom-call\(([^)]*)\), custom_call_target=\"{mosaic}",
        text, re.M,
    )
    assert len(calls) >= 2
    for operands in calls:
        assert "%copy." not in operands and "%transpose" not in operands
    moved = re.findall(
        r"^\s*(?:ROOT )?%[\w\-.]+ = (\w+\[[\d,]*\])\S* "
        r"(copy|transpose|gather|reshape)\(",
        text, re.M,
    )
    assert moved, "the pattern found no data movement at all"
    flat = batch * heads
    banned = [
        (op, shape) for shape, op in moved
        if shape in (
            f"bf16[{batch},{heads},{seq},{head_dim}]",
            f"bf16[{flat},{seq},{head_dim}]",
            f"bf16[{batch},{heads},{head_dim},{seq}]",
            f"bf16[{flat},{head_dim},{seq}]",
        )
        or shape.endswith(",2]")
    ]
    assert not banned, banned


def test_remat_keeps_the_kernels_output_on_v5e(v5e, chip_compile):
    """The gradient of a remat'd two-layer ``TransformerLM`` at the
    flagship widths and the cell's micro-batch, compiled for the
    described v5e, holds ONE forward and ONE backward Mosaic call a
    layer: a block keeps the kernel's ``out`` and ``lse`` by name
    (``block_remat``), where the bare ``nn.remat`` held two forwards a
    layer — the kernel re-run in every backward to rebuild an output
    it had (PERF.md, PR 29)."""
    import functools

    from adaptdl_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    batch, heads, seq, head_dim = CELL
    layers = 2
    cfg = TransformerConfig(
        vocab_size=512, num_layers=layers, num_heads=heads,
        d_model=heads * head_dim, d_ff=4 * heads * head_dim,
        max_seq_len=seq, dtype=jnp.bfloat16, remat=True,
        attention_fn=functools.partial(
            flash_mod.flash_attention, block_q=128, block_k=128
        ),
    )
    model = TransformerLM(cfg)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda: model.init(
                jax.random.key(0), jnp.zeros(tokens.shape, tokens.dtype)
            )
        ),
    )

    def loss(params, tokens):
        return model.apply(params, tokens, train=False).sum()

    text = jax.jit(jax.grad(loss)).lower(params, tokens).compile().as_text()

    def calls(name):
        return len(re.findall(
            rf"^\s*%{name}[.\d]* = .*{flash_mod.MOSAIC_CALL}", text, re.M
        ))

    assert calls("attention") == layers
    assert calls(flash_mod.BWD_KERNEL_NAME) == layers


def test_ladder_keeps_the_projections_results_on_v5e(v5e, chip_compile):
    """Two layers at the flagship widths and the cell's micro-batch,
    the gradient compiled for the described v5e: with the budget a
    16 GB chip leaves a ``gpt2-124m`` job (``block_remat``'s ladder
    takes its three rungs) the text holds ONE fused QKV projection a
    layer, where without a budget — the program before the ladder — it
    holds two, the forward's and the backward's re-run; the kernels
    are one forward and one backward a layer either way (PERF.md, PR
    41)."""
    import functools

    from adaptdl_tpu import device_budget, trace
    from adaptdl_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    batch, heads, seq, head_dim = CELL
    layers = 2
    cfg = TransformerConfig(
        vocab_size=512, num_layers=layers, num_heads=heads,
        d_model=heads * head_dim, d_ff=4 * heads * head_dim,
        max_seq_len=seq, dtype=jnp.bfloat16, remat=True,
        attention_fn=functools.partial(
            flash_mod.flash_attention, block_q=128, block_k=128
        ),
    )
    model = TransformerLM(cfg)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda: model.init(
                jax.random.key(0), jnp.zeros(tokens.shape, tokens.dtype)
            )
        ),
    )

    def loss(params, tokens):
        return model.apply(params, tokens, train=False).sum()

    def qkv_and_kernels(budget):
        with device_budget.tracing_with(budget):
            text = (
                jax.jit(jax.grad(loss)).lower(params, tokens).compile()
                .as_text()
            )
        qkv = len(re.findall(
            rf"^\s*%[\w.\-]+ = bf16\[3,{batch},{seq},{heads},{head_dim}\]"
            r"\S* fusion\(", text, re.M,
        ))
        kernels = tuple(
            len(re.findall(
                rf"^\s*%{name}[.\d]* = .*{flash_mod.MOSAIC_CALL}", text, re.M
            ))
            for name in ("attention", flash_mod.BWD_KERNEL_NAME)
        )
        return qkv, kernels

    assert qkv_and_kernels(None) == (2 * layers, (layers, layers))
    gib = 2**30
    # gpt2-124m on a v5e: 15.75 GiB less two copies of 2.30 GiB of
    # state and gradient and a sixteenth in reserve.
    budget = device_budget.Activations(int(10.17 * gib), int(15.75 * gib))
    assert qkv_and_kernels(budget) == (layers, (layers, layers))
    (attrs,) = [
        r["attrs"] for r in trace.snapshot_spans()
        if r["name"] == "remat.policy"
    ][-1:]
    assert attrs["rungs"] == "qkv,mixed,ff_up"
    assert attrs["rung_bytes"] == layers * batch * seq * 2 * 8 * cfg.d_model


@pytest.mark.parametrize(
    "cell", ["lfm2-8b-a1b-steady", "keye-vl-2.0-30b-a3b-steady"]
)
def test_routed_cells_steps_are_the_programs_before_the_ladder(
    v5e, chip_compile, monkeypatch, tmp_path, cell
):
    """Both routed cells at real size, on a described v5e that says
    what a 16 GB chip says (``bytes_limit`` 15.75 GiB) under a job
    with a checkpoint path: two copies of their state and gradient
    pass the limit, so the step DONATES, and a job that donates for
    want of memory has no bytes for the ladder — the program the
    trainer lowers is, text for text, the one lowered with no budget
    mechanism and no rung named at all (their traffic bypasses the
    ladder by the rule's own decision, not by their names)."""
    from adaptdl_tpu import trace
    from adaptdl_tpu import trainer as trainer_mod
    from adaptdl_tpu.models import transformer
    from tools import compile_step_v5e as rehearsal

    gmm = importlib.import_module("adaptdl_tpu.ops.grouped_matmul")
    monkeypatch.setattr(gmm, "_use_interpret", lambda: False)
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))

    def lowered(limit):
        rehearsal.inject_limit(limit, monkeypatch.setattr)
        lower, facts = rehearsal.step_program(
            cell, bytes_limit=rehearsal.BYTES_LIMIT, topo=v5e
        )
        assert facts["donated"]
        # (Without the serial numbers that lowering appends to the
        # names of private functions.)
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", lower().as_text())

    def no_mechanism():
        for mod in (flash_mod, transformer):
            monkeypatch.setattr(
                mod, "checkpoint_name",
                lambda x, name, keep=mod.checkpoint_name: x if name in (
                    flash_mod.SAVED_QKV, transformer.SAVED_QKV,
                    transformer.SAVED_MIXED, transformer.SAVED_FF_UP,
                ) else keep(x, name),
            )
        monkeypatch.setattr(
            trainer_mod.ElasticTrainer, "_activations", lambda self: None,
        )

    texts = []
    # (One call site for both: a Pallas kernel's serialized body holds
    # the Python stack it was traced under, line and column.)
    for limit, prepare in (
        (rehearsal.BYTES_LIMIT, lambda: None), (None, no_mechanism)
    ):
        prepare()
        before = len(trace.snapshot_spans())
        texts.append(lowered(limit))
        policies = [
            r["attrs"] for r in trace.snapshot_spans()[before:]
            if r["name"] == "remat.policy"
        ]
        assert policies and all(p["rungs"] == "" for p in policies)
    assert texts[0] == texts[1]


def test_the_tpu_compiler_has_the_reduce_overlap_options(v5e, chip_compile):
    """The three (internal) names a step of several replicas is
    compiled under: this jaxlib's TPU compiler takes each of them, as
    it refuses a name it does not have. Were one dropped or renamed,
    every such step would fail to compile, jitted and AOT alike."""
    from adaptdl_tpu.trainer import REDUCE_OVERLAP_OPTIONS

    mesh = Mesh(np.array(v5e.devices), ("data",))
    mean = jax.shard_map(
        lambda x: jax.lax.pmean(x, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P(),
    )
    x = jax.ShapeDtypeStruct(
        (4, 128), jnp.float32, sharding=NamedSharding(mesh, P("data"))
    )
    assert len(REDUCE_OVERLAP_OPTIONS) == 3
    for name, value in REDUCE_OVERLAP_OPTIONS.items():
        jax.jit(mean, compiler_options={name: value}).lower(x).compile()
        with pytest.raises(Exception, match="No such compile option"):
            jax.jit(
                mean, compiler_options={name + "_": value}
            ).lower(x).compile()


def test_dp4_step_reduces_under_the_last_backward_on_v5e(
    v5e, chip_compile, monkeypatch, tmp_path
):
    """``gpt2-124m-dp4``'s step at real size on the described v5e:2x2
    with the chip's memory limit: the last micro-batch stands behind
    the accumulation loop and the model is traced ONCE for both (scan
    keeps its body's trace by the function); the compiled program
    holds both micro-batches' backwards in the entry computation (a loop of one
    trip is inlined), one after the other (no more memory than the
    all-in-scan step's), and the leaves' all-reduces run under the
    weight-gradient products the scheduler left for the end — the
    compiler writes such a pair as an ``async-collective-start`` /
    ``-done`` fusion around the op it runs under — the first of them
    before the last micro-batch's backward has ended."""
    from adaptdl_tpu import trace
    from adaptdl_tpu import trainer as trainer_mod
    from tools import compile_step_v5e as rehearsal

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    rehearsal.inject_limit(rehearsal.BYTES_LIMIT, monkeypatch.setattr)
    calls = []

    def counting(loss_fn, differentiate=trainer_mod._value_and_grad):
        def counted(*args):
            calls.append(1)
            return loss_fn(*args)

        return differentiate(counted)

    monkeypatch.setattr(trainer_mod, "_value_and_grad", counting)
    lower, facts = rehearsal.step_program(
        "gpt2-124m-dp4", bytes_limit=rehearsal.BYTES_LIMIT, topo=v5e
    )
    assert facts["chips"] == 4 and not facts["donated"]
    lowered = lower()
    assert len(calls) == 1
    (event,) = [
        r["attrs"] for r in trace.snapshot_spans()
        if r["name"] == "step.reduce_overlap"
    ]
    assert event == {
        "replicas": 4, "num_micro": 2, "scanned": 1, "tail": True,
        "groups": 74,
    }
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # (The all-in-scan step: 7.48 GiB of temporaries; both
    # micro-batches' activations alive at once: 10.24.)
    assert mem.temp_size_in_bytes < 8 * 2**30
    lines = rehearsal.entry_computation(compiled.as_text()).split("\n")
    backward = [
        i for i, line in enumerate(lines)
        if re.match(r"\s*%flash_bwd[.\d]* = ", line)
    ]
    started = [
        i for i, line in enumerate(lines)
        if re.match(rehearsal.ASYNC_START, line)
    ]
    assert len(backward) == 24
    # (48 matrices a step; the 25 LayerNorm vectors, 3 KB each, and
    # the tied table, whose gradient ends with the backward, stay
    # synchronous.)
    assert len(started) >= 40
    assert started[0] < backward[-1]
    for start in started:
        under = [
            line for line in lines[start:start + 120]
            if "calls=%async_collective_fusion" in line
        ]
        assert under and "dot_general" in under[0]


def test_one_chip_step_is_the_program_with_no_tail(
    v5e, chip_compile, monkeypatch, tmp_path
):
    """``gpt2-124m-steady``'s step on one described chip: one replica
    has no reduce to overlap, and the program the trainer lowers is,
    text for text, the one lowered with the tail (and with it its
    compiler options) switched off in the trainer."""
    from adaptdl_tpu import trace
    from adaptdl_tpu import trainer as trainer_mod
    from tools import compile_step_v5e as rehearsal

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    rehearsal.inject_limit(rehearsal.BYTES_LIMIT, monkeypatch.setattr)

    def lowered():
        lower, facts = rehearsal.step_program(
            "gpt2-124m-steady", bytes_limit=rehearsal.BYTES_LIMIT, topo=v5e
        )
        assert facts["chips"] == 1
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", lower().as_text())

    def no_tail():
        monkeypatch.setattr(
            trainer_mod.ElasticTrainer, "_reduce_has_tail",
            lambda self: False,
        )

    texts = []
    for prepare in (lambda: None, no_tail):
        prepare()
        texts.append(lowered())
    assert texts[0] == texts[1]
    events = [
        r["attrs"] for r in trace.snapshot_spans()
        if r["name"] == "step.reduce_overlap"
    ]
    assert [e["tail"] for e in events[:1]] == [False]


def test_chip_smoke_incarnations_on_cpu(tmp_path, monkeypatch):
    """The smoke's two incarnations (children of this process, which
    holds no chip) with a tiny ``TransformerConfig`` and the expected
    platform passed as function arguments — the command line exposes
    neither. Everything ``chip_smoke.py`` asserts on the chip except
    the Mosaic call and the memory reading is asserted here too:
    metrics pull, goodput fit, re-optimisation, two step programs (one
    accumulated), exit 143 with a complete manifest, resume at the
    same step / position / batch configuration with the loss inside
    the band, first step of incarnation 1 from a warm cache."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    from adaptdl_tpu.models import TransformerConfig

    # A throw-away cache for the children instead of the checkout's.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("ADAPTDL_COMPILE_CACHE", str(tmp_path / "cc"))
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one CPU device
    tiny = TransformerConfig(
        vocab_size=128, num_layers=1, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=16, dtype=jnp.float32, remat=True,
    )
    device = chip_smoke.run_elastic_loop(
        config=tiny,
        expect_platform="cpu",
        sequences=4096,
        ready_after=52,
        steps=4,
        timeout=300,
    )
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert any((tmp_path / "cc" / ".jax_compile_cache").iterdir())
