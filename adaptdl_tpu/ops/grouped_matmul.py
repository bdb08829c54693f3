"""Grouped matrix products over the experts a chip holds: Pallas TPU
kernels for a dropless mixture-of-experts layer.

The rows of one buffer ``[rows, k]`` belong to groups (one per held
expert), each group multiplied with its own ``[k, n]`` weight. How
many rows a group has is known only at run time; the caller sizes the
buffer (``models.moe``: near the rows it expects, with the worst
case's length as its fall-back) and part of it is usually unused.
Three products, one op family:

- ``x W``     (``moe_gmm``):  ``out[r] = x[r] @ w[group(r)]``
- ``dy W^T``  (``moe_gmm``):  the input gradient, same kernel with the
  weight block turned inside the kernel
- ``x^T dy``  (``moe_tgmm``): the weight gradient of every group,
  accumulated over the group's rows in float32

**Groups are aligned to row tiles** (``models.moe`` lays the rows out
so): a group starts at a multiple of ``tile_rows`` and its tail up to
the next multiple is padding, so a row tile belongs to exactly ONE
expert. A scalar-prefetched ``tile_expert[tile]`` then picks the
weight block of a grid step in its index map, and the kernels are
plain tiled matmuls: no masks, no tile that spans two groups (the
megablox design under ``jax.experimental.pallas.ops.tpu`` handles
spanning tiles with masked partial stores; alignment costs at most
``tile_rows - 1`` padded rows a group instead). ``active_tiles[0]``
says how many leading tiles hold rows: a tile at or past it is never
multiplied, fetches nothing (its index maps repeat the last active
step's blocks) and writes nothing (its output block is the last
active one, left as it stands), so **rows past the active tiles come
back uninitialised**. Callers read only rows they placed.

MXU operands in the input dtype (bf16 in the cells, float32 in the
CPU tests), float32 accumulation. The weight gradient leaves in
float32: it is added to float32 accumulators. Whole ``k`` per grid
step: at the widths this is built for (2048 and 1792) an ``x`` tile
is 2 MiB and stays resident while the ``n`` tiles pass.

Differentiation: :func:`grouped_matmul` is a ``jax.custom_vjp``
whose backward is the other two products. The calls are NAMED
(``GMM_KERNEL_NAME`` / ``TGMM_KERNEL_NAME``), so a device trace has
them as ``%moe_gmm.<n>`` / ``%moe_tgmm.<n>`` the way it has
``%flash_bwd.<n>``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GMM_KERNEL_NAME = "moe_gmm"
TGMM_KERNEL_NAME = "moe_tgmm"
# Rows of one tile at real sizes: 512 rows against a [2048, 256] bf16
# weight block is 512 FLOPs per weight byte fetched, twice what a v5e
# needs to stay compute-bound (197 TFLOP/s over 819 GB/s = 240), and
# costs on average 256 padded rows a group.
TILE_ROWS = 512
_VMEM_LIMIT = 48 * 2**20


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def tile_rows(assignments: int, rows_a_group: float | None = None) -> int:
    """Rows of one tile for a buffer that may hold ``assignments``
    rows: ``TILE_ROWS`` at real sizes, smaller (a power of two, at
    least 8) where the whole buffer is smaller than a few tiles, as in
    the CPU tests. ``rows_a_group``: the rows an even router sends one
    group; at real sizes a tile is halved (not below 128) while that is
    under three quarters of it — a group of 320 rows in tiles of 512
    is 37% padding in every buffer its layer holds, which a row bound
    counts a group (32 groups: 8192 rows of 41 984)."""
    rows = TILE_ROWS
    while rows > 8 and rows * 8 > assignments:
        rows //= 2
    if rows == TILE_ROWS and rows_a_group is not None:
        while rows > 128 and rows_a_group < 0.75 * rows:
            rows //= 2
    return rows


def _col_tile(n: int) -> int:
    """Output columns of one grid step: the widest of 512 / 256 / 128
    that divides ``n``, or ``n`` whole."""
    for cols in (512, 256, 128):
        if n % cols == 0:
            return cols
    return n


def _last_active(i, active_ref):
    """Row tile ``i`` while it is active; past the active tiles, the
    last active one (tile 0 where there is none)."""
    return jnp.minimum(i, jnp.maximum(active_ref[0] - 1, 0))


def _gmm_kernel(
    tile_expert_ref, active_ref, x_ref, w_ref, o_ref, *, transpose_rhs
):
    del tile_expert_ref  # used by the index maps

    @pl.when(pl.program_id(0) < active_ref[0])
    def _tile():
        contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[0], contract,
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)


def _interpret_in_shard_map(x) -> bool:
    """Off the TPU and inside a ``shard_map`` whose axes ``x`` varies
    over: interpret mode (jax 0.9.0) cannot index a scalar-prefetch
    operand that varies over a manual axis (its ``dynamic_slice``
    refuses a varying operand with an unvarying index), so there — the
    CPU tests of a whole trainer step, never the chip — the same
    products are plain einsums over the row tiles."""
    return _use_interpret() and bool(jax.typeof(x).vma)


def _gmm(x, w, tile_expert, active_tiles, *, transpose_rhs: bool):
    """``x``: [rows, k]; ``w``: [experts, k, n] (or [experts, n, k]
    with ``transpose_rhs``) -> [rows, n] in ``x.dtype``."""
    rows, k = x.shape
    if _interpret_in_shard_map(x):
        tiles = tile_expert.shape[0]
        out = jnp.einsum(
            "tmk,tnk->tmn" if transpose_rhs else "tmk,tkn->tmn",
            x.reshape(tiles, rows // tiles, k), w[tile_expert],
            preferred_element_type=jnp.float32,
        )
        return out.reshape(rows, -1).astype(x.dtype)
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tm = rows // tile_expert.shape[0]
    tn = _col_tile(n)
    num_n = n // tn

    def col(i, j, active_ref):
        # Past the active tiles: the last active step's column block.
        return jnp.where(i < active_ref[0], j, num_n - 1)

    def x_index(i, j, te_ref, active_ref):
        return _last_active(i, active_ref), 0

    def w_index(i, j, te_ref, active_ref):
        e = te_ref[_last_active(i, active_ref)]
        c = col(i, j, active_ref)
        return (e, c, 0) if transpose_rhs else (e, 0, c)

    def o_index(i, j, te_ref, active_ref):
        return _last_active(i, active_ref), col(i, j, active_ref)

    w_block = (1, tn, k) if transpose_rhs else (1, k, tn)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tm, num_n),
            in_specs=[
                pl.BlockSpec((tm, k), x_index),
                pl.BlockSpec(w_block, w_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), o_index),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (rows, n), x.dtype, vma=jax.typeof(x).vma | jax.typeof(w).vma
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_use_interpret(),
        name=GMM_KERNEL_NAME,
    )(tile_expert, active_tiles, x, w)


def _tgmm_kernel(tile_expert_ref, active_ref, x_ref, dy_ref, o_ref):
    i = pl.program_id(1)
    active = i < active_ref[0]
    expert = tile_expert_ref[i]
    before = tile_expert_ref[jnp.maximum(i - 1, 0)]
    first_of_group = jnp.logical_or(i == 0, expert != before)

    @pl.when(jnp.logical_and(active, first_of_group))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(active)
    def _tile():
        o_ref[0] += lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _tgmm(x, dy, tile_expert, active_tiles, group_sizes):
    """``x``: [rows, k]; ``dy``: [rows, n] -> float32 [experts, k, n]:
    every group's ``x^T dy``, zero for a group without rows."""
    rows, k = x.shape
    n = dy.shape[1]
    experts = group_sizes.shape[0]
    tm = rows // tile_expert.shape[0]
    tn = _col_tile(n)
    if _interpret_in_shard_map(x):
        tiles = tile_expert.shape[0]
        per_tile = jnp.einsum(
            "tmk,tmn->tkn", x.reshape(tiles, tm, k),
            dy.reshape(tiles, tm, n), preferred_element_type=jnp.float32,
        )
        per_tile = jnp.where(
            (jnp.arange(tiles) < active_tiles[0])[:, None, None],
            per_tile, 0.0,
        )
        return jnp.zeros((experts, k, n), jnp.float32).at[
            tile_expert
        ].add(per_tile)

    def x_index(j, i, te_ref, active_ref):
        return _last_active(i, active_ref), 0

    def dy_index(j, i, te_ref, active_ref):
        return _last_active(i, active_ref), j

    def o_index(j, i, te_ref, active_ref):
        return te_ref[_last_active(i, active_ref)], 0, j

    out = pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # Row tiles innermost: a group's tiles follow each other,
            # so its output block stays put while they accumulate.
            grid=(n // tn, rows // tm),
            in_specs=[
                pl.BlockSpec((tm, k), x_index),
                pl.BlockSpec((tm, tn), dy_index),
            ],
            out_specs=pl.BlockSpec((1, k, tn), o_index),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (experts, k, n), jnp.float32,
            vma=jax.typeof(x).vma | jax.typeof(dy).vma,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_use_interpret(),
        name=TGMM_KERNEL_NAME,
    )(tile_expert, active_tiles, x, dy)
    # A group without rows was never visited: its block is whatever
    # the buffer held.
    return jnp.where(group_sizes[:, None, None] > 0, out, 0.0)


@jax.custom_vjp
def grouped_matmul(x, w, tile_expert, active_tiles, group_sizes):
    """``out[r] = x[r] @ w[tile_expert[r // tile_rows]]`` for the rows
    of the first ``active_tiles[0]`` tiles; rows past them come back
    uninitialised.

    x: [rows, k], rows a multiple of ``len(tile_expert)``;
    w: [experts, k, n], multiplied in ``x.dtype`` (float32
    parameters are rounded here, and their gradient comes back in
    float32 as the kernel accumulated it);
    tile_expert: int32 [tiles], the expert of each row tile (past the
    active tiles: the last active tile's);
    active_tiles: int32 [1]; group_sizes: int32 [experts], the rows
    placed in each group (which groups have a weight gradient).
    """
    return _gmm(
        x, w.astype(x.dtype), tile_expert, active_tiles,
        transpose_rhs=False,
    )


def _grouped_matmul_fwd(x, w, tile_expert, active_tiles, group_sizes):
    out = grouped_matmul(x, w, tile_expert, active_tiles, group_sizes)
    return out, (x, w, tile_expert, active_tiles, group_sizes)


def grouped_matmul_transposes(
    x, w, tile_expert, active_tiles, group_sizes, dy
):
    """The two other products of ``grouped_matmul(x, w, ...)`` for its
    result's cotangent ``dy``: ``(dy W^T [rows, k], x^T dy [experts, k,
    n] in ``w.dtype``)``; what the ``custom_vjp`` runs, for a caller
    that writes its own backward."""
    dx = _gmm(
        dy, w.astype(dy.dtype), tile_expert, active_tiles,
        transpose_rhs=True,
    )
    dw = _tgmm(x, dy, tile_expert, active_tiles, group_sizes)
    return dx, dw.astype(w.dtype)


def _grouped_matmul_bwd(residuals, dy):
    return (*grouped_matmul_transposes(*residuals, dy), None, None, None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
