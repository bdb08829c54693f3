"""Per-layer ZeRO-3 / FSDP: block-wise parameter gather inside a scan.

The TPU-native answer to FSDP's FlatParameter + per-module all-gather
(reference analog: none — the reference is pure DDP; this is a
beyond-reference capability, like the pipeline/expert axes). Design:

- **Storage** is flat rows over the data axis, PER BLOCK: a stacked
  ``[L, dp, shard_b]`` array for the L homogeneous transformer blocks
  plus one ``[dp, shard_o]`` row set for everything else (embeddings,
  norms, head). Each device persistently holds 1/dp of every tensor —
  the ZeRO-3 storage bound.
- **Gather rides the AD transpose.** The model scans over the L block
  rows; the scan body gathers ONE block's parameters (scatter +
  ``psum`` over the data axis — the all-gather), applies the block,
  and returns. Under ``jax.checkpoint`` the gathered block is not
  saved for the backward pass: the backward scan re-gathers it (the
  FSDP backward all-gather) and the cotangent flows through the
  gather's transpose — ``pcast``-to-varying transposes to ``psum``,
  and the scatter transposes to a rank slice, so each device receives
  the *globally summed* gradient of exactly its own row: a
  reduce-scatter, for free, per block, per microbatch.
- **Peak HBM** per device is therefore params/dp (rows) + ONE block's
  gathered parameters + activations — not the whole tree the
  ``zero3=True`` lite mode materialises at step start.

The trainer side (storage layout, optimizer-on-rows update, GNS on
row-space gradients) lives in :mod:`adaptdl_tpu.trainer` under
``zero3_blocks=...``; this module holds the pieces a MODEL needs to
write its loss against the row view, plus the layout conversions.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from adaptdl_tpu.parallel.mesh import DATA_AXIS


class Zero3View(NamedTuple):
    """What a ``zero3_blocks`` loss_fn receives instead of the param
    tree: the non-block subtree fully assembled (it is needed at both
    ends of the network and is small next to the block stack), and the
    block parameters still as this device's ``[L, 1, shard_b]`` rows —
    to be gathered one block at a time inside the model's layer scan
    via :func:`gather_block`."""

    other: Any  # assembled non-block param tree (data-varying)
    blocks: jnp.ndarray  # [L, 1, shard_b] local rows (data-varying)


class BlockSpec(NamedTuple):
    """Static layout facts for one zero3-blocks parameter family,
    derived from the user's param-tree template (dp-independent except
    for the two shard widths)."""

    num_blocks: int
    n_block: int  # true (unpadded) params per block
    n_other: int  # true params in the non-block subtree
    unravel_block: Callable[[jnp.ndarray], Any]
    unravel_other: Callable[[jnp.ndarray], Any]


def block_spec(params: Any, blocks_key: str) -> BlockSpec:
    """Layout facts from a params tree whose ``blocks_key`` entry holds
    ``[L, ...]`` layer-stacked leaves (the convention
    ``models/pipeline_lm.py`` established for chunk scans)."""
    blocks = params[blocks_key]
    leaves = jax.tree.leaves(blocks)
    if not leaves:
        raise ValueError(f"params[{blocks_key!r}] has no leaves")
    num_blocks = int(leaves[0].shape[0])
    for leaf in leaves:
        if leaf.shape[0] != num_blocks:
            raise ValueError(
                "zero3_blocks leaves must share the leading layer "
                f"dim; got {leaf.shape[0]} vs {num_blocks}"
            )
    one_block = jax.tree.map(lambda leaf: leaf[0], blocks)
    flat_b, unravel_b = ravel_pytree(one_block)
    other = {k: v for k, v in params.items() if k != blocks_key}
    flat_o, unravel_o = ravel_pytree(other)
    return BlockSpec(
        num_blocks=num_blocks,
        n_block=int(flat_b.size),
        n_other=int(flat_o.size),
        unravel_block=unravel_b,
        unravel_other=unravel_o,
    )


def gather_rows(
    row_local: jnp.ndarray, n: int, axis: str = DATA_AXIS
) -> jnp.ndarray:
    """This device's ``[1, shard]`` row -> the full ``[n]`` flat vector
    (typed VARYING over ``axis``). A true tiled ``all_gather`` — ring
    traffic (dp-1)/dp * n per device — whose AD transpose is
    ``psum_scatter``: each device receives exactly its own row of the
    globally summed cotangent, again at ring cost. (The zero1/lite
    trainer paths use a scatter+psum instead because they need an
    axis-INVARIANT result; here every consumer wants varying anyway —
    the view is differentiated per-device — so the all_gather halves
    the collective bytes in both directions.) ``n`` trims the
    dp-alignment padding and must be static."""
    full = jax.lax.all_gather(
        row_local.reshape(-1), axis, tiled=True
    )
    return full[:n]


def gather_block(
    row_local: jnp.ndarray,
    spec: BlockSpec,
    axis: str = DATA_AXIS,
    varying_axes=None,
) -> Any:
    """One block's local ``[1, shard_b]`` row -> that block's full
    parameter tree, typed varying so gradients stay per-device until
    the transpose's reduce-scatter. Call INSIDE the layer scan body
    (wrapped in ``jax.checkpoint`` so the gathered tree is re-gathered,
    not saved, for backward).

    ``varying_axes`` (default: just the gather axis) is the full
    varying set the MODEL runs under — with sequence parallelism the
    gathered block must additionally vary over the seq axis, and that
    pcast's transpose auto-psums the seq shards' cotangents before the
    all_gather transpose reduce-scatters over data."""
    tree = spec.unravel_block(gather_rows(row_local, spec.n_block, axis))
    return _ensure_varying(
        tree, varying_axes if varying_axes is not None else axis
    )


def _ensure_varying(tree: Any, axes) -> Any:
    """pcast leaves to varying over ``axes`` (a name or tuple of
    names) unless they already are — the scan carry below must have a
    stable vma type, and callers legitimately pass either (an
    axis-invariant embedding output, or a batch-sharded activation
    that is already varying)."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)

    def cast(leaf):
        missing = tuple(
            a for a in axes if a not in jax.typeof(leaf).vma
        )
        if not missing:
            return leaf
        return jax.lax.pcast(leaf, missing, to="varying")

    return jax.tree.map(cast, tree)


def scan_blocks(
    block_fn: Callable[[Any, Any], Any],
    blocks_rows: jnp.ndarray,
    x: Any,
    spec: BlockSpec,
    axis: str = DATA_AXIS,
    unroll: int = 1,
    varying_axes=None,
):
    """Apply L blocks to ``x`` with per-block gather: the canonical
    zero3-blocks layer stack. ``block_fn(block_params, x) -> x``.
    ``varying_axes``: the model's full varying set when it runs under
    more axes than the gather axis (sequence parallelism).
    The body is checkpointed: backward re-gathers each block and
    reduce-scatters its gradient — FSDP's exact communication
    schedule, produced by AD instead of hooks.

    ``unroll``: iterations unrolled per loop step (forwarded to
    ``lax.scan``). At 1, each gather serializes before its block's
    compute (the loop boundary bars cross-iteration scheduling). At
    2+, consecutive block bodies share one loop body, so XLA's
    latency-hiding scheduler can start block i+1's all-gather while
    block i's matmuls run — FSDP's prefetch-next-shard overlap,
    produced by the compiler instead of CUDA streams. Peak memory
    grows by one extra gathered block per unroll step; the remat
    (re-gather on backward) semantics are unchanged.

    ``x`` may be axis-invariant (e.g. computed from replicated inputs)
    or varying; the carry is pcast to varying either way because the
    body's output — built from the varying gathered block — is varying,
    and ``lax.scan`` requires carry-in and carry-out types to match."""

    def body(h, row):
        params_b = gather_block(row, spec, axis, varying_axes)
        return block_fn(params_b, h), None

    axes = varying_axes if varying_axes is not None else axis
    x = _ensure_varying(x, axes)
    out, _ = jax.lax.scan(
        jax.checkpoint(body), x, blocks_rows, unroll=unroll
    )
    return out


def build_view(
    blocks_rows_local: jnp.ndarray,
    other_rows_local: jnp.ndarray,
    spec: BlockSpec,
    axis: str = DATA_AXIS,
    varying_axes=None,
) -> Zero3View:
    """Inside the manual step: this device's local rows -> the
    :class:`Zero3View` a zero3-blocks loss_fn consumes. The non-block
    subtree is assembled here (needed at both ends of the network,
    small next to the block stack); block rows pass through untouched
    for :func:`scan_blocks`/:func:`gather_block` to gather one layer at
    a time. Differentiating a loss through this view hands back
    cotangents in ROW layout, already reduce-scattered (globally
    summed) through the gathers' AD transposes."""
    axes = varying_axes if varying_axes is not None else axis
    other = spec.unravel_other(
        gather_rows(other_rows_local, spec.n_other, axis)
    )
    return Zero3View(
        # The assembled values carry the model's FULL varying set (the
        # +seq pcast's transpose is the seq-shard gradient psum)...
        other=_ensure_varying(other, axes),
        # ...but the block ROWS stay varying over the gather axis
        # only: their cotangents must come back seq-INVARIANT (the
        # storage and optimizer rows are replicated across seq), which
        # they do because gather_block applies the +seq cast after the
        # gather, inside the scan body.
        blocks=_ensure_varying(blocks_rows_local, axis),
    )


def assemble_tree(
    blocks_rows_local: jnp.ndarray,
    other_rows_local: jnp.ndarray,
    blocks_key: str,
    spec: BlockSpec,
    axis: str = DATA_AXIS,
) -> Any:
    """Inside the manual step: local rows -> the FULL canonical param
    tree (materializes every block at once — evaluation/export helper,
    not the training path, which gathers per block)."""
    other = spec.unravel_other(
        gather_rows(other_rows_local, spec.n_other, axis)
    )
    blocks_flat = jax.vmap(
        lambda row: gather_rows(row, spec.n_block, axis)
    )(blocks_rows_local)
    blocks = jax.vmap(spec.unravel_block)(blocks_flat)
    return {**other, blocks_key: blocks}


# ---- layout conversions (trainer + checkpoint side) ----------------------


def shard_sizes(spec: BlockSpec, dp: int) -> tuple[int, int]:
    """(shard_b, shard_o): per-device row widths at ``dp`` replicas."""
    return (
        (spec.n_block + (-spec.n_block) % dp) // dp,
        (spec.n_other + (-spec.n_other) % dp) // dp,
    )


def tree_to_rows(params: Any, blocks_key: str, spec: BlockSpec, dp: int):
    """Param tree -> ``(blocks_rows [L, dp, shard_b], other_rows
    [dp, shard_o])``. Traceable (jit-friendly for born-sharded init)."""
    shard_b, shard_o = shard_sizes(spec, dp)

    def ravel_layer(one_block):
        flat, _ = ravel_pytree(one_block)
        return jnp.pad(flat, (0, dp * shard_b - spec.n_block))

    blocks_flat = jax.vmap(ravel_layer)(params[blocks_key])
    blocks_rows = blocks_flat.reshape(spec.num_blocks, dp, shard_b)
    other = {k: v for k, v in params.items() if k != blocks_key}
    flat_o, _ = ravel_pytree(other)
    other_rows = jnp.pad(
        flat_o, (0, dp * shard_o - spec.n_other)
    ).reshape(dp, shard_o)
    return blocks_rows, other_rows


def rows_to_tree(
    blocks_rows, other_rows, blocks_key: str, spec: BlockSpec
) -> Any:
    """Inverse of :func:`tree_to_rows` (traceable): the canonical,
    dp-independent param TREE a checkpoint stores."""
    blocks = jax.vmap(
        lambda row: spec.unravel_block(
            row.reshape(-1)[: spec.n_block]
        )
    )(blocks_rows)
    other = spec.unravel_other(
        other_rows.reshape(-1)[: spec.n_other]
    )
    return {**other, blocks_key: blocks}


def rows_to_flat_canonical(
    blocks_rows, other_rows, blocks_key: str, spec: BlockSpec
) -> np.ndarray | jnp.ndarray:
    """Row layout -> the ``[n]`` flat vector in ``ravel_pytree(tree)``
    order — the SAME canonical layout zero1/zero3-lite checkpoints use
    for optimizer moments, so rescales may change dp freely and even
    cross between the lite and blocks storage modes."""
    flat, _ = ravel_pytree(
        rows_to_tree(blocks_rows, other_rows, blocks_key, spec)
    )
    return flat


def flat_canonical_to_rows(
    flat, blocks_key: str, spec: BlockSpec, dp: int, unravel_full
):
    """Canonical ``[n]`` vector (tree ravel order) -> row layout for a
    ``dp``-replica incarnation. ``unravel_full`` is the full param
    tree's ravel_pytree inverse."""
    tree = unravel_full(jnp.asarray(flat))
    return tree_to_rows(tree, blocks_key, spec, dp)
