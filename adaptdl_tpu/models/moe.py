"""Switch-style mixture-of-experts FFN with expert parallelism.

Experts shard over an ``"expert"`` mesh axis (``E_total / ep`` experts
per device): within a replica group, each device owns an equal slice
of the replica's tokens, routes them top-k with a shared (replicated)
router, exchanges token blocks with the devices that own the chosen
experts via ``lax.all_to_all`` (the GShard dispatch), runs its
experts' FFNs on what arrives, and sends results back. Capacity is
enforced per (source device, expert): overflow tokens pass through
unchanged (the standard Switch residual behavior).

Routing:

- top-1 (Switch) by default: each token goes to its argmax expert at
  the raw router probability.
- ``top_k=2`` (GShard): the two highest-probability experts, gates
  renormalized over the chosen two.
- The Switch **load-balancing auxiliary loss** ``E * sum_e f_e * P_e``
  (f_e = fraction of tokens whose first choice is expert e, P_e = mean
  router probability of e) is returned alongside the output when
  ``return_aux=True`` — without it, real training collapses the router
  onto one expert.

The reference has no expert (or any non-data) parallelism
(SURVEY.md §2.7) — like ring attention and the GPipe stage axis, this
is a TPU-native capability extension. It plugs into the elastic
trainer the same way the stage axis does: expert weights are sharded
leaves (``param_sharding_fn`` returning ``P("expert")``), the router
and any other weights stay replicated (their gradients auto-psum over
the expert axis through shard_map's vma system), and the per-leaf
gradient-norm statistics count each expert shard exactly once.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from adaptdl_tpu import trace
from adaptdl_tpu.ops import grouped_matmul as gmm
from adaptdl_tpu.parallel.mesh import EXPERT_AXIS


from adaptdl_tpu.parallel.mesh import stack_params as stack_expert_params  # noqa: E402,F401


def _routing(x_local, router, num_experts, capacity, top_k=1):
    """Top-k dispatch/combine tensors for one device's token slice.

    Returns (dispatch [s, E, C], combine [s, E, C], aux scalar). The
    aux term is the Switch load-balancing loss over THIS slice; its
    minimum (1.0) is achieved by a uniform router.
    """
    logits = x_local.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [s, E]

    dispatches, gates = [], []
    counts = jnp.zeros((num_experts,), jnp.float32)  # queued per expert
    remaining = probs
    first_choice = None
    for _ in range(top_k):
        expert = jnp.argmax(remaining, axis=-1)  # [s]
        if first_choice is None:
            first_choice = expert
        gate = jnp.max(remaining, axis=-1)
        onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)
        # Position of each token in its expert's queue (per source
        # device), offset by tokens queued in earlier choices.
        position = (
            jnp.einsum("se,se->s", jnp.cumsum(onehot, axis=0) - 1.0, onehot)
            + onehot @ counts
        )
        counts = counts + onehot.sum(axis=0)
        keep = position < capacity
        dispatches.append(
            onehot[:, :, None]
            * jax.nn.one_hot(position.astype(jnp.int32), capacity)[:, None, :]
            * keep[:, None, None]
        )
        gates.append(gate)
        remaining = remaining * (1.0 - onehot)

    if top_k > 1:
        # GShard: gates renormalized over the chosen k.
        denom = sum(gates) + 1e-9
        combine = sum(
            d * (g / denom)[:, None, None]
            for d, g in zip(dispatches, gates)
        )
    else:
        combine = dispatches[0] * gates[0][:, None, None]
    dispatch = sum(dispatches)

    # Switch aux loss: E * sum_e f_e * P_e over this slice.
    f = jnp.mean(
        jax.nn.one_hot(first_choice, num_experts, dtype=jnp.float32),
        axis=0,
    )
    p = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(f * p)
    return dispatch, combine, aux


def _expert_choice_routing(x_local, router, num_experts, capacity):
    """Expert-choice dispatch/combine for one device's token slice
    (Zhou et al. 2022, arXiv:2202.09368): each EXPERT selects its
    top-``capacity`` tokens by router affinity, instead of tokens
    selecting experts. Load balance is structural — every expert
    processes exactly ``capacity`` tokens — so there is no auxiliary
    loss (returned as 0.0); tokens may be picked by several experts or
    none (residual pass-through).

    Returns (dispatch [s, E, C], combine [s, E, C], aux 0.0).
    """
    logits = x_local.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [s, E]
    # Each expert's top-C tokens by affinity.
    gates, token_idx = lax.top_k(probs.T, capacity)  # [E, C] both
    slots = jax.nn.one_hot(
        token_idx, probs.shape[0], dtype=jnp.float32
    )  # [E, C, s]
    dispatch = slots.transpose(2, 0, 1)  # [s, E, C]
    combine = dispatch * gates[None, :, :]  # gate of slot (e, c)
    return dispatch, combine, jnp.zeros(())


def _capacity(
    router_type, capacity_factor, top_k, slice_len, num_experts
):
    """Per-(source slice, expert) token capacity.

    Token-choice scales with top_k (each token queues k times);
    expert-choice does not (every expert takes exactly C tokens) and
    is additionally clamped to the slice length — an expert can never
    select more tokens than the slice holds (lax.top_k would reject
    k > size at trace time)."""
    if router_type == "experts":
        return min(
            max(int(capacity_factor * slice_len / num_experts), 1),
            slice_len,
        )
    if router_type != "tokens":
        raise ValueError(
            f"unknown router_type {router_type!r}: expected "
            "\"tokens\" (Switch/GShard) or \"experts\" "
            "(expert-choice)"
        )
    return max(
        int(capacity_factor * top_k * slice_len / num_experts), 1
    )


def switch_moe(
    params: Any,
    x: jnp.ndarray,
    axis_name: str = EXPERT_AXIS,
    capacity_factor: float = 2.0,
    activation: Callable = jax.nn.gelu,
    top_k: int = 1,
    return_aux: bool = False,
    router_type: str = "tokens",
):
    """Expert-parallel Switch/GShard FFN inside a shard_map manual
    over ``axis_name``.

    Args:
      params: ``{"router": [d, E_total] (replicated), "w_up":
        [k, d, f], "w_down": [k, f, d]}`` — the FFN leaves are THIS
        device's slice of the expert-stacked tree (``k = E_total /
        axis_size`` experts per device; expert ``e`` lives on device
        ``e // k`` at local index ``e % k``).
      x: the replica group's batch ``[n, d]``, identical on every
        device of the group; ``n`` must divide by the axis size. Each
        device processes the slice it owns and the result is
        re-assembled, so the return value is the full ``[n, d]``
        MoE output (identical across the group).
      return_aux: also return the load-balancing auxiliary loss
        (pmean'd over the group — a replicated scalar; identically 0
        for expert-choice routing, where balance is structural).
      router_type: ``"tokens"`` (Switch/GShard token-choice, honors
        ``top_k``) or ``"experts"`` (expert-choice: every expert takes
        its top-capacity tokens — arXiv:2202.09368).
    """
    my_rank = lax.axis_index(axis_name)
    num_devices = lax.axis_size(axis_name)
    local_e = params["w_up"].shape[0]
    num_experts = num_devices * local_e
    assert params["router"].shape[-1] == num_experts, (
        f"router has {params['router'].shape[-1]} experts but the "
        f"sharded tree implies {num_experts}"
    )
    n, dim = x.shape
    assert n % num_devices == 0, (
        f"batch {n} must divide across {num_devices} expert devices"
    )
    slice_len = n // num_devices
    capacity = _capacity(
        router_type, capacity_factor, top_k, slice_len, num_experts
    )

    x_local = lax.dynamic_slice_in_dim(
        x, my_rank * slice_len, slice_len, axis=0
    )  # [s, d]
    if router_type == "experts":
        dispatch, combine, aux = _expert_choice_routing(
            x_local, params["router"], num_experts, capacity
        )
    else:
        dispatch, combine, aux = _routing(
            x_local, params["router"], num_experts, capacity, top_k
        )
    # [E, C, d]: this device's tokens, binned by destination expert,
    # then grouped by destination DEVICE for the exchange.
    sent = jnp.einsum(
        "sec,sd->ecd", dispatch, x_local.astype(jnp.float32)
    )
    sent = sent.reshape(num_devices, local_e, capacity, dim)
    # Exchange: block g goes to device g; afterwards dim 0 indexes the
    # SOURCE device of each [local_e, C, d] block.
    recv = lax.all_to_all(
        sent, axis_name, split_axis=0, concat_axis=0, tiled=True
    )
    # This device's experts, applied to everything that arrived.
    hidden = activation(
        jnp.einsum(
            "gkcd,kdf->gkcf", recv, params["w_up"].astype(jnp.float32)
        )
    )
    expert_out = jnp.einsum(
        "gkcf,kfd->gkcd", hidden, params["w_down"].astype(jnp.float32)
    )
    # Return trip: block from source device g goes back to g.
    returned = lax.all_to_all(
        expert_out, axis_name, split_axis=0, concat_axis=0, tiled=True
    )
    returned = returned.reshape(num_experts, capacity, dim)
    out_local = jnp.einsum("sec,ecd->sd", combine, returned)
    # Overflow/unrouted tokens pass through (combine rows are zero).
    routed = jnp.einsum("sec->s", combine) > 0
    out_local = jnp.where(
        routed[:, None], out_local, x_local.astype(out_local.dtype)
    )
    # Reassemble the replica's full batch; psum of disjoint slices is
    # an all-gather that stays UNvarying over the expert axis, which
    # is what downstream (loss carries, replicated-weight grads)
    # expects.
    full = jnp.zeros((n, dim), out_local.dtype)
    full = lax.dynamic_update_slice_in_dim(
        full, out_local, my_rank * slice_len, axis=0
    )
    out = lax.psum(full, axis_name).astype(x.dtype)
    if return_aux:
        return out, lax.pmean(aux, axis_name)
    return out


def dense_switch_moe(
    router, expert_params_stacked, x, num_slices, capacity_factor=2.0,
    activation: Callable = jax.nn.gelu,
    top_k: int = 1,
    return_aux: bool = False,
    router_type: str = "tokens",
):
    """Single-device reference with IDENTICAL routing math (same
    per-slice capacity binning) — the equivalence target for tests and
    the compute path when no expert mesh axis exists."""
    n, dim = x.shape
    num_experts = expert_params_stacked["w_up"].shape[0]
    slice_len = n // num_slices
    capacity = _capacity(
        router_type, capacity_factor, top_k, slice_len, num_experts
    )
    outs, auxes = [], []
    w_up = expert_params_stacked["w_up"].astype(jnp.float32)
    w_down = expert_params_stacked["w_down"].astype(jnp.float32)
    for s in range(num_slices):
        x_local = x[s * slice_len : (s + 1) * slice_len]
        if router_type == "experts":
            dispatch, combine, aux = _expert_choice_routing(
                x_local, router, num_experts, capacity
            )
        else:
            dispatch, combine, aux = _routing(
                x_local, router, num_experts, capacity, top_k
            )
        sent = jnp.einsum(
            "sec,sd->ecd", dispatch, x_local.astype(jnp.float32)
        )
        hidden = activation(jnp.einsum("ecd,edf->ecf", sent, w_up))
        expert_out = jnp.einsum("ecf,efd->ecd", hidden, w_down)
        out_local = jnp.einsum("sec,ecd->sd", combine, expert_out)
        routed = jnp.einsum("sec->s", combine) > 0
        outs.append(
            jnp.where(
                routed[:, None], out_local, x_local.astype(out_local.dtype)
            )
        )
        auxes.append(aux)
    out = jnp.concatenate(outs, axis=0).astype(x.dtype)
    if return_aux:
        return out, jnp.mean(jnp.stack(auxes))
    return out


# ---- the dropless expert layer: one chip's share of the experts -----
#
# Separate from the capacity-dropping Switch path above and calling
# none of it. The layer is TOLD which experts it holds
# (``first_expert``, ``experts_held`` of ``experts_total``): it routes
# over all of them as published, drops nothing under any imbalance,
# and returns the part of the result its own experts give. With
# ``experts_held == experts_total`` that is the whole layer; with fewer
# it is one chip's share of an expert-parallel deployment — what the
# other chips' experts would add is left out, and nothing here stands
# in for them or for their exchange.


class RowPlan(NamedTuple):
    """Where every (token, choice) assignment to a held expert lies in
    the rows the grouped products run over: groups in expert order,
    each starting at a multiple of the row tile, the first at row 0.
    The row arrays are ``rows_capacity`` long, so that a plan exists
    under any imbalance; where a row lies does not depend on that
    length, so the first ``rows_bound`` rows ARE the plan of a buffer
    of that length whenever ``active_tiles`` lie inside it."""

    dest: Any  # int32 [tokens, top_k]: row of the assignment, or
    # ``rows_capacity`` (past every buffer) for an expert not held here
    row_token: Any  # int32 [rows]: the token a row holds (0: padding)
    row_assignment: Any  # int32 [rows]: token * top_k + choice, or -1
    tile_expert: Any  # int32 [rows / tile]: the held expert of a tile
    active_tiles: Any  # int32 [1]: leading tiles that hold rows
    group_sizes: Any  # int32 [experts_held]: rows placed per expert


def sigmoid_top_k(x, router, bias, top_k: int, eps: float, scale: float):
    """The published router: float32 sigmoid scores over ALL experts;
    the chosen set is the top ``top_k`` of ``score + bias`` (the bias a
    buffer no gradient reaches); the weights are the chosen scores
    WITHOUT the bias, divided by their sum, times ``scale``.

    x: [tokens, d]; router: [d, experts]; bias: [experts] ->
    (experts int32 [tokens, top_k], weights float32 [tokens, top_k]).
    """
    scores = jax.nn.sigmoid(
        jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
    )
    _, experts = lax.top_k(scores + lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + eps) * scale
    return experts, weights


def softmax_top_k(x, router, top_k: int, eps: float, scale: float):
    """The other published router: float32 logits over ALL experts,
    softmax over all of them, the ``top_k`` largest probabilities,
    divided by their sum, times ``scale``; no bias buffer.

    x: [tokens, d]; router: [d, experts] -> (experts int32 [tokens,
    top_k], weights float32 [tokens, top_k]).
    """
    probs = jax.nn.softmax(
        jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        ),
        axis=-1,
    )
    chosen, experts = lax.top_k(probs, top_k)
    weights = chosen / (chosen.sum(-1, keepdims=True) + eps) * scale
    return experts, weights


def rows_capacity(
    tokens: int, top_k: int, experts_held: int, tile: int
) -> int:
    """Rows of the worst case: every assignment a token can make to
    held experts (its choices are distinct experts) plus each group's
    padding to whole tiles, in whole tiles. The plan is this long; the
    usual step walks ``rows_bound`` of its rows."""
    worst = tokens * min(top_k, experts_held) + experts_held * (tile - 1)
    return -(-worst // tile) * tile


# Rows the glue usually walks, as a multiple of the held assignments
# EXPECTED under an even router. On fresh weights both routed benchmark
# cells place 1.0 of them; within its window ``lfm2-8b-a1b-steady``
# drifts to 1.6 (PERF.md section 6, PR 40): 2.5 leaves half as much
# again. A step that places more walks the rest of the plan too and
# drops nothing.
ROWS_BOUND_FACTOR = 2.5
# Where the worst case is at least this many times the usual rows (one
# chip's share of 8 of 256 experts: 9.4 times), a buffer of the worst
# case is mostly rows nobody is ever sent, alive and walked on every
# step (three of 594 MiB a layer, which the compiler refuses beside
# 602 M parameters): there the plan is walked in pieces of
# ``rows_bound`` rows, as many as hold active tiles (the usual step:
# one). Why the one pass stays below it: a share whose router SETTLES
# on its held experts (16 of 128: 91-98% of the assignments within
# twenty steps, 2.8 bounds) would fill three pieces on every step — a
# loop around three times the kernel calls for the rows of one pass,
# each call over a third of the rows, which the accepted reader of
# the grouped products' roofline prices at all of them (PERF.md
# section 7, PR 46). Between 2.8 and 9.4 nothing has been measured —
# but at 2.8 itself a second share has (8 of 64, top 4, under 600 M
# parameters: PERF.md section 6, PR 56): its router, sigmoid at a
# fine-tuning rate, stays near even routing, and the chip's compiler
# refuses the worst case's buffers (1.1 GiB a layer pass) beside its
# state. Which of the two a share is the program cannot observe: the
# threshold is what ``routed_experts`` is told (``pieces_from``,
# ``TransformerConfig.experts_pieces_from``), this its default.
ROWS_PIECES_FROM = 4


def rows_bound(
    tokens: int, top_k: int, experts_held: int, experts_total: int,
    tile: int, pieces_from: float = ROWS_PIECES_FROM,
) -> int:
    """Rows of the buffer a step usually walks: ``ROWS_BOUND_FACTOR``
    times the assignments an even router sends to the held experts,
    plus each group's padding to whole tiles, in whole tiles — where
    the rest of ``rows_capacity`` is no longer than that, else
    ``rows_capacity`` itself (as where every expert is held). A step
    whose active tiles pass the bound walks the rest of the plan in a
    second pass (``expert_rows``), which costs more than one pass over
    all of it would have; where the worst case is several times the
    bound, a router that leaves even routing would pay that on every
    step (one chip's share of 16 of 128 experts does, within twenty
    steps: PERF.md section 6, PR 40), so there the layer keeps the one
    pass — unless the worst case is ``pieces_from`` times the bound or
    more: then the plan (``rows_planned`` long) is walked in pieces of
    the bound, and a router that leaves even routing pays for the
    pieces it fills."""
    capacity = rows_capacity(tokens, top_k, experts_held, tile)
    expected = tokens * top_k * experts_held / experts_total
    usual = math.ceil(ROWS_BOUND_FACTOR * expected) + experts_held * (tile - 1)
    bound = -(-usual // tile) * tile
    if capacity - bound <= bound < capacity:
        return bound
    return bound if capacity >= pieces_from * bound else capacity


def rows_planned(
    tokens: int, top_k: int, experts_held: int, experts_total: int,
    tile: int, pieces_from: float = ROWS_PIECES_FROM,
) -> int:
    """How long the plan's row arrays are: ``rows_capacity``, or,
    where the plan is walked in pieces of ``rows_bound`` rows, the
    next whole number of pieces."""
    capacity = rows_capacity(tokens, top_k, experts_held, tile)
    bound = rows_bound(
        tokens, top_k, experts_held, experts_total, tile, pieces_from
    )
    if capacity - bound <= bound:
        return capacity
    return -(-capacity // bound) * bound


def plan_rows(
    experts, first_expert: int, experts_held: int, tile: int,
    rows: int | None = None,
) -> RowPlan:
    """Order the assignments by held expert. Integer work only: two
    stable sorts of ``tokens * top_k`` keys (the order, and its
    inverse) and a few gathers; no scatter, no one-hot over rows.
    ``rows``: the plan's length where it is more than
    ``rows_capacity`` (``rows_planned``)."""
    tokens, top_k = experts.shape
    count = tokens * top_k
    if rows is None:
        rows = rows_capacity(tokens, top_k, experts_held, tile)
    local = experts.reshape(count) - first_expert
    held = (local >= 0) & (local < experts_held)
    key = jnp.where(held, local, experts_held).astype(jnp.int32)
    ids = jnp.arange(count, dtype=jnp.int32)
    # order[p]: the assignment at sorted position p; rank[a]: the
    # sorted position of assignment a.
    _, order = lax.sort((key, ids), num_keys=1, is_stable=True)
    _, rank = lax.sort((order, ids), num_keys=1, is_stable=True)
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(experts_held)[None, :], axis=0,
        dtype=jnp.int32,
    )
    padded = -(-group_sizes // tile) * tile
    ends = jnp.cumsum(group_sizes)
    padded_ends = jnp.cumsum(padded)
    # A group's rows are shifted right by the padding before it.
    shift = jnp.concatenate(
        [(padded_ends - padded) - (ends - group_sizes),
         jnp.zeros((1,), jnp.int32)]
    )
    dest = jnp.where(held, rank + shift[key], rows).reshape(
        tokens, top_k
    )
    active = padded_ends[-1] // tile
    tile_start = jnp.arange(rows // tile, dtype=jnp.int32) * tile
    tile_expert = jnp.minimum(
        jnp.searchsorted(padded_ends, tile_start, side="right"),
        experts_held - 1,
    ).astype(jnp.int32)
    # Past the active tiles: the last active tile's expert, so that
    # the kernels' index maps stay where they were.
    last = tile_expert[jnp.maximum(active - 1, 0)]
    tile_expert = jnp.where(
        jnp.arange(rows // tile) < active, tile_expert, last
    )
    row = jnp.arange(rows, dtype=jnp.int32)
    row_expert = jnp.repeat(tile_expert, tile)
    position = row - shift[row_expert]
    placed = (row < padded_ends[-1]) & (position < ends[row_expert])
    row_assignment = jnp.where(
        placed, order[jnp.clip(position, 0, count - 1)], -1
    )
    return RowPlan(
        dest=dest,
        row_token=jnp.maximum(row_assignment, 0) // top_k,
        row_assignment=row_assignment,
        tile_expert=tile_expert,
        active_tiles=active.reshape(1).astype(jnp.int32),
        group_sizes=group_sizes,
    )


def _gather_rows(buffer, dest):
    """``buffer[dest]`` with zeros where ``dest`` lies outside the
    buffer: ``dest.shape + buffer.shape[1:]``. A select, not a product:
    rows nobody placed may hold anything."""
    rows = buffer.shape[0]
    taken = buffer[jnp.clip(dest, 0, rows - 1)]
    inside = ((dest >= 0) & (dest < rows)).reshape(
        dest.shape + (1,) * (buffer.ndim - 1)
    )
    return jnp.where(inside, taken, 0)


# Rows back to tokens gathers ``[tokens, top_k, d]`` in one array
# while that is at most this many bytes (the accepted cells: 256 to 576
# MiB); beyond, a choice at a time into a float32 sum (16 384 tokens x
# 10 choices x 2048 in bfloat16 are 640 MiB, 1 GiB with the ten choices
# padded to a sublane tile of sixteen, and the compiler refused the
# step that held it beside 626 M parameters by 882 MiB).
_CHOICES_AT_ONCE_BYTES = 600 * 2**20


def _tokens_from_rows(buffer, dest, weights=None):
    """``sum_j weights[t, j] * buffer[dest[t, j]]`` (``weights`` None:
    ones), rows outside the buffer as zeros: ``[tokens, d]`` in the
    buffer's dtype."""
    tokens, top_k = dest.shape
    size = tokens * top_k * buffer.shape[1] * buffer.dtype.itemsize
    if size <= _CHOICES_AT_ONCE_BYTES:
        taken = _gather_rows(buffer, dest)
        if weights is None:
            return taken.sum(axis=1)
        return jnp.einsum("tjd,tj->td", taken, weights.astype(buffer.dtype))
    total = jnp.zeros((tokens, buffer.shape[1]), jnp.float32)
    for j in range(top_k):
        taken = _gather_rows(buffer, dest[:, j]).astype(jnp.float32)
        if weights is not None:
            taken = taken * weights[:, j, None].astype(jnp.float32)
        total = total + taken
    return total.astype(buffer.dtype)


def _tile(plan: RowPlan) -> int:
    return plan.row_token.shape[0] // plan.tile_expert.shape[0]


def _groups(start, rows: int, plan: RowPlan):
    """The grouped products' view of rows ``start .. start + rows`` of
    the plan (whole tiles): the tiles' experts, how many of them are
    active, and the active tiles each group has there (which groups
    the pass's weight gradient visits)."""
    first, tiles = start // _tile(plan), rows // _tile(plan)
    tile_expert = lax.dynamic_slice_in_dim(plan.tile_expert, first, tiles)
    active = jnp.clip(plan.active_tiles - first, 0, tiles)
    visited = jnp.sum(
        (tile_expert[:, None] == jnp.arange(plan.group_sizes.shape[0]))
        & (jnp.arange(tiles) < active[0])[:, None],
        axis=0, dtype=jnp.int32,
    )
    return tile_expert, active, visited


# What a gated expert puts its gate's product through before it
# multiplies the up product: SwiGLU's or ReGLU's (``routed_experts``:
# ``activation``). Said once: the forward, the backward and the forward
# run again in the backward all read it here.
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _forward_rows(
    start, rows: int, activation: str, x, weights, w_gate, w_up, w_down, plan
):
    """The held experts over rows ``start .. start + rows`` of the
    plan: tokens into the rows (``x[row_token]``, a gather), the three
    grouped products, and rows back to tokens, ``y[t] = sum_j
    weights[t, j] * y_rows[dest[t, j]]`` over the choices whose row
    lies there. Returns ``(out, residuals)``, the residuals all
    ``[rows, .]``; ``out`` is ``y``, or under "relu" ``(y,
    hidden_zero)``: the exact zeros among the gated hidden values of
    the rows PLACED there (int32; one compare-and-sum beside the
    gate's elementwise pass)."""
    groups = _groups(start, rows, plan)
    taken = x[lax.dynamic_slice_in_dim(plan.row_token, start, rows)]
    gate = gmm.grouped_matmul(taken, w_gate, *groups)
    up = gmm.grouped_matmul(taken, w_up, *groups)
    hidden = ACTIVATIONS[activation](gate) * up
    y_rows = gmm.grouped_matmul(hidden, w_down, *groups)
    out = _tokens_from_rows(y_rows, plan.dest - start, weights)
    if activation == "relu":
        assignment = lax.dynamic_slice_in_dim(plan.row_assignment, start, rows)
        zero = (hidden == 0) & (assignment >= 0)[:, None]
        out = out, jnp.sum(zero, dtype=jnp.int32)
    return out, (taken, gate, up, hidden, y_rows)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _backward_rows(
    start, rows: int, activation: str, residuals, d_y, x, weights, w_gate,
    w_up, w_down, plan,
):
    """The transpose of ``_forward_rows`` over the same rows; every
    direction is a gather. The combine's weight gradient is taken on
    the row side: ``d_y[row_token]`` is gathered once, for the rows'
    cotangent, and ``<y_rows[r], d_y[row_token[r]]>`` in float32 beside
    it; a token reads its ``top_k`` scalars back by ``dest``."""
    taken, gate, up, hidden, y_rows = residuals
    groups = _groups(start, rows, plan)
    dest = plan.dest - start
    assignment = lax.dynamic_slice_in_dim(plan.row_assignment, start, rows)
    row_weight = jnp.where(
        assignment >= 0, weights.reshape(-1)[jnp.maximum(assignment, 0)], 0.0
    )
    d_y_taken = d_y[lax.dynamic_slice_in_dim(plan.row_token, start, rows)]
    d_y_rows = d_y_taken * row_weight.astype(d_y.dtype)[:, None]
    d_weights = _gather_rows(
        jnp.einsum(
            "rd,rd->r", y_rows, d_y_taken,
            preferred_element_type=jnp.float32,
        ),
        dest,
    )
    d_hidden, d_w_down = gmm.grouped_matmul_transposes(
        hidden, w_down, *groups, d_y_rows
    )
    _, gated = jax.vjp(lambda g, u: ACTIVATIONS[activation](g) * u, gate, up)
    d_gate, d_up = gated(d_hidden)
    d_taken_gate, d_w_gate = gmm.grouped_matmul_transposes(
        taken, w_gate, *groups, d_gate
    )
    d_taken_up, d_w_up = gmm.grouped_matmul_transposes(
        taken, w_up, *groups, d_up
    )
    d_x = _tokens_from_rows(d_taken_gate + d_taken_up, dest)
    return d_x, d_weights.astype(weights.dtype), d_w_gate, d_w_up, d_w_down


def _past_the_bound(bound: int, plan: RowPlan, first, rest):
    """``first`` (what the plan's first ``bound`` rows gave) plus
    ``rest(bound, rows)`` (what its other ``rows`` rows give) where an
    active tile lies among those: in the usual step it does not, and
    ``first`` is handed through untouched.

    A ``while_loop`` that runs once or not at all, in place of a
    ``cond``: the TPU compiler gives the two branches of a ``cond``
    memory of their own each and copies what one of them only passes
    on, where a loop's state is updated in place. Where the other rows
    are more than ``bound`` (``rows_planned``: then a whole number of
    pieces), the loop takes them ``bound`` at a time while active
    tiles lie ahead."""
    rows = plan.row_token.shape[0]
    if bound == rows:
        return first
    rows_active = plan.active_tiles[0] * _tile(plan)
    piece = rows - bound if rows - bound <= bound else bound
    assert (rows - bound) % piece == 0

    def add_the_rest(state):
        start, total = state
        return start + piece, jax.tree.map(
            jnp.add, total, rest(start, piece)
        )

    return lax.while_loop(
        lambda state: state[0] < rows_active, add_the_rest,
        (jnp.int32(bound), first),
    )[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def expert_rows(
    bound: int, activation: str, x, weights, w_gate, w_up, w_down, plan
):
    """The held experts' part of the layer for a router's ``weights``
    and their ``plan``: ``_forward_rows`` over the first ``bound`` rows
    and, only where an active tile lies past them, over the plan's
    other rows as well — nothing is dropped, and no more is alive at
    once than one buffer of ``rows_capacity`` rows held. Everything
    XLA generates around the kernels has static shapes and walks every
    row it is given (the kernels skip inactive tiles by themselves), so
    the usual step is given ``bound`` rows; they are the same rows in
    the same tiles as in a buffer of any length, so the same bits.
    Under ``activation`` "relu" the result is ``(y, hidden_zero)``
    (``_forward_rows``), the count summed over the rows walked.

    One ``custom_vjp`` for the whole section: differentiated, either
    kind of control flow would write zeros in the shapes of the path
    not taken. The forward rule hands over the first rows' residuals;
    the forward of the rest, where it ran, runs again in the
    backward."""
    return _expert_rows_fwd(
        bound, activation, x, weights, w_gate, w_up, w_down, plan
    )[0]


def _expert_rows_fwd(bound: int, activation: str, *operands):
    out, residuals = _forward_rows(0, bound, activation, *operands)
    out = _past_the_bound(
        bound, operands[-1], out,
        lambda start, rows: _forward_rows(
            start, rows, activation, *operands
        )[0],
    )
    return out, (residuals, operands)


def _expert_rows_bwd(bound: int, activation: str, saved, d_out):
    residuals, operands = saved
    # (The count's cotangent is no number: an integer's.)
    d_y = d_out[0] if activation == "relu" else d_out

    def rest(start, rows):
        _, again = _forward_rows(start, rows, activation, *operands)
        return _backward_rows(start, rows, activation, again, d_y, *operands)

    grads = _past_the_bound(
        bound, operands[-1],
        _backward_rows(0, bound, activation, residuals, d_y, *operands), rest,
    )
    return (*grads, None)


expert_rows.defvjp(_expert_rows_fwd, _expert_rows_bwd)


def routed_experts(
    x,
    router,
    bias,
    w_gate,
    w_up,
    w_down,
    *,
    experts_total: int,
    first_expert: int,
    top_k: int,
    norm_eps: float = 1e-20,
    scale: float = 1.0,
    router_kind: str = "sigmoid",
    shared_gate: str = "none",
    pieces_from: float | None = None,
    routed_on=None,
    activation: str = "silu",
):
    """One chip's share of a dropless top-k expert layer.

    x: [tokens, d] in the compute dtype; router: [d, experts_total];
    bias: [experts_total] (``router_kind`` "sigmoid": ``sigmoid_top_k``)
    or None ("softmax": ``softmax_top_k``); w_gate / w_up: [experts_held, d, f];
    w_down: [experts_held, f, d] — the held experts are
    ``first_expert .. first_expert + experts_held``. Returns
    ``(y [tokens, d], load)``: ``y[t] = sum over chosen AND held e of
    weight_e * w_down[e] (act(w_gate[e] x) * (w_up[e] x))``, and
    ``load`` the int32 counters ``held_rows [experts_held]``,
    ``left_out`` (assignments to experts not held here) and
    ``dropped`` (assignments to held experts that found no row among
    those walked: 0 by construction, counted, not assumed),
    ``rows_active`` (the plan's active tiles in rows: what has to fit
    ``rows_bound``), ``rows_walked`` (rows the glue passed over:
    ``rows_bound``, or ``rows_capacity`` where the plan did not fit,
    or the pieces of ``rows_bound`` rows it took)
    and ``fell_back`` (1 where it did not), beside the router's own
    result, ``experts`` and ``weights`` ``[tokens,
    top_k]``, for whoever checks the routing itself. ``shared_gate``:
    what the caller multiplies its shared expert by ("sigmoid", or
    "none": no gate, or no shared expert), said in ``moe.schedule``.
    ``pieces_from``: from how many bounds of worst case on the plan is
    walked in pieces of ``rows_bound`` rows (``rows_bound``; None:
    ``ROWS_PIECES_FROM``). ``routed_on`` [tokens, d]: what the router
    reads where that is not ``x``, what the experts multiply (a block
    whose router stands on its INPUT, ahead of the mixer): the scores,
    the choice and the weights come from it, and the router's gradient
    goes to it and to the ``router`` leaf, none of it to ``x``.
    ``activation``: a held expert's gate function, "silu" or "relu"
    (``ACTIVATIONS``); under "relu" ``load`` gains ``hidden_zero``, the
    exact zeros among the ``held_rows.sum() x f`` gated hidden values
    of the rows placed.
    """
    if activation not in ACTIVATIONS:
        raise ValueError(
            f"activation must be one of {sorted(ACTIVATIONS)}, got "
            f"{activation!r}"
        )
    if pieces_from is None:
        pieces_from = ROWS_PIECES_FROM
    tokens, _ = x.shape
    experts_held = w_gate.shape[0]
    assert router.shape[1] == experts_total
    if router_kind not in ("sigmoid", "softmax"):
        raise ValueError(
            f"router_kind must be 'sigmoid' or 'softmax', got "
            f"{router_kind!r}"
        )
    assert (bias is None) == (router_kind == "softmax")
    assert 0 <= first_expert <= experts_total - experts_held
    tile = gmm.tile_rows(
        tokens * min(top_k, experts_held), tokens * top_k / experts_total
    )
    capacity = rows_planned(
        tokens, top_k, experts_held, experts_total, tile, pieces_from
    )
    bound = rows_bound(
        tokens, top_k, experts_held, experts_total, tile, pieces_from
    )
    trace.event(
        "moe.schedule",
        experts_total=experts_total,
        experts_held=experts_held,
        first_expert=first_expert,
        top_k=top_k,
        tokens=tokens,
        rows_capacity=capacity,
        rows_bound=bound,
        tile_rows=tile,
        d_model=x.shape[1],
        d_expert=w_gate.shape[2],
        dtype=x.dtype.name,
        product="pallas:" + gmm.GMM_KERNEL_NAME + "," + gmm.TGMM_KERNEL_NAME,
        router=router_kind,
        shared_gate=shared_gate,
        routed_on="ffn_input" if routed_on is None else "block_input",
        activation=activation,
    )
    read = x if routed_on is None else routed_on
    assert read.shape == x.shape, (read.shape, x.shape)
    if router_kind == "softmax":
        experts, weights = softmax_top_k(read, router, top_k, norm_eps, scale)
    else:
        experts, weights = sigmoid_top_k(
            read, router, bias, top_k, norm_eps, scale
        )
    plan = lax.stop_gradient(
        plan_rows(experts, first_expert, experts_held, tile, capacity)
    )
    y = expert_rows(bound, activation, x, weights, w_gate, w_up, w_down, plan)
    if activation == "relu":
        y, hidden_zero = y
    # Counted from the plan and the rows the step walked, not assumed.
    rows_active = (plan.active_tiles[0] * tile).astype(jnp.int32)
    if capacity - bound > bound:  # walked in pieces of ``bound`` rows
        walked = jnp.maximum(-(-rows_active // bound), 1) * bound
    else:
        walked = jnp.where(rows_active > bound, capacity, bound)
    held = jnp.sum(plan.dest < capacity, dtype=jnp.int32)
    placed = jnp.sum(
        (plan.row_assignment >= 0) & (jnp.arange(capacity) < walked),
        dtype=jnp.int32,
    )
    load = {
        "held_rows": plan.group_sizes,
        "left_out": jnp.int32(tokens * top_k) - held,
        "dropped": held - placed,
        "rows_active": rows_active,
        "rows_walked": walked,
        "fell_back": (walked > bound).astype(jnp.int32),
        "experts": experts,
        "weights": lax.stop_gradient(weights),
    }
    if activation == "relu":
        load["hidden_zero"] = hidden_zero
    return y, load
