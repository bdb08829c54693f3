"""Pipeline parallelism: GPipe microbatch scheduling over a mesh axis.

Layers split across a ``"stage"`` mesh axis; activations flow between
neighboring stages with ``lax.ppermute`` (nearest-neighbor hops that
ride ICI) while a ``lax.scan`` advances the schedule — the classic
collective-permute pipeline. With M microbatches and S stages the
schedule runs M + S - 1 ticks; every device runs its stage every tick
(static shapes, no data-dependent control flow), and the bubble is the
usual (S-1)/(M+S-1) fraction.

The reference has no pipeline (or any non-data) parallelism
(SURVEY.md §2.7); this is a capability extension like ring attention.
Autodiff flows through ``ppermute`` (its transpose is the reverse
permute), so the same pipelined callable is used for training inside
the elastic trainer's ``shard_map`` — see
``ElasticTrainer``'s ``stage``-axis support, which treats a stage
group as ONE data-parallel replica whose parameters are sharded (not
replicated) across the group.

Convention: every parameter leaf is STACKED along a leading stage axis
(``stack_stage_params``), sharded ``P("stage")``; inside the manual
shard_map each device sees its own stage's slice with the leading axis
dropped by indexing ``[0]``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from adaptdl_tpu.parallel.mesh import STAGE_AXIS


from adaptdl_tpu.parallel.mesh import stack_params as stack_stage_params  # noqa: E402,F401


def gpipe(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params_local: Any,
    micro_inputs: jnp.ndarray,
    axis_name: str = STAGE_AXIS,
) -> jnp.ndarray:
    """Run the GPipe schedule inside a ``shard_map`` manual over
    ``axis_name``.

    Args:
      stage_fn: one stage's forward, ``stage_fn(params, x) -> y`` with
        ``y.shape == x.shape`` (uniform inter-stage activation shape —
        the transformer-block case).
      stage_params_local: THIS stage's parameters (the ``[0]``-indexed
        slice of the stage-stacked tree).
      micro_inputs: ``[num_micro, micro_batch, ...]`` microbatched
        input, identical on every stage device (only stage 0 consumes
        it).

    Returns:
      ``[num_micro, micro_batch, ...]`` final-stage outputs, valid on
      the LAST stage (other stages hold garbage — combine with a
      ``where``/psum keyed on ``lax.axis_index``).
    """
    stage = lax.axis_index(axis_name)
    num_stages = lax.axis_size(axis_name)
    num_micro = micro_inputs.shape[0]
    ticks = num_micro + num_stages - 1
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    # The handoff carry is stage-varying (each device passes ITS
    # stage's activations), while micro_inputs is replicated across
    # the stage group — pcast the init so the scan carry types line up
    # under shard_map's vma tracking.
    zero_act = lax.pcast(
        micro_inputs[0] * 0.0, axis_name, to="varying"
    )

    def tick(carry, t):  # graftcheck: stage-seq=pipeline-tick
        incoming = carry  # activation handed over by the previous stage
        # Stage 0 feeds microbatch t (clamped; out-of-range ticks
        # compute garbage that the output masking discards).
        feed_idx = jnp.clip(t, 0, num_micro - 1)
        first_in = lax.dynamic_index_in_dim(
            micro_inputs, feed_idx, axis=0, keepdims=False
        )
        x = jnp.where(stage == 0, first_in, incoming)
        y = stage_fn(stage_params_local, x)
        handoff = lax.ppermute(y, axis_name, perm)
        return handoff, y

    _, per_tick = lax.scan(tick, zero_act, jnp.arange(ticks))
    # The last stage emits microbatch m at tick m + (S - 1). Gather
    # those M ticks; correct only on the last stage.
    return lax.dynamic_slice_in_dim(
        per_tick, num_stages - 1, num_micro, axis=0
    )


def stack_interleaved_params(
    chunk_params: list, num_stages: int
) -> Any:
    """Stack v*S chunk param trees (GLOBAL chunk order: chunk g runs
    on device ``g % S``, visit ``g // S``) into leaves shaped
    ``[S, v, ...]`` for ``P(STAGE_AXIS)`` sharding — device d's local
    slice ``[0]`` is ``[v, ...]``, its visit-k chunk at index k."""
    total = len(chunk_params)
    assert total % num_stages == 0, (
        f"{total} chunks do not divide over {num_stages} stages"
    )
    v = total // num_stages
    # Device-major flat order: element d*v + k is device d's visit-k
    # chunk, i.e. global chunk k*num_stages + d.
    device_major = [
        chunk_params[k * num_stages + d]
        for d in range(num_stages)
        for k in range(v)
    ]
    return jax.tree.map(
        lambda *leaves: jnp.stack(
            [
                jnp.stack(leaves[d * v : (d + 1) * v])
                for d in range(num_stages)
            ]
        ),
        *device_major,
    )


def interleaved_pipeline(
    chunk_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    chunks_local: Any,
    micro_inputs: jnp.ndarray,
    axis_name: str = STAGE_AXIS,
) -> jnp.ndarray:
    """Interleaved (circular) pipeline schedule inside a ``shard_map``
    manual over ``axis_name`` — the bubble-reduction schedule
    (Megatron-LM's interleaved stages, arXiv:2104.04473 §2.2, recast
    as an SPMD collective-permute program).

    The model is v*S chunks; device d owns chunks ``d, d+S, ...``
    (leaves of ``chunks_local`` are ``[v, ...]``). Each device runs
    its chunks DEPTH-FIRST — all M microbatches through local chunk k
    before touching chunk k+1 — so the pipeline fill is paid once per
    *chunk-hop* (S-1 small ticks), not once per *stage-pass*:
    total ticks = v*M + S - 1, bubble (S-1)/(v*M + S - 1) versus
    GPipe's (S-1)/(M + S - 1) at the same per-tick work M.

    Timing: device d processes (visit k, microbatch m) at tick
    ``t = k*M + m + d``; its neighbor produced that activation at
    ``t - 1``, so for d >= 1 the ppermute hand-off arrives exactly on
    time. The wrap hop (device S-1 chunk k -> device 0 chunk k+1)
    arrives ``M - S`` ticks early when M > S, so incoming activations
    land in an M-slot buffer carried through the scan, keyed by
    microbatch index (each slot is rewritten once per visit).

    Args:
      chunk_fn: ``chunk_fn(one_chunk_params, x) -> y`` with
        ``y.shape == x.shape`` (uniform activation shape).
      chunks_local: this device's chunk params, leaves ``[v, ...]``.
      micro_inputs: ``[M, micro_batch, ...]`` microbatched input,
        replicated across the stage group.

    Returns:
      ``[M, micro_batch, ...]`` final-chunk outputs, valid on the
      LAST stage device (garbage elsewhere — mask like :func:`gpipe`).

    Requires M >= S (enough microbatches to cover the wrap hop's
    buffering window; the scheduler's topology search respects this).
    """
    stage = lax.axis_index(axis_name)
    num_stages = lax.axis_size(axis_name)
    num_micro = micro_inputs.shape[0]
    if num_micro < num_stages:
        # With M < S the wrap-hop activation lands AFTER its read
        # tick and device 0 would consume garbage silently; both
        # values are static, so fail at trace time.
        raise ValueError(
            f"interleaved pipeline needs num_micro >= num_stages "
            f"(got M={num_micro} < S={num_stages}); use gpipe or "
            "raise the microbatch count"
        )
    v = jax.tree.leaves(chunks_local)[0].shape[0]
    ticks = v * num_micro + num_stages - 1
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    zero_act = lax.pcast(
        micro_inputs[0] * 0.0, axis_name, to="varying"
    )
    # buffer[m] = activation for microbatch m at this device's
    # current visit level; starts as garbage, first written before
    # first read on every device (d >= 1 reads slot m the tick after
    # it lands; d == 0 visit 0 reads micro_inputs instead).
    buffer = jnp.broadcast_to(
        zero_act, (num_micro,) + zero_act.shape
    )

    def tick(carry, t):  # graftcheck: stage-seq=pipeline-tick
        buf, incoming = carry
        # Index of the chunk the ring PREDECESSOR computed last tick —
        # the microbatch slot the incoming activation belongs to
        # (device 0's predecessor is device S-1: t_in = t - S).
        prev = (stage - 1) % num_stages
        t_in = t - 1 - prev
        m_in = t_in % num_micro
        buf = lax.dynamic_update_index_in_dim(
            buf, incoming, m_in, axis=0
        )
        # This device's work item at tick t.
        t_here = t - stage
        k_here = jnp.clip(t_here // num_micro, 0, v - 1)
        m_here = jnp.clip(t_here % num_micro, 0, num_micro - 1)
        first_in = lax.dynamic_index_in_dim(
            micro_inputs, m_here, axis=0, keepdims=False
        )
        buffered = lax.dynamic_index_in_dim(
            buf, m_here, axis=0, keepdims=False
        )
        is_first_chunk = jnp.logical_and(stage == 0, k_here == 0)
        x = jnp.where(is_first_chunk, first_in, buffered)
        params_k = jax.tree.map(
            lambda leaf: lax.dynamic_index_in_dim(
                leaf, k_here, axis=0, keepdims=False
            ),
            chunks_local,
        )
        y = chunk_fn(params_k, x)
        handoff = lax.ppermute(y, axis_name, perm)
        return (buf, handoff), y

    (_, _), per_tick = lax.scan(
        tick, (buffer, zero_act), jnp.arange(ticks)
    )
    # Last device emits microbatch m of the final visit at tick
    # (v-1)*M + m + (S-1); gather those M ticks.
    return lax.dynamic_slice_in_dim(
        per_tick,
        (v - 1) * num_micro + num_stages - 1,
        num_micro,
        axis=0,
    )


def interleaved_loss(
    chunk_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    loss_head: Callable[[jnp.ndarray, Any], jnp.ndarray],
    num_micro: int,
    axis_name: str = STAGE_AXIS,
) -> Callable:
    """ElasticTrainer-compatible loss over the interleaved schedule
    (the ``gpipe_loss`` counterpart; same masking contract)."""

    # Both pipeline flavors must execute the identical (ppermute ×
    # ticks, psum) collective program — a divergence deadlocks the
    # stage group at the first mismatched rendezvous. GC802 compares
    # the transitively flattened sequences of this group.
    def loss_fn(chunks_local, batch, rng):  # graftcheck: stage-seq=pipeline-loss
        del rng
        # Trainer-sharded leaves arrive [1, v, ...] (leading stage
        # axis size 1 locally, the stack_stage_params convention);
        # drop it so chunk leaves are [v, ...].
        chunks_local = jax.tree.map(lambda l: l[0], chunks_local)
        x = batch["x"]
        assert x.shape[0] % num_micro == 0, (
            f"per-replica batch {x.shape[0]} not divisible into "
            f"{num_micro} pipeline microbatches"
        )
        micro = x.reshape((num_micro, -1) + x.shape[1:])
        outs = interleaved_pipeline(
            chunk_fn, chunks_local, micro, axis_name
        )
        final = outs.reshape(x.shape)
        stage = lax.axis_index(axis_name)
        num_stages = lax.axis_size(axis_name)
        is_last = stage == num_stages - 1
        final = jnp.where(is_last, final, jnp.ones_like(final))
        loss = loss_head(final, batch)
        return lax.psum(jnp.where(is_last, loss, 0.0), axis_name)

    return loss_fn


def gpipe_loss(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    loss_head: Callable[[jnp.ndarray, Any], jnp.ndarray],
    num_micro: int,
    axis_name: str = STAGE_AXIS,
) -> Callable:
    """Build an ElasticTrainer-compatible loss over a GPipe pipeline.

    Args:
      stage_fn: one stage's forward (see :func:`gpipe`).
      loss_head: ``loss_head(final_activations, batch) -> scalar`` mean
        loss, evaluated logically on the last stage; ``batch`` is the
        UN-microbatched per-replica batch.
      num_micro: pipeline microbatches per step (static; independent
        of the trainer's gradient-accumulation microbatching).

    Returns:
      ``loss_fn(stage_params_local, batch, rng)`` where ``batch["x"]``
      is ``[per_replica_batch, ...]`` and divisible by ``num_micro``.
    """

    def loss_fn(stage_params_local, batch, rng):  # graftcheck: stage-seq=pipeline-loss
        del rng
        x = batch["x"]
        assert x.shape[0] % num_micro == 0, (
            f"per-replica batch {x.shape[0]} not divisible into "
            f"{num_micro} pipeline microbatches"
        )
        micro = x.reshape((num_micro, -1) + x.shape[1:])
        outs = gpipe(stage_fn, stage_params_local, micro, axis_name)
        final = outs.reshape(x.shape)
        stage = lax.axis_index(axis_name)
        num_stages = lax.axis_size(axis_name)
        is_last = stage == num_stages - 1
        # Non-final stages hold garbage intermediates here. Replace
        # them with ones BEFORE loss_head: a head with a
        # partial-domain op (log, division) would otherwise produce
        # NaN whose cotangent survives the 0-mask below (0 * NaN is
        # NaN) and poisons every stage's gradients.
        final = jnp.where(is_last, final, jnp.ones_like(final))
        loss = loss_head(final, batch)
        # Only the last stage's loss is real; share it with the whole
        # stage group (psum of a masked value == broadcast).
        return lax.psum(jnp.where(is_last, loss, 0.0), axis_name)

    return loss_fn
