"""The flagship transformer LM under pipeline parallelism.

Builds an :class:`~adaptdl_tpu.trainer.ElasticTrainer`-ready
(loss_fn, params) pair that runs the TransformerLM block stack through
the GPipe or interleaved collective-permute schedule
(``adaptdl_tpu.parallel.pipeline``) over a ``dp x stage`` mesh — the
piece that turns pipeline parallelism from a toy-MLP capability into a
model-zoo one. (The reference has no pipeline axis at all, SURVEY.md
§2.7; its transformer example is pure DP,
examples/transformer/main.py.)

Layout decisions (TPU-first):

- **Blocks are the pipeline.** Only the uniform-[batch, seq, d_model]
  transformer blocks are staged; embedding, final LayerNorm, and the
  tied LM head are *replicated* across the stage group and computed
  redundantly. That keeps the inter-stage activation shape uniform
  (the collective-permute schedule's requirement) and the redundant
  work is O(vocab·d) per device — noise next to the block stack at
  pipeline-worthy depths.
- **Chunks scan their layers.** A chunk's ``layers_per_chunk`` block
  applications run as a ``lax.scan`` over layer-stacked params: one
  trace regardless of depth, XLA-friendly.
- **Params carry the schedule.** ``blocks`` leaves are stacked
  ``[S, layers_per_chunk, ...]`` (GPipe) or ``[S, v, layers_per_chunk,
  ...]`` (interleaved), sharded ``P("stage")`` by
  :func:`pipeline_lm_sharding_fn`; embed/head/ln_f leaves replicate.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from adaptdl_tpu.models.transformer import (
    TransformerConfig,
    block_remat,
)
from adaptdl_tpu.parallel.mesh import STAGE_AXIS
from adaptdl_tpu.parallel.pipeline import (
    gpipe,
    interleaved_pipeline,
    stack_interleaved_params,
    stack_stage_params,
)


def _map_params_like(tree, fn, match=None):
    """Apply ``fn`` to every subtree that ``match`` recognizes as a
    params dict anywhere in a TrainState — params themselves,
    optimizer moments (mu/nu), and any other params-shaped mirror all
    get the same restacking. Default match: the pipeline-LM layout
    (keys exactly {embed, ln_f, blocks})."""
    if match is None:
        keys = {"embed", "ln_f", "blocks"}

        def match(node):  # noqa: F811
            return set(node.keys()) == keys

    def walk(node):
        if isinstance(node, dict):
            if match(node):
                return fn(node)
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, tuple):
            vals = [walk(v) for v in node]
            if hasattr(node, "_fields"):  # NamedTuple
                return type(node)(*vals)
            return tuple(vals)
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    return walk(tree)


def _to_layer_major(leaf, num_stages: int, interleave: int):
    """[S, (v,) lpc, ...] -> [num_layers, ...] in global layer order
    (layer l = (k*S + d) * lpc + i lives at [d, (k,) i])."""
    import numpy as _np

    if interleave > 1:
        s, v, lpc = leaf.shape[:3]
        # [d, k, i] -> order (k, d, i)
        arranged = _np.transpose(
            leaf, (1, 0, 2) + tuple(range(3, leaf.ndim))
        )
        return arranged.reshape((s * v * lpc,) + leaf.shape[3:])
    s, lpc = leaf.shape[:2]
    return leaf.reshape((s * lpc,) + leaf.shape[2:])


def _from_layer_major(leaf, num_stages: int, interleave: int):
    """Inverse of :func:`_to_layer_major` for the new topology."""
    import numpy as _np

    num_layers = leaf.shape[0]
    lpc = num_layers // (num_stages * interleave)
    if interleave > 1:
        shaped = leaf.reshape(
            (interleave, num_stages, lpc) + leaf.shape[1:]
        )
        return _np.transpose(
            shaped, (1, 0, 2) + tuple(range(3, shaped.ndim))
        )
    return leaf.reshape((num_stages, lpc) + leaf.shape[1:])


def pipeline_checkpoint_transforms(num_stages: int, interleave: int = 1):
    """(transform_save, transform_load) for
    ``ElasticTrainer.make_checkpoint_state``: block leaves are stored
    layer-major on disk (topology-independent) and restacked for the
    RUN's (num_stages, interleave) on load — so the scheduler can
    change the stage factorization between restarts and the job
    restores weights AND optimizer moments (reference has no
    structure-changing rescale at all; its checkpoints are plain
    state_dicts, adaptdl/torch/checkpoint).
    """

    def save(host_state):
        return _map_params_like(
            host_state,
            lambda p: {
                **p,
                "blocks": jax.tree.map(
                    lambda leaf: _to_layer_major(
                        leaf, num_stages, interleave
                    ),
                    p["blocks"],
                ),
            },
        )

    def load(host_state):
        return _map_params_like(
            host_state,
            lambda p: {
                **p,
                "blocks": jax.tree.map(
                    lambda leaf: _from_layer_major(
                        leaf, num_stages, interleave
                    ),
                    p["blocks"],
                ),
            },
        )

    return save, load


def dense_lm_checkpoint_transforms(num_layers: int):
    """(transform_save, transform_load) for the PLAIN (non-pipelined)
    :class:`TransformerLM` — the other half of structure-changing
    rescale. Both the dense and the pipelined builds persist the SAME
    canonical layout ({embed, ln_f, blocks layer-major}), so the
    scheduler can move a job between ss = 1 and ss > 1 across restarts
    and either incarnation restores the other's checkpoint (weights
    and optimizer moments). Only valid for homogeneous block stacks
    (no MoE-every-n: heterogeneous layer trees cannot stack)."""

    def is_dense(node):
        return (
            "embed" in node
            and "LayerNorm_0" in node
            and sum(1 for k in node if k.startswith("layer_"))
            == num_layers
            and len(node) == num_layers + 2
        )

    def to_canonical(p):
        layers = [p[f"layer_{i}"] for i in range(num_layers)]
        import numpy as _np

        return {
            "embed": p["embed"],
            "ln_f": p["LayerNorm_0"],
            "blocks": jax.tree.map(
                lambda *ls: _np.stack(ls), *layers
            ),
        }

    def from_canonical(p):
        out = {"embed": p["embed"], "LayerNorm_0": p["ln_f"]}
        for i in range(num_layers):
            out[f"layer_{i}"] = jax.tree.map(
                lambda leaf: leaf[i], p["blocks"]
            )
        return out

    def save(host_state):
        return _map_params_like(
            host_state, to_canonical, match=is_dense
        )

    def load(host_state):
        return _map_params_like(host_state, from_canonical)

    return save, load


def pipeline_lm_sharding_fn(path, leaf) -> P:
    """``param_sharding_fn`` for :func:`init_pipeline_lm` params:
    block leaves stage-sharded, everything else replicated."""
    keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
    if keys and str(keys[0]) == "blocks":
        return P(STAGE_AXIS)
    return P()


def pipeline_lm_tp_sharding_fn(path, leaf) -> P:
    """``param_sharding_fn`` composing the stage axis with Megatron
    tensor parallelism over a ``dp x stage x model`` mesh: block
    leaves are manual on ``stage`` (axis 0, the schedule's shard) and
    GSPMD-auto on ``model`` over the same kernel dims
    ``transformer_tp_specs`` uses — the trainer's partial-manual step
    leaves the model axis to the compiler, so the composition needs no
    new collectives (tests hold the composed run to the stage-only
    run within float tolerance — model-axis reduction ordering keeps
    exact bitwise equality off the table).

    Leaf shapes carry the ``[S, (v,) layers_per_chunk, ...]`` stacking
    prefix, so the per-parameter kernel dims sit ``leaf.ndim - rank``
    from the end; specs are built right-aligned to work for both the
    GPipe and interleaved stackings.
    """
    keys = [
        str(getattr(k, "key", getattr(k, "name", ""))) for k in path
    ]
    if not keys or keys[0] != "blocks":
        return P()
    from adaptdl_tpu.parallel.tensor_parallel import (
        match_tp_kernel_spec,
    )

    spec = match_tp_kernel_spec(path)
    if spec is None:
        return P(STAGE_AXIS)
    pad = leaf.ndim - len(spec) - 1
    return P(STAGE_AXIS, *([None] * pad), *spec)


def init_pipeline_lm(
    config: TransformerConfig,
    num_stages: int,
    num_micro: int,
    interleave: int = 1,
    rng=None,
    seq_len: int | None = None,
):
    """(loss_fn, params) for a pipelined causal LM.

    ``config.num_layers`` must divide into ``num_stages * interleave``
    uniform chunks. ``loss_fn(params, batch, rng)`` expects
    ``batch["tokens"]`` of shape ``[rows, seq_len + 1]`` with
    ``rows`` divisible by ``num_micro``, and is built for an
    ElasticTrainer over a ``{"data": dp, "stage": num_stages}`` mesh
    with ``param_sharding_fn=pipeline_lm_sharding_fn``. Interleaved
    schedules require ``num_micro >= num_stages``.
    """
    total_chunks = num_stages * max(interleave, 1)
    assert config.num_layers % total_chunks == 0, (
        f"{config.num_layers} layers cannot split into "
        f"{total_chunks} uniform chunks ({num_stages} stages x "
        f"{interleave} interleave)"
    )
    assert interleave == 1 or num_micro >= num_stages, (
        "the interleaved schedule needs num_micro >= num_stages"
    )
    assert config.dropout_rate == 0, (
        "dropout is unsupported under the pipeline schedule (blocks "
        "run without dropout_rng); set dropout_rate=0"
    )
    assert config.moe_every_n == 0, (
        "MoE blocks are unsupported under the pipeline schedule (the "
        "staged chunk scan applies the dense Block only); compose "
        "expert parallelism with dp instead, or set moe_every_n=0"
    )
    layers_per_chunk = config.num_layers // total_chunks
    rng = rng if rng is not None else jax.random.key(0)
    seq_len = seq_len or min(config.max_seq_len, 128)

    # Pipeline stages see plain (non-ring) attention; the seq axis
    # composes with dp, not with the staged blocks, in this layout.
    block_config = dataclasses.replace(
        config, seq_axis=None, attention_fn=None, moe_axis=None
    )
    block = block_remat(block_config)(block_config)
    embed = nn.Embed(
        config.vocab_size, config.d_model, dtype=config.dtype
    )
    ln_f = nn.LayerNorm(dtype=config.dtype, use_bias=False)

    dummy = jnp.zeros((1, seq_len, config.d_model), config.dtype)
    positions0 = jnp.arange(seq_len)
    rng, embed_rng, ln_rng = jax.random.split(rng, 3)
    layer_rngs = jax.random.split(rng, config.num_layers)
    layer_params = [
        block.init(layer_rngs[i], dummy, positions0)["params"]
        for i in range(config.num_layers)
    ]
    # Chunk c owns layers [c*lpc, (c+1)*lpc) in GLOBAL chunk order —
    # layer-stacked so the chunk body is a scan.
    chunk_trees = [
        jax.tree.map(
            lambda *leaves: jnp.stack(leaves),
            *layer_params[c * layers_per_chunk:(c + 1) * layers_per_chunk],
        )
        for c in range(total_chunks)
    ]
    if interleave > 1:
        blocks = stack_interleaved_params(chunk_trees, num_stages)
    else:
        blocks = stack_stage_params(chunk_trees)
    params: dict[str, Any] = {
        "embed": embed.init(
            embed_rng, jnp.zeros((1, seq_len), jnp.int32)
        )["params"],
        "ln_f": ln_f.init(ln_rng, dummy)["params"],
        "blocks": blocks,
    }

    def chunk_fn(chunk_params, x):
        """Apply one chunk (layers_per_chunk blocks) to [mb, seq, d]."""
        positions = jnp.arange(x.shape[1])

        def body(h, one_layer):
            h = block.apply({"params": one_layer}, h, positions)
            return h, None

        out, _ = lax.scan(body, x, chunk_params)
        return out

    def loss_fn(params, batch, rng):
        del rng  # dropout unsupported under the pipeline schedule
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        assert inputs.shape[0] % num_micro == 0, (
            f"per-replica batch {inputs.shape[0]} not divisible into "
            f"{num_micro} pipeline microbatches"
        )
        x = embed.apply({"params": params["embed"]}, inputs).astype(
            config.dtype
        )
        micro = x.reshape((num_micro, -1) + x.shape[1:])
        blocks_local = jax.tree.map(
            lambda leaf: leaf[0], params["blocks"]
        )
        if interleave > 1:
            outs = interleaved_pipeline(
                chunk_fn, blocks_local, micro
            )
        else:
            outs = gpipe(chunk_fn, blocks_local, micro)
        final = outs.reshape(x.shape)
        stage = lax.axis_index(STAGE_AXIS)
        num_stages_ = lax.axis_size(STAGE_AXIS)
        is_last = stage == num_stages_ - 1
        # Garbage intermediates off the last stage would feed the
        # softmax; neutralize them BEFORE the head (0 * NaN is NaN in
        # the cotangent, see gpipe_loss).
        final = jnp.where(is_last, final, jnp.ones_like(final))
        h = ln_f.apply({"params": params["ln_f"]}, final)
        logits = embed.apply(
            {"params": params["embed"]}, h, method="attend"
        ).astype(jnp.float32)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()
        return lax.psum(
            jnp.where(is_last, loss, 0.0), STAGE_AXIS
        )

    return loss_fn, params
