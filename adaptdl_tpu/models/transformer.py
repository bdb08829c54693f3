"""Flagship decoder-only transformer LM (reference families:
examples/transformer/ WMT LM and examples/BERT/ MLM).

TPU-first design notes:

- einsum-shaped attention and MLP so XLA tiles every contraction onto
  the MXU; compute dtype bfloat16 on TPU, params float32.
- pre-LN blocks with optional per-block rematerialisation
  (``jax.checkpoint`` via ``nn.remat``) to trade FLOPs for HBM.
- RoPE positions (no position table to re-shard on sequence-length
  changes).
- the attention inner function is pluggable: the default is plain
  causal attention; the sequence-parallel path substitutes ring
  attention from ``adaptdl_tpu.parallel.ring_attention`` without
  touching the rest of the model.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from adaptdl_tpu import trace


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 2048
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # Rematerialisation policy (a jax.checkpoint_policies name, e.g.
    # "dots_with_no_batch_dims_saveable" to keep matmul outputs and
    # recompute only the cheap elementwise ops). A remat'd block
    # ALWAYS keeps the flash kernel's output and log-sum-exp
    # (block_remat: attention is never computed twice; per layer one
    # [batch, seq, d_model] activation plus 4 bytes a head and
    # position); a named policy ADDS to that, so None and
    # "nothing_saveable" keep just those two. The policy trades
    # recompute FLOPs for HBM — the knob to turn when activations,
    # not weights, bound the batch size.
    remat_policy: str | None = None
    # attention_fn(q, k, v) -> out; q/k/v are [batch, heads, seq,
    # head_dim]; None selects plain causal attention (or ring
    # attention when seq_axis is set).
    attention_fn: Callable | None = None
    # Mesh axis the sequence dim is sharded over (sequence
    # parallelism): positions become global and attention defaults to
    # ``seq_attention`` over this axis.
    seq_axis: str | None = None
    # Which sequence-parallel attention runs over seq_axis: "ring"
    # (ppermute K/V rotation, any head count, O(seq/shards) memory —
    # parallel/ring_attention.py) or "ulysses" (two all_to_all head
    # exchanges around one full-sequence attention; needs
    # num_heads % seq_shards == 0 — parallel/ulysses.py).
    seq_attention: str = "ring"
    # causal=False gives bidirectional (encoder / BERT-style)
    # attention — the MLM families (reference: examples/BERT/) — for
    # both the plain and the ring attention paths.
    causal: bool = True
    # Mixture-of-experts: every ``moe_every_n``-th block (1-indexed;
    # 0 disables) replaces its dense FFN with a Switch/GShard MoE of
    # ``moe_num_experts`` experts. With ``moe_axis`` set the experts
    # shard over that mesh axis (all_to_all dispatch inside the
    # trainer's shard_map); otherwise they run densely on-device.
    # The load-balancing auxiliary loss is sown into the
    # "moe_losses" collection — lm_loss_fn/mlm_loss_fn add it with
    # weight ``moe_aux_weight`` (without it the router collapses onto
    # one expert).
    moe_every_n: int = 0
    moe_num_experts: int = 0
    moe_axis: str | None = None
    moe_capacity_factor: float = 2.0
    moe_top_k: int = 1
    moe_aux_weight: float = 1e-2
    # "tokens" (Switch/GShard token-choice) or "experts"
    # (expert-choice, arXiv:2202.09368: structural balance, aux = 0).
    # CAVEAT: expert-choice ranks across the whole token slice, so a
    # token's routing depends on LATER tokens — not causally valid for
    # autoregressive training/decoding; intended for encoder/MLM
    # models (causal=False), the paper's setting.
    moe_router: str = "tokens"
    # Test/equivalence knob: the dense (moe_axis=None) path bins
    # token slices as if the batch were split across this many
    # devices, matching an expert-parallel run's per-device capacity.
    moe_dense_slices: int = 1


def rope(x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Rotary position embedding over the last (head_dim) axis:
    adjacent pairs ``(x[2i], x[2i + 1])`` turned by ``positions *
    10000 ** (-2i / head_dim)``.

    x: [batch, seq, heads, head_dim], the projection's own layout;
    positions: [seq].

    Written as ``x * cos + partner(x) * (-+sin)`` at full width, the
    partner of a lane the other one of its pair, taken by a
    ``[head_dim, head_dim]`` permutation as a matmul (exact: one 1 a
    column): one pass that XLA fuses, in its own layout. Strided halves
    (``x[..., 0::2]``) and their ``stack`` cost gathers and a relayout
    through an array whose minor dimension is 2, and a lane roll is
    slices and pads that XLA leaves unfused (PERF.md, PR 27); the
    values are the same to the bit.
    """
    head_dim = x.shape[-1]
    lane = jnp.arange(head_dim)
    freqs = 1.0 / (10000.0 ** ((lane // 2 * 2) / head_dim))
    angles = positions[:, None] * freqs[None, :]  # [seq, head_dim]
    sin = jnp.sin(angles).astype(x.dtype)
    sin = jnp.where(lane % 2 == 0, -sin, sin)[:, None, :]
    cos = jnp.cos(angles).astype(x.dtype)[:, None, :]
    swap = (lane[:, None] == (lane ^ 1)[None, :]).astype(x.dtype)
    partner = jnp.einsum(
        "...d,de->...e", x, swap, precision=jax.lax.Precision.HIGHEST
    )
    return x * cos + partner * sin


def causal_attention(q, k, v, axis_name=None, causal=True):
    """Plain attention; q/k/v: [batch, heads, seq, head_dim].
    ``causal=False`` attends bidirectionally (encoder-style)."""
    del axis_name
    seq_len = q.shape[2]
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale
    if causal:
        mask = jnp.tril(jnp.ones((seq_len, seq_len), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


class Attention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        head_dim = cfg.d_model // cfg.num_heads
        qkv = nn.DenseGeneral(
            (3, cfg.num_heads, head_dim),
            axis=-1,
            dtype=cfg.dtype,
            use_bias=False,
            name="qkv",
        )(x)
        q, k, v = jnp.moveaxis(qkv, -3, 0)  # each [b, s, h, d]
        q = rope(q, positions)
        k = rope(k, positions)
        # attention_fn's contract is [b, h, s, d]. The flash kernels
        # index [b * h, d, s], which is how XLA lays these arrays out
        # by itself, and swap into it themselves: their swap and this
        # one (and the one of ``out``) cost no copy between the
        # projections and the kernels (PERF.md, PR 27).
        q = jnp.swapaxes(q, 1, 2)
        k = jnp.swapaxes(k, 1, 2)
        v = jnp.swapaxes(v, 1, 2)
        attn = cfg.attention_fn
        if attn is None:
            if cfg.seq_axis is not None:
                if cfg.seq_attention == "ulysses":
                    from adaptdl_tpu.parallel.ulysses import (
                        make_ulysses_attention,
                    )

                    attn = make_ulysses_attention(
                        cfg.seq_axis, causal=cfg.causal
                    )
                elif cfg.seq_attention == "ring":
                    from adaptdl_tpu.parallel.ring_attention import (
                        make_ring_attention,
                    )

                    attn = make_ring_attention(
                        cfg.seq_axis, causal=cfg.causal
                    )
                else:
                    raise ValueError(
                        "seq_attention must be 'ring' or 'ulysses', "
                        f"got {cfg.seq_attention!r}"
                    )
            else:
                from functools import partial

                attn = partial(causal_attention, causal=cfg.causal)
        out = attn(q, k, v)  # [b, h, s, d]
        out = jnp.swapaxes(out, 1, 2).reshape(
            x.shape[:-1] + (cfg.d_model,)
        )
        return nn.DenseGeneral(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="out"
        )(out)


class MoEFFN(nn.Module):
    """Switch/GShard FFN: expert-stacked parameters (leading axis =
    experts) so the trainer shards them ``P("expert")``; under the
    trainer's manual shard_map each device sees its local slice and
    ``switch_moe`` exchanges tokens with all_to_all. The aux
    load-balancing loss is sown into the "moe_losses" collection."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from adaptdl_tpu.models.moe import dense_switch_moe, switch_moe

        cfg = self.config
        num_experts = cfg.moe_num_experts
        router = self.param(
            "router",
            nn.initializers.normal(0.02),
            (cfg.d_model, num_experts),
            jnp.float32,
        )
        # Expert-stacked leaves: full [E, d, f] at init (moe_axis is
        # None there — init_transformer strips it); inside the
        # trainer's shard_map this module sees the device's local
        # [E/ep, d, f] slice, so declare THAT shape (flax validates
        # declared vs received shapes at apply time).
        local_experts = num_experts
        if cfg.moe_axis is not None:
            ep = jax.lax.axis_size(cfg.moe_axis)
            assert num_experts % ep == 0, (
                f"{num_experts} experts cannot shard over {ep} devices"
                " (each shard owns a whole number of experts)"
            )
            local_experts = num_experts // ep
        w_up = self.param(
            "w_up",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            (local_experts, cfg.d_model, cfg.d_ff),
            jnp.float32,
        )
        w_down = self.param(
            "w_down",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            (local_experts, cfg.d_ff, cfg.d_model),
            jnp.float32,
        )
        flat = x.reshape(-1, cfg.d_model)
        if cfg.moe_axis is not None:
            out, aux = switch_moe(
                {"router": router, "w_up": w_up, "w_down": w_down},
                flat,
                axis_name=cfg.moe_axis,
                capacity_factor=cfg.moe_capacity_factor,
                top_k=cfg.moe_top_k,
                return_aux=True,
                router_type=cfg.moe_router,
            )
        else:
            out, aux = dense_switch_moe(
                router,
                {"w_up": w_up, "w_down": w_down},
                flat,
                num_slices=cfg.moe_dense_slices,
                capacity_factor=cfg.moe_capacity_factor,
                top_k=cfg.moe_top_k,
                return_aux=True,
                router_type=cfg.moe_router,
            )
        self.sow("moe_losses", "aux", aux)
        return out.reshape(x.shape).astype(cfg.dtype)


class Block(nn.Module):
    config: TransformerConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, positions, dropout_rng=None):
        cfg = self.config
        y = nn.LayerNorm(dtype=cfg.dtype, use_bias=False)(x)
        y = Attention(cfg, name="attention")(y, positions)
        if cfg.dropout_rate > 0 and dropout_rng is not None:
            y = nn.Dropout(cfg.dropout_rate, deterministic=False)(
                y, rng=dropout_rng
            )
        x = x + y
        y = nn.LayerNorm(dtype=cfg.dtype, use_bias=False)(x)
        if self.use_moe:
            y = MoEFFN(cfg, name="moe")(y)
        else:
            y = nn.Dense(
                cfg.d_ff, dtype=cfg.dtype, use_bias=False, name="ff_up"
            )(y)
            y = nn.gelu(y)
            y = nn.Dense(
                cfg.d_model, dtype=cfg.dtype, use_bias=False,
                name="ff_down",
            )(y)
        return x + y


# Only the zero-config policies are valid by NAME — the other
# jax.checkpoint_policies attributes are factories (they build a
# policy from arguments) and passing one where a policy is expected
# silently disables remat or crashes mid-trace.
_REMAT_POLICIES = (
    "everything_saveable",
    "nothing_saveable",
    "dots_saveable",
    "checkpoint_dots",
    "dots_with_no_batch_dims_saveable",
    "checkpoint_dots_with_no_batch_dims",
)


def _check_remat_policy(config: TransformerConfig) -> None:
    if (
        config.remat_policy is not None
        and config.remat_policy not in _REMAT_POLICIES
    ):
        raise ValueError(
            f"unknown remat_policy {config.remat_policy!r}; valid "
            f"names: {sorted(_REMAT_POLICIES)} (policy FACTORIES like "
            "save_only_these_names need arguments — build them "
            "yourself and wrap the Block with nn.remat directly)"
        )


def block_remat(config: TransformerConfig):
    """The ``Block`` class a model of this config stacks: ``Block``
    itself, or with ``config.remat`` its ``nn.remat`` — the one place
    that knows what a remat'd block keeps from forward to backward.

    Always the flash kernel's ``out`` and ``lse``, by the names its
    forward rule gives them: the kernel's FLOPs per byte of output
    grow with the sequence, so its output is the dearest byte of the
    block at every shape, and no policy a user can name would keep it
    (a ``pallas_call`` is not a dot). A named ``remat_policy`` adds
    what it saves to that. Blocks without the kernel name nothing and
    are remat'd as the policy alone would.

    Records one ``remat.policy`` event a call: a model calls this once
    each time it is traced.
    """
    if not config.remat:
        return Block
    _check_remat_policy(config)
    from adaptdl_tpu.ops.flash_attention import SAVED_LSE, SAVED_OUT

    saved_names = (SAVED_OUT, SAVED_LSE)
    policy = jax.checkpoint_policies.save_only_these_names(*saved_names)
    if config.remat_policy is not None:
        policy = jax.checkpoint_policies.save_from_both_policies(
            getattr(jax.checkpoint_policies, config.remat_policy),
            policy,
        )
    trace.event(
        "remat.policy",
        saved_names=",".join(saved_names),
        policy=config.remat_policy or "none",
        blocks=config.num_layers,
    )
    return nn.remat(Block, static_argnums=(), policy=policy)


class TransformerLM(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(
        self,
        tokens,
        *,
        train: bool = True,
        rng=None,
        return_hidden: bool = False,
    ):
        cfg = self.config
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            name="embed",
        )
        x = embed(tokens)
        if cfg.seq_axis is not None:
            # Sequence-sharded: this device holds one contiguous block
            # of the global sequence; positions must be global for RoPE
            # and the ring-attention causal mask to line up.
            positions = jax.lax.axis_index(
                cfg.seq_axis
            ) * tokens.shape[1] + jnp.arange(tokens.shape[1])
        else:
            positions = jnp.arange(tokens.shape[1])
        block_cls = block_remat(cfg)
        for layer in range(cfg.num_layers):
            dropout_rng = (
                jax.random.fold_in(rng, layer)
                if (train and rng is not None and cfg.dropout_rate > 0)
                else None
            )
            use_moe = (
                cfg.moe_every_n > 0
                and cfg.moe_num_experts > 0
                and (layer + 1) % cfg.moe_every_n == 0
            )
            x = block_cls(cfg, use_moe=use_moe, name=f"layer_{layer}")(
                x, positions, dropout_rng
            )
        x = nn.LayerNorm(dtype=cfg.dtype, use_bias=False)(x)
        if return_hidden:
            # For losses that stream the output head themselves (the
            # chunked cross-entropy, ops/chunked_xent.py): no
            # [tokens, vocab] logits tensor is ever built.
            return x
        # Tied output head through the embedding table keeps the only
        # O(vocab x d_model) matmul single-sourced.
        return embed.attend(x).astype(jnp.float32)


def init_transformer(config: TransformerConfig, rng=None, seq_len=None):
    import dataclasses

    if (
        config.moe_router == "experts"
        and config.causal
        and config.moe_every_n > 0
        and config.moe_num_experts > 0
    ):
        # Expert-choice gating ranks across the whole token slice, so
        # a token's routing depends on LATER tokens — silently invalid
        # for autoregressive training/decoding. Fail loud; the
        # encoder/MLM families (causal=False) are the paper's setting.
        raise ValueError(
            "moe_router='experts' is not causally valid with "
            "causal=True (expert-choice gating sees future tokens); "
            "use causal=False (encoder/MLM) or moe_router='tokens'"
        )
    # Fail at configuration time, not deep inside the first step's
    # jit trace (which on TPU wastes the whole startup).
    _check_remat_policy(config)
    model = TransformerLM(config)
    # Parameter shapes don't depend on the parallelism config, and the
    # mapped seq/expert axes don't exist outside shard_map — init
    # unsharded (expert leaves come out full-stacked [E, ...]).
    init_model = TransformerLM(
        dataclasses.replace(
            config, seq_axis=None, attention_fn=None, moe_axis=None
        )
    )
    rng = rng if rng is not None else jax.random.key(0)
    seq_len = seq_len or min(config.max_seq_len, 128)
    dummy = jnp.zeros((1, seq_len), jnp.int32)
    params = init_model.init(rng, dummy, train=False)["params"]
    return model, params


def apply_with_moe_aux(
    model: TransformerLM, params, inputs, rng, return_hidden=False
):
    """model.apply that also returns the weighted MoE load-balancing
    aux loss (0.0 for dense models) from the "moe_losses" collection —
    the building block for custom losses over MoE configs (the
    lm/mlm loss factories below use it; example:
    examples/transformer_lm.py). ``return_hidden`` passes through to
    the model (final hidden states instead of logits — for losses
    that stream the output head, ops/chunked_xent.py).
    """
    cfg = model.config
    if cfg.moe_every_n > 0 and cfg.moe_num_experts > 0:
        out, mutated = model.apply(
            {"params": params},
            inputs,
            train=True,
            rng=rng,
            return_hidden=return_hidden,
            mutable=["moe_losses"],
        )
        auxes = jax.tree.leaves(mutated.get("moe_losses", {}))
        aux = (
            cfg.moe_aux_weight * sum(jnp.mean(a) for a in auxes)
            if auxes
            else jnp.zeros(())
        )
        return out, aux
    out = model.apply(
        {"params": params},
        inputs,
        train=True,
        rng=rng,
        return_hidden=return_hidden,
    )
    return out, jnp.zeros(())


def mlm_loss_fn(
    model: TransformerLM, mask_token: int, mask_rate: float = 0.15
):
    """Masked-LM cross-entropy (the reference's BERT-family objective,
    examples/BERT/mlm_task_adaptdl.py): each step masks ``mask_rate``
    of tokens (fresh mask per step from the step rng) and scores only
    the masked positions. Use with ``TransformerConfig(causal=False)``
    so attention is bidirectional. batch = {"tokens": [b, s] int32}.
    """

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        mask_rng = jax.random.fold_in(rng, 0x3A5)
        mask = jax.random.uniform(mask_rng, tokens.shape) < mask_rate
        inputs = jnp.where(mask, mask_token, tokens)
        logits, aux = apply_with_moe_aux(model, params, inputs, rng)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens
        )
        weights = mask.astype(jnp.float32)
        return (
            jnp.sum(losses * weights)
            / jnp.maximum(jnp.sum(weights), 1.0)
            + aux
        )

    return loss_fn


def lm_loss_fn(model: TransformerLM):
    """Next-token cross-entropy (+ weighted MoE aux loss when the
    config enables experts); batch = {"tokens": [b, s+1] int32}."""

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits, aux = apply_with_moe_aux(model, params, inputs, rng)
        return (
            optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            ).mean()
            + aux
        )

    return loss_fn


def moe_param_sharding_fn(path, leaf):
    """``param_sharding_fn`` for expert-parallel MoE transformers:
    expert-stacked leaves (under a ``moe`` module, except the
    replicated router) shard over the expert mesh axis; everything
    else replicates.
    """
    from jax.sharding import PartitionSpec as P

    from adaptdl_tpu.parallel.mesh import EXPERT_AXIS

    keys = tuple(
        str(p.key) if hasattr(p, "key") else str(p) for p in path
    )
    if "moe" in keys and keys[-1] in ("w_up", "w_down"):
        return P(EXPERT_AXIS)
    return P()
