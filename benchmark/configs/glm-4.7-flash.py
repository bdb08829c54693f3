"""glm-4.7-flash: builder of the system under test, and its plain
reference.

One chip's share of GLM-4.7-Flash (``glm4_moe_lite``, 30B-A3B) under
expert parallelism over 8 chips (``glm-4.7-flash.json``: published
widths, published layers 0-3 = [latent attention + the dense FFN, then
three routed layers] and the multi-token-prediction module's layer
after them, 8 of each routed layer's 64 experts held beside the shared
expert, an eighth of both vocabulary tables, which the module shares).
The system side goes through the program's own entry points
(``TransformerConfig`` / ``TransformerLM`` with ``layer_types`` "mla",
``q_lora_rank``, rotary on the 64-wide part and ``mtp_depth`` 1, the
flash kernels at a q/k width of 256 and a v width of 256, the grouped
products, ``routed_lm_loss_fn``, ``ElasticTrainer``). The reference
side is written from the published equations (the DeepSeek-V3 block and
its prediction module, arXiv:2412.19437) with the departures the JSON
lists, in plain float32 ``jax.numpy`` at "highest" matmul precision,
and imports nothing from ``adaptdl_tpu``: latent attention as a dense
masked softmax by query blocks with the rotary written out, experts as
a checkpointed loop over the held ones, the module written out, the
head by blocks of rows, no kernel, the same share.
"""

from __future__ import annotations

import numpy as np

from benchmark import near_ties

# What decides ``correct`` (reference_check), on the run's own weights
# at the published widths on ONE row of the timed length. "first" is
# what the system gave over its seeds (the cell's own runs print them:
# ``compared.reference``): seven seeds, 2560000701-706 and 2560000801
# (four more, 2560000201 and 2560001001-003, inside every range); "second" what the
# reference computed in the nearest precision BELOW the stated one, or
# with a part of the mathematics left out, gave against the reference
# itself on the system's own inputs, compiled as stated
# (benchmark/tests/glm_precision.py --controls, seeds 2560000101,
# 2560000901 and 2560001101). Readings: my chip runs, PR 56, TPU v5 lite.
#
# 1. Whole model: |system - reference| / reference of BOTH loss terms
#    apart (the trunk's mean over 16 384 tokens, the module's over the
#    16 383 targeted ones) and of their weighted sum. First 1.8e-6 ..
#    4.7e-5; second (the module's term left out of the sum) 0.091.
REFERENCE_RTOL = 2.5e-4
# 2. The head alone, token by token, on BOTH streams of hidden states
#    the SYSTEM hands to it: float32 accumulation, logits, softmax and
#    loss. First 9.5e-7 .. 1.9e-6 nats; second (logits and their
#    softmax in bfloat16) 0.069, 0.070.
HEAD_TOKEN_LOSS_ATOL = 1e-3  # max |token loss - reference|, nats
# 3. Every router alone (four: three of the trunk, the module's last),
#    token by token, on the inputs the SYSTEM hands to it: sets of four
#    and their weights against the float32 "highest" router. First 0
#    and 1.8e-7 on every seed; second (bfloat16 scores) 0.070 of the
#    tokens and 3.8e-3.
ROUTER_SET_MISMATCH_SHARE = 1e-3
ROUTER_WEIGHT_ATOL = 1e-4  # weights carry the scale of 1.8
# 4. Rows each held expert received against the whole reference's
#    count (first 0.0032 .. 0.0049); exactly: no row dropped, held +
#    left-out = tokens x 4, the shared expert multiplied every token —
#    in all four routed layers.
ROUTING_L1_SHARE = 0.05
# 5. Every routed layer (with its shared expert) and the last trunk
#    layer's latent-attention mixer, each ALONE, token by token, on the
#    inputs the SYSTEM hands it: ``layer_error`` = (worst token, rms
#    over tokens) of |system - reference| over the layer's rms output
#    norm; the prediction module alone (its normed output from the
#    trunk's state and the next tokens) the same way, and once more as
#    the WHOLE model ran it (``mtp_as_run_rms_err``).
# 6. Backward, each alone: gradients of ``sum(y * cotangent)``
#    (cotangent = the layer's input) with respect to every parameter
#    leaf and the input — for the mixer that is dq through BOTH
#    bottleneck matrices (``mla_q_a_grad_err``) and the one k_pe's
#    gradient summed over the 20 heads (kv_a's columns 512 ..:
#    ``mla_k_pe_grad_err``) — against ``jax.grad`` of the reference:
#    |system - reference| / |reference| of a leaf (routed: of each
#    expert's slice), the worst; the input as 5's rms. The module's
#    objective is ITS loss term (unweighted): gradients of every leaf
#    of its own, of the trunk's state ``h``, of the embedding table and
#    of the output table.
#    First (seven seeds) / second readings:
#      "mla", second = the rotary's angles in bfloat16; no rotary
#        worst token   0.048 .. 0.055 / 0.15, 0.24; 0.90, 1.05
#        rms           0.00488 .. 0.00496 / 0.071, 0.073; 0.20, 0.21
#        worst leaf    0.00426 .. 0.00482 / 0.057, 0.058; 0.27
#          (q_a 0.00415 .. 0.00419, k_pe's columns 0.00565 .. 0.00576)
#        input's rms   0.00624 .. 0.00638 / 0.119; 0.34
#        (bfloat16 logits / a bfloat16 statistic read 0.0013 / 0.0017
#        here, UNDER the system's own: comparison 8's to refuse)
#      "routed", second = routed_scaling_factor left out
#        worst token   0.0083 .. 0.0088 / 0.39, 0.40
#        rms           0.005659 .. 0.005668 / 0.133, 0.134
#        input's rms   0.004791 .. 0.004796 / 0.134, 0.135
#        expert slice  0.0039 .. 0.0042, router 0.0040 .. 0.0044: a
#        first reading and room (no fault of theirs read)
#      "mtp" (the module alone), second = no rotary in its block; its
#      routed part's scale left out; a table's gradient cut off (its
#      rotary's angles in bfloat16 read 0.019 / 0.027 / 0.023 / 0.017
#      on the four lines below that a limit separates, under their
#      limits: the residual path thins the mixer's 7%; the worst
#      leaf's gradient, 0.145, refuses it)
#        worst token   0.28 .. 0.34 / 0.34; 0.18: a maximum over
#          tokens of a whole block separates nothing, a blunt guard
#        rms           0.0125 .. 0.0154 (as run 0.0130 .. 0.0156) /
#          0.0365; 0.069
#        worst leaf    0.0267 (its block's second norm; the others
#          0.0042 .. 0.022) / 0.40; 0.43
#        its routed part (router 0.086 .. 0.139, an expert's slice
#          0.073 .. 0.112) / 0.25, 0.43: NOT the routed layer's 0.004 —
#          the module's router sees what the system's own rounding of
#          eh_proj and the mixer left of its input and chooses other
#          experts for ~1% of the tokens, which moves an expert's rows
#          and with them its gradient by the root of their share; the
#          routed layer ALONE on equal inputs is held above. A blunt
#          guard.
#        h's rms       0.0158 .. 0.0196 / 0.052; 0.087
#        embedding     0.0131 .. 0.0181 / 0.046; 0.086; 1.0 (cut off)
#        output table  0.0103 .. 0.0142 / 0.032; 0.068; 1.0 (cut off)
#    Every limit lies between its two readings (the blunt ones
#    apart): 1.4 to 1.6 times the first, at most 0.7 of the smallest
#    second.
LAYER_LIMITS = {
    # kind: (worst token, rms over tokens)
    "routed": (0.04, 0.0085),
    "mla": (0.15, 0.0075),
    "mtp": (0.8, 0.022),
}
EXPERT_GRAD_RTOL = 0.0075  # worst expert's slice of a weight leaf
ROUTER_GRAD_RTOL = 0.008  # the router leaf
INPUT_GRAD_RMS = 0.0075  # a routed layer's input gradient
MIXER_GRAD_LIMITS = (0.0075, 0.0095)  # (worst leaf, the input's rms)
# The module's: (worst leaf of its own outside its routed part; its
# router and experts' slices; h's rms; the embedding table's gradient;
# the output table's), each |got - want| / |want|.
MTP_GRAD_LIMITS = {
    "leaf": 0.04, "routed": 0.25, "trunk": 0.03, "embedding": 0.028,
    "head": 0.022,
}
# 7. The WHOLE model's gradient of each shared table against the
#    reference's, |got - want| / |want|: each has two uses (the
#    embedding the trunk's lookup and the module's, the output table
#    two streams of rows). First: embedding 0.0302 .. 0.0355, output
#    table 0.0225 .. 0.0265 (the system's own rounding through five
#    blocks). Second (the reference with the module's use of a table
#    cut off, ``TABLE_FAULTS``, against the reference): 0.0539, 0.0540
#    and 0.0995, 0.0995 — the module's use is a tenth of the loss, so
#    the embedding's limit has 30% of room above and 15% below (a
#    system WITHOUT the use would read the root of both squares,
#    0.062). The sharper half is ``<table>_second_use``: how much of
#    lambda x the module's own gradient of the table (``mtp_check``)
#    the system's whole gradient holds. First 1.0008 .. 1.0011 (both
#    tables); second 0 by construction.
TABLE_GRAD_RTOL = {"embedding": 0.046, "head": 0.045}
SECOND_USE_TOL = 0.25  # |<table>_second_use - 1|
# 8. The flash kernels ALONE at the cell's widths (q/k 256, v 256) and
#    row on operands made from the seed and rounded to bfloat16 (so the
#    products are exact and the kernels' own arithmetic is all that
#    differs) against the dense masked softmax on the same values. The
#    output's and the gradients' rms error (``layer_error``) hold
#    little: the output leaves the kernel in bfloat16, first 2.09e-3 ..
#    2.11e-3 (gradients 2.39e-3 .. 2.47e-3) by that rounding alone,
#    where a softmax statistic held in bfloat16 reads 1.6e-3 (2.5e-3)
#    and bfloat16 logits 3.1e-3 (3.6e-3): blunt guards, a first reading
#    and room. What separates is the error ALONG an output row
#    (``row_scale_error``): a rounding an element points nowhere and
#    averages out over the 256 lanes, a wrong scale of the row does
#    not. First 2.26e-4 .. 2.29e-4; second 5.8e-4 (bfloat16 logits),
#    1.66e-3 (a bfloat16 statistic): the limit 1.6 times the first,
#    0.62 of the smaller second.
KERNEL_RMS_LIMIT = 4e-3
KERNEL_ROW_SCALE_LIMIT = 3.6e-4
KERNEL_HEADS = 2
REFERENCE_SEQUENCES = 1
ATTENTION_QUERY_BLOCK = 128
HEAD_ROW_BLOCK = 2048  # rows of the reference's head at a time
HEAD_GROUP = 4  # heads of latent attention the reference runs at once
BLOCK_NORMS = ("RMSNorm_0", "RMSNorm_1")


def units_per_sample(sizes: dict) -> int:
    return int(sizes["sequence_length"])


def routed_layers(sizes: dict) -> list[int]:
    """The trunk's routed layers."""
    return list(
        range(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    )


def forward_flops_per_token(sizes: dict) -> dict[str, float]:
    """Forward matmul FLOPs per token, by part: 2 FLOPs per
    multiply-accumulate, the causal half of attention at the timed
    length, routed experts at UNIFORM routing, no recomputation —
    counted as ``benchmark/flops.py`` counts. The prediction module is
    one more block (latent attention, router, shared and held
    experts), its ``[2 d, d]`` projection and a second pass of the
    head; rotary, norms and lookups are no matrix products."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, pe = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim, latent = sizes["v_head_dim"], sizes["kv_lora_rank"]
    q_rank = sizes["q_lora_rank"]
    modules = sizes["num_nextn_predict_layers"]
    dense = sizes["first_k_dense_replace"]
    blocks = sizes["num_hidden_layers"] + modules
    routed = blocks - dense
    per_token_experts = (
        sizes["num_experts_per_tok"] * sizes["experts_held"]
        / sizes["router_width"]
    )
    expert = 2 * 3 * d * sizes["moe_intermediate_size"]
    return {
        "mla_projections": float(
            blocks * 2 * (
                d * q_rank + q_rank * heads * (nope + pe)
                + d * (latent + pe) + latent * heads * (nope + v_dim)
                + heads * v_dim * d
            )
        ),
        "mla_attention": float(
            blocks * 2 * sizes["sequence_length"] * heads
            * (nope + pe + v_dim) * 0.5
        ),
        "dense_ffn": float(dense * 2 * 3 * d * sizes["intermediate_size"]),
        "router": float(routed * 2 * d * sizes["router_width"]),
        "shared_experts": float(
            routed * sizes["n_shared_experts"] * expert
        ),
        "routed_experts": float(routed * per_token_experts * expert),
        "mtp_projection": float(modules * 2 * (2 * d) * d),
        "head": float((1 + modules) * 2 * d * sizes["vocab_size"]),
    }


def train_flops_per_unit(sizes: dict) -> float:
    """Forward + backward (3x forward) model FLOPs per trained token."""
    return 3.0 * sum(forward_flops_per_token(sizes).values())


def make_dataset(sizes: dict, seed: int, samples: int) -> dict:
    """Packed token rows from the seed, as the other configurations':
    documents of lognormal length (median ~400 tokens), each an
    arithmetic progression modulo the vocabulary SLICE with its own
    start and stride, packed back to back into rows of
    ``sequence_length + 1`` tokens, no padding. (The module's targets
    are ``targets`` moved one place: the loss's doing, not the data's.)"""
    rng = np.random.default_rng(seed)
    vocab, row = sizes["vocab_size"], sizes["sequence_length"] + 1
    total = samples * row
    lengths = np.maximum(
        rng.lognormal(mean=6.0, sigma=1.0, size=total // 256 + 16), 2
    ).astype(np.int64)
    while lengths.sum() < total:
        lengths = np.concatenate([lengths, lengths])
    starts = np.cumsum(lengths) - lengths
    doc = np.repeat(np.arange(len(lengths)), lengths)[:total]
    position = np.arange(total) - starts[doc]
    first = rng.integers(0, vocab, size=len(lengths))
    stride = rng.integers(1, 4, size=len(lengths))
    tokens = ((first[doc] + stride[doc] * position) % vocab).astype(
        np.int32
    ).reshape(samples, row)
    return {
        "inputs": np.ascontiguousarray(tokens[:, :-1]),
        "targets": np.ascontiguousarray(tokens[:, 1:]),
    }


def model_config(sizes: dict, attention_fn=None):
    """The ``TransformerConfig`` of these sizes."""
    import jax.numpy as jnp

    from adaptdl_tpu.models import TransformerConfig

    assert sizes["rope_scaling"] is None and sizes["n_group"] == 1
    assert sizes["partial_rotary_factor"] == 1  # of the 64-wide part
    assert sizes["num_key_value_heads"] == sizes["num_attention_heads"]
    assert sizes["topk_method"] == "noaux_tc" and sizes["norm_topk_prob"]
    return TransformerConfig(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        d_model=sizes["hidden_size"],
        d_ff=sizes["intermediate_size"],
        max_seq_len=sizes["sequence_length"],
        dtype=jnp.dtype(sizes.get("compute_dtype", "bfloat16")).type,
        remat=True,
        attention_fn=attention_fn,
        norm="rmsnorm",
        norm_eps=sizes["rms_norm_eps"],
        ffn="swiglu",
        rope=True,
        rope_theta=float(sizes["rope_theta"]),
        head_dim=sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        layer_types=("mla",) * sizes["num_hidden_layers"],
        q_lora_rank=sizes["q_lora_rank"],
        kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        experts_total=sizes["router_width"],
        experts_held=sizes["experts_held"],
        first_expert=sizes["first_expert"],
        experts_top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["moe_intermediate_size"],
        d_shared_expert=sizes["n_shared_experts"]
        * sizes["moe_intermediate_size"],
        num_dense_layers=sizes["first_k_dense_replace"],
        expert_weight_eps=sizes["expert_weight_eps"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        experts_router="sigmoid",
        experts_pieces_from=sizes["experts_pieces_from"],
        tie_embeddings=sizes["tie_word_embeddings"],
        mtp_depth=sizes["num_nextn_predict_layers"],
        mtp_loss_weight=float(sizes["mtp_loss_weight"]),
    )


def checked_mixer(sizes: dict) -> int:
    """The layer whose latent-attention mixer is checked alone: the
    trunk's last (its input has passed every kind of layer)."""
    return sizes["num_hidden_layers"] - 1


def build(sizes: dict, geometry: dict, seed: int) -> dict:
    """The system under test for one cell: model, weights made on the
    device in one jitted call from the seed, loss, trainer."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    model_config(sizes)  # a program without the fields says so here
    import flax.linen as nn

    from adaptdl_tpu.models.transformer import (
        LatentAttention,
        PredictionModule,
        RoutedFFN,
        TransformerLM,
        block_remat,
        moe_load_counters,
        routed_lm_loss_fn,
    )
    from adaptdl_tpu.ops.chunked_xent import (
        chunked_softmax_xent,
        weighted_xent_sum,
    )
    from adaptdl_tpu.ops.flash_attention import flash_attention
    from adaptdl_tpu.scaling_rules import AdamScale
    from adaptdl_tpu.trainer import ElasticTrainer

    block = min(128, sizes["sequence_length"])
    attention = functools.partial(
        flash_attention, block_q=block, block_k=block
    )
    cfg = model_config(sizes, attention)
    model = TransformerLM(cfg)
    # Parameter shapes depend on neither the attention function nor
    # the sequence: init through plain attention on a short row.
    init_model = TransformerLM(model_config(sizes))
    dummy = jnp.zeros((1, min(128, sizes["sequence_length"])), jnp.int32)

    def fresh(key):
        """flax's initialisers, and the embedding table at UNIT
        variance (as the three configurations before this one)."""
        params = init_model.init(
            key, dummy, train=False, next_tokens=dummy
        )["params"]
        table = params["embed"]["embedding"]
        params["embed"]["embedding"] = table * table.shape[1] ** 0.5
        return params

    params = jax.jit(lambda key: fresh(key))(jax.random.key(seed))

    trunk_routed = routed_layers(sizes)
    module_at = sizes["num_hidden_layers"]
    mixer_at = checked_mixer(sizes)
    # Where each routed layer lives in the tree, the module's last.
    routed_paths = [(f"layer_{i}",) for i in trunk_routed] + [
        ("mtp", f"layer_{module_at}")
    ]
    captured_paths = (
        {(f"layer_{module_at - 1}",)}  # the trunk's state before its norm
        | {path + (name,) for path in routed_paths
           for name in (BLOCK_NORMS[1], "moe")}
        | {(f"layer_{mixer_at}", name) for name in (BLOCK_NORMS[0], "mla")}
    )

    def head_io(params, batch, rng):
        """From ONE evaluation of the whole model, as it runs: both
        streams' final hidden states and every token's loss on each;
        the trunk's state before its final norm; of every routed layer
        (the module's last) its input, its output (shared expert
        included), the router's choice and the load counters; of one
        latent-attention mixer its input and output."""
        (hidden, predicted), captured = model.apply(
            {"params": params}, batch["inputs"], train=True, rng=rng,
            return_hidden=True, next_tokens=batch["targets"],
            capture_intermediates=lambda module, _method: module.path
            in captured_paths,
            mutable=["moe_load", "moe_routing", "intermediates"],
        )
        further = jnp.roll(batch["targets"], -1, axis=1)
        losses = jnp.stack([
            head_losses(params, hidden, batch["targets"]),
            head_losses(params, predicted, further),
        ])
        load = moe_load_counters(cfg, captured)

        def seen(path):
            return _leaf(captured["intermediates"], path)["__call__"][0]

        for name in ("experts", "weights"):
            load[name] = [
                _leaf(captured["moe_routing"], path + ("moe", name))[0]
                for path in routed_paths
            ]
        for name, module in (("inputs", BLOCK_NORMS[1]), ("outputs", "moe")):
            load[name] = [
                seen(path + (module,)).reshape(-1, sizes["hidden_size"])
                for path in routed_paths
            ]
        load["mla"] = tuple(
            seen((f"layer_{mixer_at}", name))
            for name in (BLOCK_NORMS[0], "mla")
        )
        load["trunk"] = seen((f"layer_{module_at - 1}",))
        return jnp.stack([hidden, predicted]), losses, load

    def head_losses(params, hidden, targets):
        """The system's head on ``hidden``, streamed ``head_chunk_rows``
        rows at a time: every token's loss."""
        return chunked_softmax_xent(
            hidden.reshape(-1, hidden.shape[-1]), params["lm_head"],
            targets.reshape(-1), sizes["head_chunk_rows"],
        ).reshape(targets.shape)

    def routed_vjp(moe_params, x, cotangent, sets=False):
        """The system's routed layer alone, backward: the gradients of
        ``sum(y * cotangent)`` with respect to the layer's parameters
        and its input ``x`` [tokens, d]; with ``sets`` also the experts
        ITS router chose [tokens, top_k] (``near_ties``)."""

        def objective(moe_params, x):
            y, sown = RoutedFFN(cfg).apply(
                {"params": moe_params}, x, mutable=["moe_routing"]
            )
            return (
                jnp.sum(y.astype(jnp.float32) * cotangent),
                sown["moe_routing"]["experts"][0],
            )

        grads, chosen = jax.grad(objective, argnums=(0, 1), has_aux=True)(
            moe_params, x
        )
        return (grads, chosen) if sets else grads

    def mixer_vjp(mixer_params, x, cotangent):
        """The system's latent-attention mixer alone on ``x`` [1, seq,
        d]: the gradients of ``sum(y * cotangent)`` with respect to
        (its parameters, x)."""
        positions = jnp.arange(x.shape[1])

        def objective(mixer_params, x):
            y = LatentAttention(cfg).apply(
                {"params": mixer_params}, x, positions
            )
            return jnp.sum(y.astype(jnp.float32) * cotangent)

        return jax.grad(objective, argnums=(0, 1))(mixer_params, x)

    def mtp_alone(module_params, embedding, table, trunk, targets):
        """The system's prediction module alone, from the trunk's state
        ``trunk`` [b, s, d] and the batch's ``targets``: its normed
        output and ITS loss term (unweighted: the mean over the
        targeted positions, through the system's streamed head), with
        the loss's gradients with respect to (the module's leaves, the
        embedding table, the output table, the trunk's state). The
        system's own modules at their own settings: the model's
        ``Embed``, ``PredictionModule`` over the trunk's block class."""
        seq_len = targets.shape[1]
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype
        )
        module = PredictionModule(cfg, block_remat(cfg, targets.shape))
        weight = mtp_position_weights(targets)

        def objective(module_params, embedding, table, trunk):
            out = module.apply(
                {"params": module_params}, trunk,
                embed.apply({"params": {"embedding": embedding}}, targets),
                jnp.arange(seq_len),
            )
            loss, _ = weighted_xent_sum(
                out.reshape(-1, cfg.d_model), table,
                jnp.roll(targets, -1, axis=1).reshape(-1),
                weight.reshape(-1), sizes["head_chunk_rows"],
            )
            return loss, out

        (loss, out), grads = jax.value_and_grad(
            objective, argnums=(0, 1, 2, 3), has_aux=True
        )(module_params, embedding, table, trunk)
        return out, loss, grads

    def table_grads(params, batch, rng):
        """The WHOLE model's loss (the step's own ``loss_fn``) and its
        gradient with respect to the two shared tables alone: (loss,
        counters, {"embedding", "head"})."""
        rest = {
            k: v for k, v in params.items() if k not in ("embed", "lm_head")
        }

        def objective(tables):
            return loss_fn(
                {**rest, "embed": {"embedding": tables["embedding"]},
                 "lm_head": tables["head"]},
                batch, rng,
            )

        (loss, counters), grads = jax.value_and_grad(
            objective, has_aux=True
        )({
            "embedding": params["embed"]["embedding"],
            "head": params["lm_head"],
        })
        return loss, counters["mtp.loss"], grads

    def flash_kernels(q, k, v):
        """The flash kernels alone on ``[1, heads, seq, width]``
        operands: (out, (dq, dk, dv) of ``sum(out * q)``)."""

        def objective(q, k, v):
            out = attention(q, k, v)
            return jnp.sum(
                out.astype(jnp.float32)
                * jax.lax.stop_gradient(q).astype(jnp.float32)
            ), out

        grads, out = jax.grad(objective, argnums=(0, 1, 2), has_aux=True)(
            q, k, v
        )
        return out, grads

    recipe = sizes["recipe"]
    loss_fn = routed_lm_loss_fn(model, sizes["head_chunk_rows"])
    trainer = ElasticTrainer(
        loss_fn=loss_fn,
        params=params,
        optimizer=optax.adamw(recipe["learning_rate"]),
        init_batch_size=geometry["global_batch"],
        scaling_rule=AdamScale(),
        precondition=recipe["precondition"],
        seed=seed,
    )
    return {
        "trainer": trainer,
        "model": model,
        "loss_fn": loss_fn,
        "head_io": head_io,
        "head_losses": head_losses,
        "routed_vjp": routed_vjp,
        "mixer_vjp": mixer_vjp,
        "mtp_alone": mtp_alone,
        "table_grads": table_grads,
        "flash_kernels": flash_kernels,
        "checkpoint_transforms": None,
    }


# ---- the plain reference --------------------------------------------


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


# The reference's names of a mixer's, a routed layer's and the dense
# FFN's weights -> the system's parameter leaves.
MLA_LEAVES = {
    "w_qa": ("q_a", "kernel"), "q_norm": ("q_norm", "scale"),
    "w_qb": ("q_b", "kernel"), "w_kva": ("kv_a", "kernel"),
    "kv_norm": ("kv_norm", "scale"), "w_kvb": ("kv_b", "kernel"),
    "w_out": ("out", "kernel"),
}
ROUTED_LEAVES = {
    "w1": ("w_gate",), "w3": ("w_up",), "w2": ("w_down",),
    "router": ("router",), "s1": ("shared", "ff_gate", "kernel"),
    "s3": ("shared", "ff_up", "kernel"),
    "s2": ("shared", "ff_down", "kernel"),
}
DENSE_LEAVES = {
    "w1": ("ff_gate", "kernel"), "w3": ("ff_up", "kernel"),
    "w2": ("ff_down", "kernel"),
}


def mla_weights(mixer) -> dict:
    """w_qa [d, q_rank], w_qb [q_rank, heads, nope + pe], w_kva [d,
    latent + pe], w_kvb [latent, heads, nope + v], w_out [heads * v,
    d], the two norms' scales."""
    return {name: _leaf(mixer, path) for name, path in MLA_LEAVES.items()}


def routed_weights(moe) -> dict:
    """router [d, router_width], bias, w1 / w3 [held, d, f], w2 [held,
    f, d], the shared expert's s1 / s3 / s2."""
    return {
        **{name: _leaf(moe, path) for name, path in ROUTED_LEAVES.items()},
        "bias": moe["expert_bias"],
    }


def block_weights(block, routed: bool) -> dict:
    layer = {
        "norm_op": block[BLOCK_NORMS[0]]["scale"],
        "norm_ffn": block[BLOCK_NORMS[1]]["scale"],
        "mla": mla_weights(block["mla"]),
    }
    if routed:
        layer.update(routed_weights(block["moe"]))
    else:
        layer.update(
            {n: _leaf(block["ffn"], p) for n, p in DENSE_LEAVES.items()}
        )
    return layer


def module_weights(module, sizes: dict) -> dict:
    """The prediction module's own weights: the two input norms, the
    ``[2 d, d]`` projection (rows 0 .. d - 1 the embedding's half), its
    block, its final norm. It has no table."""
    return {
        "norm_e": module["enorm"]["scale"],
        "norm_h": module["hnorm"]["scale"],
        "w_eh": module["eh_proj"]["kernel"],
        "block": block_weights(
            module[f"layer_{sizes['num_hidden_layers']}"], routed=True
        ),
        "norm_out": module["norm"]["scale"],
    }


def reference_weights(params, sizes: dict) -> dict:
    """The system's parameter tree in the reference's own layout."""
    return {
        "embedding": params["embed"]["embedding"],
        "head": params["lm_head"],  # [vocab, d]
        "layers": [
            block_weights(
                params[f"layer_{i}"], i >= sizes["first_k_dense_replace"]
            )
            for i in range(sizes["num_hidden_layers"])
        ],
        "norm_out": params[BLOCK_NORMS[0]]["scale"],
        "mtp": module_weights(params["mtp"], sizes),
    }


# What the comparisons can tell apart is MEASURED: the reference
# functions take a ``variant`` that computes in the nearest precision
# below the stated one, or leaves a part of the mathematics out (never
# used by ``reference_check``; benchmark/tests/glm_precision.py reads
# each against the right one, the tests hold that each differs).
ROUTER_FAULTS = ("bf16_scores",)
ROUTED_FAULTS = ("no_scale",)  # routed_scaling_factor left out
MLA_FAULTS = (
    "bf16_angles",  # the rotary's angles (position x frequency) in bfloat16
    "no_rotary",  # the 64-wide part left unturned
)
KERNEL_FAULTS = ("bf16_logits", "bf16_stat")
HEAD_FAULTS = ("bf16_loss",)  # logits and their softmax in bfloat16
TABLE_FAULTS = (
    "embedding_one_use",  # the module's lookup sends no gradient back
    "head_one_use",  # the module's rows send none to the output table
)
LOSS_FAULTS = ("no_mtp_loss",)  # the module's term left out of the sum


def _rms_norm(x, scale, eps: float):
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _gated(x, w1, w3, w2):
    import jax

    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _rotary(x, theta: float, variant: str = ""):
    """``x`` [b, s, ..., lanes] turned by rotary over ALL its lanes,
    positions 0 .. s - 1: the pair of adjacent lanes ``(x[2i], x[2i +
    1])`` by the angle ``position * theta ** (-2i / lanes)``, written
    out on the two halves of each pair; angles, sines and cosines in
    float32 (``bf16_angles``: the angle rounded to bfloat16)."""
    import jax.numpy as jnp

    lanes, seq = x.shape[-1], x.shape[1]
    freq = theta ** (-jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    if variant == "bf16_angles":
        angle = angle.astype(jnp.bfloat16).astype(jnp.float32)
    shape = (1, seq) + (1,) * (x.ndim - 3) + (lanes // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1
    ).reshape(x.shape)


def _softmax_pv(scores, seen, v_of, variant: str = ""):
    """``softmax(scores masked by seen) @ v`` over the last axis of
    ``scores`` [..., q, s]; ``v_of(p)`` multiplies the probabilities
    with v. The two precision faults: logits rounded to bfloat16, and
    the statistic (max and sum) held in bfloat16."""
    import jax
    import jax.numpy as jnp

    if variant == "bf16_logits":
        scores = scores.astype(jnp.bfloat16).astype(jnp.float32)
    scores = jnp.where(seen, scores, -jnp.inf)
    if variant != "bf16_stat":
        return v_of(jax.nn.softmax(scores, axis=-1))
    top = scores.max(-1, keepdims=True).astype(jnp.bfloat16)
    p = jnp.exp(scores - top.astype(jnp.float32))
    total = p.sum(-1, keepdims=True).astype(jnp.bfloat16)
    return v_of(p / total.astype(jnp.float32))


def reference_softmax_attention(q, k, v, variant: str = ""):
    """Causal softmax attention of ``q``, ``k`` [b, s, heads, width]
    and ``v`` [b, s, heads, v_width] at scale ``width ** -0.5``: a
    dense masked softmax, one block of ``ATTENTION_QUERY_BLOCK``
    queries after another (a ``lax.map`` whose body is checkpointed: a
    gradient holds one block's scores). ``variant``: of
    ``KERNEL_FAULTS``."""
    import jax
    import jax.numpy as jnp

    batch, seq, heads, width = q.shape
    block = min(ATTENTION_QUERY_BLOCK, seq)
    assert seq % block == 0
    key_at = jnp.arange(seq)

    @jax.checkpoint
    def attend(operands):
        q_block, start = operands
        scores = jnp.einsum("bqhk,bshk->bhqs", q_block, k) * width**-0.5
        seen = key_at[None, :] <= (start + jnp.arange(block))[:, None]
        return _softmax_pv(
            scores, seen[None, None],
            lambda p: jnp.einsum("bhqs,bshk->bqhk", p, v), variant,
        )

    out = jax.lax.map(
        attend,
        (
            jnp.moveaxis(
                q.reshape(batch, seq // block, block, heads, width), 1, 0
            ),
            jnp.arange(0, seq, block),
        ),
    )  # [blocks, b, block, heads, v_width]
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, -1)


def reference_mla(layer: dict, u, sizes: dict, variant: str = ""):
    """Latent attention on ``u`` [batch, seq, d], the training form:
    ``c_q = norm(u W_qa)``, ``q = c_q W_qb`` a head ``[q_nope | q_pe]``;
    ``[c | k_pe] = u W_kva``, ``[k_nope | v] = norm(c) W_kvb`` a head;
    rotary on every head's ``q_pe`` and on the ONE ``k_pe``, which then
    goes onto every head; causal softmax at ``(nope + pe) ** -0.5``;
    ``W_out``. ``HEAD_GROUP`` heads at a time, one group after another
    (a ``lax.map`` whose body is checkpointed, so that a gradient holds
    a group's float32 q, k and v and not the layer's; the one ``k_pe``
    is every group's, and its gradient the sum over them).
    ``variant``: of ``MLA_FAULTS`` or ``KERNEL_FAULTS``."""
    import jax
    import jax.numpy as jnp

    rank, nope = sizes["kv_lora_rank"], sizes["qk_nope_head_dim"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    heads = layer["w_qb"].shape[1]
    held = min(HEAD_GROUP, heads)
    assert heads % held == 0
    c_q = _rms_norm(u @ layer["w_qa"], layer["q_norm"], eps)
    kv_a = u @ layer["w_kva"]
    latent = _rms_norm(kv_a[..., :rank], layer["kv_norm"], eps)
    k_pe = kv_a[..., rank:]
    if variant != "no_rotary":
        k_pe = _rotary(k_pe, theta, variant)

    def by_group(w):  # [r, heads, k] -> [groups, r, held, k]
        return jnp.moveaxis(
            w.reshape(w.shape[0], heads // held, held, w.shape[2]), 1, 0
        )

    @jax.checkpoint
    def some_heads(operands):
        w_qb, w_kvb = operands
        q = jnp.einsum("bsr,rhk->bshk", c_q, w_qb)
        kv = jnp.einsum("bsr,rhk->bshk", latent, w_kvb)
        q_pe = q[..., nope:]
        if variant != "no_rotary":
            q_pe = _rotary(q_pe, theta, variant)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [
                kv[..., :nope],
                jnp.broadcast_to(
                    k_pe[:, :, None, :], kv.shape[:3] + k_pe.shape[-1:]
                ),
            ],
            axis=-1,
        )
        return reference_softmax_attention(
            q, k, kv[..., nope:],
            variant if variant in KERNEL_FAULTS else "",
        )

    out = jax.lax.map(
        some_heads, (by_group(layer["w_qb"]), by_group(layer["w_kvb"]))
    )  # [groups, b, s, held, v]
    out = jnp.moveaxis(out, 0, 2)
    return out.reshape(out.shape[:2] + (-1,)) @ layer["w_out"]


def in_expert_order(experts, weights):
    """A token's chosen experts in ascending order, and their weights
    in that order."""
    import jax.numpy as jnp

    order = jnp.argsort(experts, axis=-1)
    return (
        jnp.take_along_axis(experts, order, -1),
        jnp.take_along_axis(weights, order, -1),
    )


def reference_router(
    layer: dict, x, sizes: dict, variant: str = "", system=None
):
    """The published router alone on ``x`` [..., d]: float32 sigmoid
    scores over all experts, the top 4 of ``score + bias``, weights =
    the chosen scores WITHOUT the bias over their sum (+ epsilon) times
    ``routed_scaling_factor``. Returns (experts [..., top_k] in
    ascending order, their weights in that order). With
    ``system``, the sets the system chose: a near-tied token's experts
    are the system's (``near_ties.settle``), and a third result, the
    ``Ties``."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    if variant == "bf16_scores":
        scores = jax.nn.sigmoid(
            x.astype(jnp.bfloat16) @ layer["router"].astype(jnp.bfloat16)
        ).astype(jnp.float32)
    else:
        with jax.default_matmul_precision("highest"):
            scores = jax.nn.sigmoid(x @ layer["router"])
    _, chosen = jax.lax.top_k(
        scores + layer["bias"], sizes["num_experts_per_tok"]
    )
    if system is not None:
        chosen, ties = near_ties.settle(
            scores + layer["bias"], chosen, system
        )
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = picked / (
        picked.sum(-1, keepdims=True) + sizes["expert_weight_eps"]
    )
    if variant != "no_scale":
        weights = weights * sizes["routed_scaling_factor"]
    found = in_expert_order(chosen, weights)
    return found if system is None else (*found, ties)


def reference_routed_ffn(
    layer: dict, x, sizes: dict, first_expert: int | None = None,
    shared: bool = True, variant: str = "", system=None,
):
    """The published routed FFN, this share of it: the router over all
    experts, the sum over the experts chosen AND held (``first_expert
    ..`` + the number of expert weights the layer has) of weight x
    gated FFN, and (``shared``) the shared expert on every token,
    unweighted. Returns (y, rows each of ALL experts was chosen for),
    and with ``system`` the router's ``Ties``."""
    import jax
    import jax.numpy as jnp

    first = sizes["first_expert"] if first_expert is None else first_expert
    chosen, weights, *ties = reference_router(
        layer, x, sizes, variant, system
    )
    # (A scan whose body is checkpointed: one expert after another,
    # and a gradient holds one expert's float32 intermediates at a
    # time — as a Python loop the compiler runs the eight backwards
    # side by side, 2.65 GiB at the cell's row.)
    @jax.checkpoint
    def add_expert(y, expert):
        at, w1, w3, w2 = expert
        weight = jnp.where(chosen == at, weights, 0.0).sum(-1, keepdims=True)
        return y + weight * _gated(x, w1, w3, w2), None

    held = layer["w1"].shape[0]
    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (first + jnp.arange(held), layer["w1"], layer["w3"], layer["w2"]),
    )
    if shared:
        y = y + _gated(x, layer["s1"], layer["s3"], layer["s2"])
    counts = jnp.sum(
        chosen[..., None] == jnp.arange(sizes["router_width"]),
        axis=tuple(range(chosen.ndim)),
    )
    return (y, counts, *ties)


def reference_routed_vjp(
    layer: dict, x, cotangent, sizes: dict, system=None
):
    """Gradients of ``sum(y * cotangent)`` of the routed FFN with
    respect to (its weights, x), by ``jax.grad``; with ``system``
    (those gradients, the router's ``Ties``)."""
    import jax
    import jax.numpy as jnp

    def objective(weights, x):
        y, _, *ties = reference_routed_ffn(
            {**layer, **weights}, x, sizes, system=system
        )
        return jnp.sum(y * cotangent), ties

    weights = {k: layer[k] for k in ROUTED_LEAVES}
    grads, ties = jax.grad(objective, argnums=(0, 1), has_aux=True)(
        weights, x
    )
    return grads if system is None else (grads, *ties)


def reference_mixer(layer: dict, u, sizes: dict, variant: str = ""):
    """The reference's latent-attention mixer on the system's ``u``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return reference_mla(layer, u.astype(jnp.float32), sizes, variant)


def reference_mixer_vjp(layer: dict, u, cotangent, sizes: dict):
    """Gradients of ``sum(y * cotangent)`` of the mixer with respect to
    (its weights, u), by ``jax.grad``."""
    import jax
    import jax.numpy as jnp

    def objective(layer, u):
        return jnp.sum(reference_mixer(layer, u, sizes) * cotangent)

    return jax.grad(objective, argnums=(0, 1))(layer, u.astype(jnp.float32))


def reference_block(layer: dict, x, sizes: dict, variant: str = ""):
    """One block on the residual stream ``x``: ``x + mla(norm x)``,
    then ``+ FFN(norm .)``, the FFN dense or routed as the layer's
    weights say. Returns (x, the router's counts or None). The two
    halves are checkpointed apart: a gradient holds the float32
    intermediates of one of them at a time (together the module's are
    6.1 GiB at the cell's row, beside the run's train state)."""
    import jax

    eps = sizes["rms_norm_eps"]

    @jax.checkpoint
    def mixed(mixer, scale, x):
        return x + reference_mla(
            mixer, _rms_norm(x, scale, eps), sizes,
            variant if variant in MLA_FAULTS + KERNEL_FAULTS else "",
        )

    @jax.checkpoint
    def fed(layer, x):
        u = _rms_norm(x, layer["norm_ffn"], eps)
        if "router" not in layer:
            return x + _gated(u, layer["w1"], layer["w3"], layer["w2"]), None
        y, counts = reference_routed_ffn(
            layer, u, sizes,
            variant=variant
            if variant in ROUTER_FAULTS + ROUTED_FAULTS else "",
        )
        return x + y, counts

    return fed(
        {k: v for k, v in layer.items() if k != "mla"},
        mixed(layer["mla"], layer["norm_op"], x),
    )


def reference_trunk(weights: dict, inputs, sizes: dict, variant: str = ""):
    """The trunk's state BEFORE its final norm on ``inputs`` [b, s],
    and its routed layers' expert counts. Each block is checkpointed
    (a gradient holds one block's float32 intermediates)."""
    import jax
    import jax.numpy as jnp

    x = weights["embedding"][inputs].astype(jnp.float32)
    counts = []
    for layer in weights["layers"]:
        x, chosen = jax.checkpoint(
            lambda layer, x: reference_block(layer, x, sizes, variant)
        )(layer, x)
        if chosen is not None:
            counts.append(chosen)
    return x, counts


def reference_mtp(
    module: dict, embedding, trunk, next_tokens, sizes: dict,
    variant: str = "",
):
    """The prediction module (DeepSeek-V3 equations 21-23) from the
    trunk's state ``trunk`` [b, s, d] (before the final norm) and the
    token after each position: ``u = [norm_e(Emb(t_{i+1})) ;
    norm_h(h_i)] W_eh``, one block on ``u`` at the same positions, its
    own final norm. Returns (normed state, its router's counts).
    ``embedding_one_use``: no gradient back through the lookup."""
    import jax
    import jax.numpy as jnp

    eps = sizes["rms_norm_eps"]
    if variant == "embedding_one_use":
        embedding = jax.lax.stop_gradient(embedding)
    e = embedding[next_tokens].astype(jnp.float32)
    u = jnp.concatenate(
        [
            _rms_norm(e, module["norm_e"], eps),
            _rms_norm(trunk, module["norm_h"], eps),
        ],
        axis=-1,
    ) @ module["w_eh"]
    g, counts = jax.checkpoint(
        lambda layer, x: reference_block(layer, x, sizes, variant)
    )(module["block"], u)
    return _rms_norm(g, module["norm_out"], eps), counts


def reference_token_losses(hidden, table, targets, variant: str = ""):
    """Every token's cross-entropy of ``softmax(hidden @ table^T)``
    against ``targets``, ``HEAD_ROW_BLOCK`` rows at a time (a
    ``lax.map`` whose body is checkpointed: neither a pass nor a
    gradient holds more than one block's logits). ``bf16_loss``: the
    logits and their softmax in bfloat16."""
    import jax
    import jax.numpy as jnp

    shape = targets.shape
    rows = hidden.reshape(-1, hidden.shape[-1])
    block = min(HEAD_ROW_BLOCK, rows.shape[0])
    assert rows.shape[0] % block == 0

    @jax.checkpoint
    def some_rows(operands):
        x, aims = operands
        logits = x @ table.T
        if variant == "bf16_loss":
            logits = logits.astype(jnp.bfloat16)
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), aims[:, None], axis=-1
        )
        return -picked[:, 0].astype(jnp.float32)

    return jax.lax.map(
        some_rows,
        (
            rows.reshape(-1, block, rows.shape[-1]),
            targets.reshape(-1, block),
        ),
    ).reshape(shape)


def mtp_position_weights(targets):
    """A position's weight in the module's mean: 1 / (rows x (s - 1))
    where it has a target (every position of a row but the last)."""
    import jax.numpy as jnp

    batch, seq = targets.shape
    return jnp.broadcast_to(
        (jnp.arange(seq) < seq - 1).astype(jnp.float32)
        / (batch * (seq - 1)),
        targets.shape,
    )


def reference_mtp_loss(
    module: dict, embedding, table, trunk, targets, sizes: dict,
    variant: str = "",
):
    """The module's loss term (unweighted) from the trunk's state and
    the batch's ``targets``: its input ids ARE ``targets``, its targets
    ``targets`` one place further on, a row's last position without
    one. Returns (loss, (normed state, every token's loss, counts))."""
    import jax
    import jax.numpy as jnp

    state, counts = reference_mtp(
        module, embedding, trunk, targets, sizes, variant
    )
    if variant == "head_one_use":
        table = jax.lax.stop_gradient(table)
    losses = reference_token_losses(
        state, table, jnp.roll(targets, -1, axis=1)
    )
    return jnp.sum(losses * mtp_position_weights(targets)), (
        state, losses, counts
    )


def reference_loss(
    weights: dict, inputs, targets, sizes: dict, per_token: bool = False,
    variant: str = "",
):
    """Both terms of the share's loss and their sum, ``L = mean_i
    CE(logits_i, t_{i+1}) + lambda x mean_{i < s - 1} CE(logits'_i,
    t_{i+2})``: returns (L, {"main", "mtp" (each the mean, or every
    token's with ``per_token``), "counts" [routed layers, router_width],
    the module's last}). Float32, "highest" matmul precision, no
    kernel."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        trunk, counts = reference_trunk(weights, inputs, sizes, variant)
        hidden = _rms_norm(
            trunk, weights["norm_out"], sizes["rms_norm_eps"]
        )
        main = reference_token_losses(hidden, weights["head"], targets)
        mtp, (_, mtp_tokens, chosen) = reference_mtp_loss(
            weights["mtp"], weights["embedding"], weights["head"], trunk,
            targets, sizes, variant,
        )
        total = main.mean() + (
            0.0 if variant == "no_mtp_loss"
            else sizes["mtp_loss_weight"] * mtp
        )
        return total, {
            "main": main if per_token else main.mean(),
            "mtp": mtp_tokens if per_token else mtp,
            "counts": jnp.stack(counts + [chosen]),
        }


def reference_logits(weights: dict, inputs, targets, sizes: dict):
    """Both streams' logits ``[2, b, s, vocab]`` (the trunk's, the
    module's), whole: for the small sizes of the CPU tests."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        trunk, _ = reference_trunk(weights, inputs, sizes)
        hidden = _rms_norm(
            trunk, weights["norm_out"], sizes["rms_norm_eps"]
        )
        state, _ = reference_mtp(
            weights["mtp"], weights["embedding"], trunk, targets, sizes
        )
        return jnp.stack([hidden, state]) @ weights["head"].T


def reference_table_grads(
    weights: dict, inputs, targets, sizes: dict, variant: str = ""
):
    """The whole reference's loss and its gradient with respect to the
    two shared tables alone: (L, its parts, {"embedding", "head"})."""
    import jax

    def objective(tables):
        return reference_loss(
            {**weights, **tables}, inputs, targets, sizes, variant=variant
        )

    (loss, parts), grads = jax.value_and_grad(objective, has_aux=True)(
        {"embedding": weights["embedding"], "head": weights["head"]}
    )
    return loss, parts, grads


def reference_head(hidden, table, targets, variant: str = ""):
    """The untied head and next-token loss in float32 on the operands
    the system's head gets: the hidden states as handed over, the
    table rounded to their type. Returns every token's loss."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        # reduce_precision, not a cast there and back: the compiler may
        # drop such a pair.
        kind = jnp.finfo(hidden.dtype)
        table = jax.lax.reduce_precision(table, kind.nexp, kind.nmant)
        return reference_token_losses(
            hidden.astype(jnp.float32), table, targets, variant
        )


def router_disagreement(got, want):
    """(share of tokens whose expert sets differ, max |weight
    difference| over the tokens whose sets agree) of two (experts,
    weights) pairs in ascending expert order."""
    import jax.numpy as jnp

    same = jnp.all(got[0] == want[0], axis=-1)
    diff = jnp.where(same[..., None], jnp.abs(got[1] - want[1]), 0.0)
    return 1.0 - same.mean(), diff.max()


def routing_l1_share(system_held, reference_counts, sizes: dict):
    """Worst routed layer's ``sum_e |system_e - reference_e| / sum_e
    reference_e`` over the held experts."""
    import jax.numpy as jnp

    first, held = sizes["first_expert"], sizes["experts_held"]
    ref = reference_counts[:, first:first + held].astype(jnp.float32)
    diff = jnp.abs(system_held.astype(jnp.float32) - ref).sum(-1)
    return jnp.max(diff / jnp.maximum(ref.sum(-1), 1.0))


def layer_error(got, want):
    """How far a layer's output ``got`` [..., d] is from ``want``: a
    token's |got - want| over the root mean square of |want| over the
    tokens. Returns (the worst token's, the root mean square over the
    tokens)."""
    import jax.numpy as jnp

    want = want.reshape(-1, want.shape[-1])
    got = got.astype(jnp.float32).reshape(want.shape)
    err = jnp.sqrt(jnp.sum((got - want) ** 2, axis=-1))
    scale = jnp.sqrt(jnp.mean(jnp.sum(want ** 2, axis=-1)))
    scale = jnp.where(scale > 0, scale, 1.0)  # a layer that adds nothing
    return err.max() / scale, jnp.sqrt(jnp.mean(err ** 2)) / scale


def slice_error(got, want):
    """Worst |got[e] - want[e]| / |want[e]| over the leading axis."""
    import jax.numpy as jnp

    axes = tuple(range(1, want.ndim))
    diff = jnp.sqrt(jnp.sum((got.astype(jnp.float32) - want) ** 2, axes))
    norm = jnp.sqrt(jnp.sum(want ** 2, axes))
    return jnp.max(jnp.where(norm > 0, diff / norm, diff))


def row_scale_error(got, want):
    """The root mean square, over rows (the last axis), of a row's
    error ALONG the row: ``<got - want, want> / <want, want>``. A
    rounding of every element on its own (the output's, the
    probabilities') points nowhere and averages out over a row's
    lanes; a wrong scale of the row — a softmax statistic held in
    bfloat16 — is all along it."""
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    along = jnp.sum((got - want) * want, -1) / jnp.sum(want * want, -1)
    return jnp.sqrt(jnp.mean(along ** 2))


def whole_error(got, want):
    """|got - want| / |want| of one array."""
    return slice_error(got[None], want[None])


def routed_grad_errors(got, want) -> dict:
    """The system's (parameter gradients, input gradient) of a routed
    layer against the reference's: worst expert's slice of a held
    expert's leaf (a shared expert's leaf as one slice), the router
    leaf, the input."""
    import jax.numpy as jnp

    (got_w, got_x), (want_w, want_x) = got, want

    def err(name):
        g, w = _leaf(got_w, ROUTED_LEAVES[name]), want_w[name]
        return slice_error(g, w) if name[0] == "w" else whole_error(g, w)

    return {
        "expert_grad_err": jnp.max(
            jnp.stack([err(n) for n in ROUTED_LEAVES if n != "router"])
        ),
        "router_grad_err": err("router"),
        "input_grad_err": layer_error(got_x, want_x)[1],
    }


def mixer_leaf_errors(got_w, want_w) -> dict:
    """Each leaf of a latent-attention mixer: |got - want| / |want|
    (the system's tree against the reference's names)."""
    return {
        name: whole_error(_leaf(got_w, path), want_w[name])
        for name, path in MLA_LEAVES.items()
    }


def block_leaf_errors(got, want) -> dict:
    """Each leaf of a routed block (the module's): the mixer's, the two
    norms', the routed FFN's (experts by slice)."""
    errors = {
        f"mla.{k}": v
        for k, v in mixer_leaf_errors(got["mla"], want["mla"]).items()
    }
    for name, module in (("norm_op", 0), ("norm_ffn", 1)):
        errors[name] = whole_error(
            got[BLOCK_NORMS[module]]["scale"], want[name]
        )
    for name, path in ROUTED_LEAVES.items():
        g, w = _leaf(got["moe"], path), want[name]
        errors[f"moe.{name}"] = (
            slice_error(g, w) if name[0] == "w" else whole_error(g, w)
        )
    return errors


def module_leaf_errors(got, want, sizes: dict) -> dict:
    """Each leaf of the prediction module's own."""
    errors = {
        "norm_e": whole_error(got["enorm"]["scale"], want["norm_e"]),
        "norm_h": whole_error(got["hnorm"]["scale"], want["norm_h"]),
        "w_eh": whole_error(got["eh_proj"]["kernel"], want["w_eh"]),
        "norm_out": whole_error(got["norm"]["scale"], want["norm_out"]),
    }
    errors.update(
        {
            f"block.{k}": v
            for k, v in block_leaf_errors(
                got[f"layer_{sizes['num_hidden_layers']}"], want["block"]
            ).items()
        }
    )
    return errors


def routed_check(built: dict, sizes: dict):
    """The program of comparisons 5 and 6 for ONE routed layer:
    ``check(reference layer, the system's layer parameters, the
    system's input x [tokens, d], its output y, the experts its router
    chose)``. Without the experts the reference routes for itself
    alone, as before PR 62."""
    import jax
    import jax.numpy as jnp

    def check(layer, moe_params, x, y, experts=None):
        first = x[: sizes["sequence_length"]]
        first32 = first.astype(jnp.float32)
        got = built["routed_vjp"](
            moe_params, first, first32, sets=experts is not None
        )
        with jax.default_matmul_precision("highest"):
            want, _, *ties = reference_routed_ffn(
                layer, x.astype(jnp.float32), sizes, system=experts
            )
            if experts is None:
                grads = reference_routed_vjp(layer, first32, first32, sizes)
            else:  # the backward on the sets ITS system side chose
                got, own = got
                grads, back = reference_routed_vjp(
                    layer, first32, first32, sizes, system=own
                )
                ties.append(back)
        token, rms = layer_error(y, want)
        return {
            "routed_token_err": token, "routed_rms_err": rms,
            **routed_grad_errors(got, grads),
            **near_ties.worst(*ties),
        }

    return check


def mixer_check(built: dict, sizes: dict):
    """Comparisons 5 and 6 for the mixer: ``check(reference mixer, the
    system's mixer parameters, the system's input u [1, seq, d], its
    output y)``. Four programs, one after another: the reference's
    forward, the system's gradients, the reference's, the comparison —
    the device holds the run's train state beside them."""
    import jax
    import jax.numpy as jnp

    def forward(layer, u, y):
        return layer_error(y, reference_mixer(layer, u, sizes))

    def system(mixer_params, u):
        return built["mixer_vjp"](mixer_params, u, u.astype(jnp.float32))

    def reference(layer, u):
        return reference_mixer_vjp(layer, u, u.astype(jnp.float32), sizes)

    def compare(got, want):
        leaves = mixer_leaf_errors(got[0], want[0])
        return {
            "mla_param_grad_err": jnp.max(jnp.stack(list(leaves.values()))),
            # dq through both bottleneck matrices, and the one k_pe's
            # gradient summed over the heads (kv_a's last columns).
            "mla_q_a_grad_err": leaves["w_qa"],
            "mla_k_pe_grad_err": whole_error(
                _leaf(got[0], MLA_LEAVES["w_kva"])[
                    :, sizes["kv_lora_rank"]:
                ],
                want[0]["w_kva"][:, sizes["kv_lora_rank"]:],
            ),
            "mla_input_grad_err": layer_error(got[1], want[1])[1],
        }

    def check(layer, mixer_params, u, y):
        token, rms = jax.jit(forward)(layer, u, y)
        errors = jax.jit(compare)(
            jax.jit(system)(mixer_params, u), jax.jit(reference)(layer, u)
        )
        return {"mla_token_err": token, "mla_rms_err": rms, **errors}

    return check


def mtp_check(built: dict, sizes: dict):
    """Comparisons 5 and 6 for the prediction module alone:
    ``check(reference weights, the system's params, the trunk's state
    [1, seq, d], targets [1, seq], the module's normed output as the
    whole model gave it)``: its output, its loss term, and the loss's
    gradients with respect to its own leaves, the trunk's state, the
    embedding table and the output table."""
    import jax
    import jax.numpy as jnp

    def system(params, trunk, targets):
        return built["mtp_alone"](
            params["mtp"], params["embed"]["embedding"], params["lm_head"],
            trunk, targets,
        )

    def reference(weights, trunk, targets, variant=""):
        def objective(module, embedding, table, trunk):
            loss, (state, _, _) = reference_mtp_loss(
                module, embedding, table, trunk, targets, sizes, variant
            )
            return loss, state

        with jax.default_matmul_precision("highest"):
            (loss, state), grads = jax.value_and_grad(
                objective, argnums=(0, 1, 2, 3), has_aux=True
            )(
                weights["mtp"], weights["embedding"], weights["head"],
                trunk.astype(jnp.float32),
            )
        return state, loss, grads

    def compare(got, want, as_run, whole):
        (got_out, got_loss, got_g), (want_out, want_loss, want_g) = got, want
        token, rms = layer_error(got_out, want_out)
        leaves = module_leaf_errors(got_g[0], want_g[0], sizes)
        routers = {
            k: v for k, v in leaves.items()
            if k.split(".")[-1] in ("router", "w1", "w3", "w2")
        }
        found = {
            "mtp_token_err": token,
            "mtp_rms_err": rms,
            "mtp_alone_loss_rel": jnp.abs(got_loss - want_loss)
            / jnp.abs(want_loss),
            "mtp_leaf_grad_err": jnp.max(jnp.stack(
                [v for k, v in leaves.items() if k not in routers]
            )),
            "mtp_routed_grad_err": jnp.max(jnp.stack(list(routers.values()))),
            "mtp_embedding_grad_err": whole_error(got_g[1], want_g[1]),
            "mtp_head_grad_err": whole_error(got_g[2], want_g[2]),
            "mtp_trunk_grad_err": layer_error(got_g[3], want_g[3])[1],
            **{f"mtp_grad.{k}": v for k, v in leaves.items()},
        }
        if as_run is not None:
            # The module inside the whole model is the module alone.
            found["mtp_as_run_rms_err"] = layer_error(as_run, want_out)[1]
        if whole is not None:
            # Comparison 7's sharper half. What the module's use adds to
            # the WHOLE model's gradient of a table is lambda times the
            # module's own gradient of it (the trunk's state held): how
            # much of that the system's whole gradient holds, 1 when
            # the use is there and 0 when it sent nothing back. The
            # system's rounding is no part of it: it does not point
            # along the use.
            system_tables, reference_tables = whole
            for name, use in (("embedding", want_g[1]), ("head", want_g[2])):
                use = sizes["mtp_loss_weight"] * use
                found[f"{name}_second_use"] = 1.0 + jnp.sum(
                    (system_tables[name] - reference_tables[name]) * use
                ) / jnp.sum(use * use)
        return found

    def check(weights, params, trunk, targets, as_run=None, whole=None,
              variant=""):
        """``variant`` (glm_precision.py): the faulty reference takes
        the system's place."""
        want = jax.jit(reference)(weights, trunk, targets)
        if variant:
            got = jax.jit(
                reference, static_argnums=3, compiler_options=AS_STATED
            )(weights, trunk, targets, variant)
            # Its gradients under the system's names.
            module = params["mtp"]
            at = f"layer_{sizes['num_hidden_layers']}"
            grads = got[2][0]
            tree = {
                "enorm": {"scale": grads["norm_e"]},
                "hnorm": {"scale": grads["norm_h"]},
                "eh_proj": {"kernel": grads["w_eh"]},
                "norm": {"scale": grads["norm_out"]},
                at: system_block_tree(grads["block"], module[at]),
            }
            got = (got[0], got[1], (tree,) + got[2][1:])
        else:
            got = jax.jit(system)(params, trunk, targets)
        return jax.jit(compare)(got, want, as_run, whole)

    return check


def system_block_tree(block: dict, like) -> dict:
    """A reference block's weights (or their gradients) under the
    system's names; ``like``: the system's block, for what the
    reference does not hold (the bias buffer)."""
    def put(tree, path, leaf):
        for key in path[:-1]:
            tree = tree.setdefault(key, {})
        tree[path[-1]] = leaf

    out = {
        BLOCK_NORMS[0]: {"scale": block["norm_op"]},
        BLOCK_NORMS[1]: {"scale": block["norm_ffn"]},
        "mla": {}, "moe": {"expert_bias": like["moe"]["expert_bias"]},
    }
    for name, path in MLA_LEAVES.items():
        put(out["mla"], path, block["mla"][name])
    for name, path in ROUTED_LEAVES.items():
        put(out["moe"], path, block[name])
    return out


def kernel_operands(sizes: dict, seed: int):
    """q, k ``[1, KERNEL_HEADS, seq, nope + pe]`` and v ``[.., v]``
    from the seed: unit normal (logits of unit variance), rounded to
    the compute dtype (bfloat16 in the cell)."""
    import jax
    import jax.numpy as jnp

    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    shape = (1, KERNEL_HEADS, sizes["sequence_length"])
    keys = jax.random.split(jax.random.key(seed), 3)
    return tuple(
        jax.random.normal(key, shape + (width,), jnp.float32).astype(
            sizes.get("compute_dtype", "bfloat16")
        )
        for key, width in zip(keys, (qk, qk, sizes["v_head_dim"]))
    )


def kernel_check(built: dict, sizes: dict, seed: int, variant: str = ""):
    """Comparison 8: the flash kernels alone on bfloat16 operands
    against the dense masked softmax on the same values in float32,
    forward and dq / dk / dv of ``sum(out * q)``. With ``variant`` (of
    ``KERNEL_FAULTS``) the faulty reference takes the system's place."""
    import jax
    import jax.numpy as jnp

    def reference(q, k, v, variant=""):
        def objective(q, k, v):
            with jax.default_matmul_precision("highest"):
                out = reference_softmax_attention(
                    *(jnp.swapaxes(t, 1, 2) for t in (q, k, v)), variant
                )
            out = jnp.swapaxes(out, 1, 2)
            return jnp.sum(out * jax.lax.stop_gradient(q)), out

        grads, out = jax.grad(objective, argnums=(0, 1, 2), has_aux=True)(
            *(t.astype(jnp.float32) for t in (q, k, v))
        )
        return out, grads

    def compare(got, want):
        (got_out, got_grads), (want_out, want_grads) = got, want
        return {
            "kernel_row_scale_err": row_scale_error(got_out, want_out),
            "kernel_out_rms_err": layer_error(got_out, want_out)[1],
            "kernel_grad_rms_err": jnp.max(
                jnp.stack(
                    [
                        layer_error(g, w)[1]
                        for g, w in zip(got_grads, want_grads)
                    ]
                )
            ),
        }

    operands = jax.jit(lambda: kernel_operands(sizes, seed))()
    want = jax.jit(reference)(*operands)
    if variant:
        got = jax.jit(lambda q, k, v: reference(q, k, v, variant))(*operands)
    else:
        got = jax.jit(built["flash_kernels"])(*operands)
    return {k: float(v) for k, v in jax.jit(compare)(got, want).items()}


def layer_checks(
    built: dict, params, load: dict, sample: dict, predicted, whole,
    sizes: dict,
) -> dict:
    """Comparisons 5 and 6: every routed layer (the module's last), the
    checked mixer and the module alone, forward and backward, each on
    the system's own inputs. One program a layer kind, so that no two
    layers' float32 intermediates are alive together."""
    import jax

    weights = reference_weights(params, sizes)
    module_at = sizes["num_hidden_layers"]
    routed = jax.jit(routed_check(built, sizes))
    blocks = [
        (weights["layers"][i], params[f"layer_{i}"]["moe"])
        for i in routed_layers(sizes)
    ] + [(weights["mtp"]["block"], params["mtp"][f"layer_{module_at}"]["moe"])]
    found = [
        routed(
            layer, moe, load["inputs"][i], load["outputs"][i],
            load["experts"][i],
        )
        for i, (layer, moe) in enumerate(blocks)
    ]
    worst = near_ties.worst_layer(found)
    for name in ("inputs", "outputs"):
        del load[name]
    at = checked_mixer(sizes)
    u, y = load.pop("mla")
    worst.update(
        {
            k: float(v)
            for k, v in mixer_check(built, sizes)(
                weights["layers"][at]["mla"], params[f"layer_{at}"]["mla"],
                u[:1], y[:1],
            ).items()
        }
    )
    worst.update(
        {
            k: float(v)
            for k, v in mtp_check(built, sizes)(
                weights, params, load["trunk"][:1], sample["targets"][:1],
                predicted[:1], whole,
            ).items()
        }
    )
    return worst


# The TPU compiler's default (``xla_allow_excess_precision``) keeps a
# value in float32 where the program rounds it to bfloat16 on the way
# to the next operation (the final norm's output on its way into the
# head: 1e-2 nats a token). More precision than stated is no fault, but
# a comparison layer by layer needs what a layer CONSUMED to be what
# the capture shows: the model's program of the comparisons is compiled
# as stated, as kimi-linear-48b-a3b's. The two loss terms and the
# tables' gradients take the trainer's own ``loss_fn`` under the
# default, as the step does.
AS_STATED = {"xla_allow_excess_precision": False}


def reference_check(built: dict, params, dataset: dict, sizes: dict) -> dict:
    """The system against the plain reference on the run's own weights
    and a sample of the seeded data, both computed on this device: both
    loss terms and their sum, the head token by token on both streams,
    every router token by token on the system's own inputs, the
    routed layers' per-expert row counts, every routed layer, a mixer
    and the prediction module alone, forward and backward
    (``layer_checks``), the whole model's gradient of each shared table
    (``table_grads``) and the flash kernels alone (``kernel_check``)."""
    import jax
    import jax.numpy as jnp

    sample = {
        k: jnp.asarray(v[:REFERENCE_SEQUENCES]) for k, v in dataset.items()
    }
    hidden, token_losses, load = (
        jax.jit(built["head_io"])
        .lower(params, sample, jax.random.key(0))
        .compile(compiler_options=AS_STATED)
    )(params, sample, jax.random.key(0))
    step_loss, step_parts, step_tables = jax.jit(built["table_grads"])(
        params, sample, jax.random.key(0)
    )
    targets = jnp.stack(
        [sample["targets"], jnp.roll(sample["targets"], -1, axis=1)]
    )
    # Once more from the hidden states alone, outside the model's
    # program: what the loss streams is what the model hands over.
    alone = jnp.stack([
        jax.jit(built["head_losses"])(params, hidden[i], targets[i])
        for i in range(2)
    ])
    weights = reference_weights(params, sizes)
    loss, parts, tables = jax.jit(
        lambda weights, sample: reference_table_grads(
            weights, sample["inputs"], sample["targets"], sizes
        )
    )(weights, sample)

    # Everything is an argument: data closed over would be constants of
    # the program and make its compile-cache key follow the seed.
    def compare(
        weights, sample, hidden, targets, token_losses, alone, load,
        step, reference,
    ):
        step_loss, step_parts, step_tables = step
        loss, parts, tables = reference
        # The module's last position has no target: out of the maximum.
        counted = jnp.stack([
            jnp.ones(sample["targets"].shape, bool),
            mtp_position_weights(sample["targets"]) > 0,
        ])
        head_losses = jnp.stack([
            reference_head(hidden[i], weights["head"], targets[i])
            for i in range(2)
        ])
        assignments = sample["inputs"].size * sizes["num_experts_per_tok"]
        routers = [
            layer for layer in weights["layers"] if "router" in layer
        ] + [weights["mtp"]["block"]]
        set_mismatch, weight_err = zip(
            *(
                router_disagreement(
                    in_expert_order(
                        load["experts"][i], load["weights"][i]
                    ),
                    reference_router(layer, load["inputs"][i], sizes),
                )
                for i, layer in enumerate(routers)
            )
        )

        def rel(got, want):
            return jnp.abs(got - want) / jnp.abs(want)

        return {
            "routers": len(routers),
            "router_set_mismatch_share": jnp.max(jnp.stack(set_mismatch)),
            "router_weight_err": jnp.max(jnp.stack(weight_err)),
            "system_loss": step_loss,
            "system_main_loss": step_parts["main"],
            "system_mtp_loss": step_parts["mtp"],
            "as_stated_loss": token_losses[0].mean(),
            "reference_loss": loss,
            "reference_main_loss": parts["main"],
            "reference_mtp_loss": parts["mtp"],
            "rel_diff": rel(step_loss, loss),
            "main_rel_diff": rel(step_parts["main"], parts["main"]),
            "mtp_rel_diff": rel(step_parts["mtp"], parts["mtp"]),
            "head_token_loss_err": jnp.maximum(
                jnp.max(
                    jnp.where(counted, jnp.abs(token_losses - head_losses), 0)
                ),
                jnp.max(jnp.where(counted, jnp.abs(alone - head_losses), 0)),
            ),
            "embedding_table_grad_err": whole_error(
                step_tables["embedding"], tables["embedding"]
            ),
            "head_table_grad_err": whole_error(
                step_tables["head"], tables["head"]
            ),
            "routing_l1_share": routing_l1_share(
                load["held_rows"], parts["counts"], sizes
            ),
            "rows_dropped": jnp.sum(load["dropped"]),
            "rows_unaccounted": jnp.sum(
                jnp.abs(
                    load["held_rows"].sum(-1) + load["left_out"]
                    - assignments
                )
            ),
            "shared_rows_missing": jnp.sum(
                jnp.abs(load["shared_rows"] - sample["inputs"].size)
            ),
            "held_rows_max_over_mean": jnp.max(
                load["held_rows"].max(-1)
                / jnp.maximum(load["held_rows"].mean(-1), 1.0)
            ),
        }

    small = {
        k: load[k]
        for k in (
            "experts", "weights", "inputs", "held_rows", "left_out",
            "dropped", "shared_rows",
        )
    }
    result = {
        k: float(v)
        for k, v in jax.jit(compare)(
            weights, sample, hidden, targets, token_losses, alone, small,
            (step_loss, step_parts, step_tables), (loss, parts, tables),
        ).items()
    }
    # What the later programs need room for: a routed layer's captured
    # rows are let go as soon as they have been compared
    # (``layer_checks``).
    del small
    result.update(layer_checks(
        built, params, load, sample, hidden[1], (step_tables, tables), sizes
    ))
    del tables, step_tables
    result.update(
        kernel_check(built, sizes, int(sample["inputs"][0, 0]))
    )
    result.update(
        rtol=REFERENCE_RTOL,
        head_atol=HEAD_TOKEN_LOSS_ATOL,
        routing_tol=ROUTING_L1_SHARE,
        router_set_tol=ROUTER_SET_MISMATCH_SHARE,
        router_weight_atol=ROUTER_WEIGHT_ATOL,
        near_tie_margin=near_ties.NEAR_TIE_MARGIN,
        layer_limits=LAYER_LIMITS,
        grad_limits=[EXPERT_GRAD_RTOL, ROUTER_GRAD_RTOL, INPUT_GRAD_RMS],
        mixer_grad_limits=list(MIXER_GRAD_LIMITS),
        mtp_grad_limits=MTP_GRAD_LIMITS,
        table_grad_rtol=TABLE_GRAD_RTOL,
        second_use_tol=SECOND_USE_TOL,
        kernel_limits=[KERNEL_RMS_LIMIT, KERNEL_ROW_SCALE_LIMIT],
    )
    result["ok"] = bool(
        np.isfinite(result["system_loss"])
        and within_limits(result)
        and near_ties.within(
            result, ROUTER_SET_MISMATCH_SHARE, sample["inputs"].size
        )
    )
    return result


def within_limits(result: dict) -> bool:
    """Whether every number ``reference_check`` compared lies inside
    its limit (also what ``glm_precision.py`` asks of a faulty
    reference's readings, which must NOT)."""
    return bool(
        all(
            result[k] <= REFERENCE_RTOL
            for k in ("rel_diff", "main_rel_diff", "mtp_rel_diff")
        )
        and result["head_token_loss_err"] <= HEAD_TOKEN_LOSS_ATOL
        and result["router_set_mismatch_share"] <= ROUTER_SET_MISMATCH_SHARE
        and result["router_weight_err"] <= ROUTER_WEIGHT_ATOL
        and result["routing_l1_share"] <= ROUTING_L1_SHARE
        and result["rows_dropped"] == 0
        and result["rows_unaccounted"] == 0
        and result["shared_rows_missing"] == 0
        and all(
            result[f"{kind}_token_err"] <= token
            and result[f"{kind}_rms_err"] <= rms
            for kind, (token, rms) in LAYER_LIMITS.items()
        )
        and result["mtp_as_run_rms_err"] <= LAYER_LIMITS["mtp"][1]
        and result["expert_grad_err"] <= EXPERT_GRAD_RTOL
        and result["router_grad_err"] <= ROUTER_GRAD_RTOL
        and result["input_grad_err"] <= INPUT_GRAD_RMS
        and all(
            result[f"mla_{leaf}_grad_err"] <= MIXER_GRAD_LIMITS[0]
            for leaf in ("param", "q_a", "k_pe")
        )
        and result["mla_input_grad_err"] <= MIXER_GRAD_LIMITS[1]
        and result["mtp_alone_loss_rel"] <= REFERENCE_RTOL
        and all(
            result[f"mtp_{name}_grad_err"] <= limit
            for name, limit in MTP_GRAD_LIMITS.items()
        )
        and all(
            result[f"{name}_table_grad_err"] <= limit
            and abs(result[f"{name}_second_use"] - 1.0) <= SECOND_USE_TOL
            for name, limit in TABLE_GRAD_RTOL.items()
        )
        and result["kernel_out_rms_err"] <= KERNEL_RMS_LIMIT
        and result["kernel_grad_rms_err"] <= KERNEL_RMS_LIMIT
        and result["kernel_row_scale_err"] <= KERNEL_ROW_SCALE_LIMIT
    )
