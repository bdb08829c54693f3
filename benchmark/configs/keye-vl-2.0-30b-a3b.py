"""keye-vl-2.0-30b-a3b: builder of the system under test, and its plain
reference.

One chip's share of Keye-VL-2.0-30B-A3B's language model under expert
parallelism over eight chips (``keye-vl-2.0-30b-a3b.json``: published
widths, 4 of 48 layers, 16 of each layer's 128 experts held, an eighth
of both vocabulary tables). The system side goes through the program's
own entry points (``TransformerConfig`` / ``TransformerLM`` with the
options the configuration forced, the Pallas kernels of
``adaptdl_tpu/ops/sparse_attention.py`` and ``grouped_matmul.py``,
``ElasticTrainer``). The reference side is written from the layer
equations of the JSON (``assumed`` says which are assumed) in plain
float32 ``jax.numpy`` at "highest" matmul precision and imports
nothing from ``adaptdl_tpu``: index scores and attention by query
blocks against all earlier keys with a boolean membership mask from
``lax.top_k``, experts as a Python loop over the held ones with a
boolean mask, no kernel, no remat, the same share, ``jax.grad`` for
gradients.
"""

from __future__ import annotations

import numpy as np

from benchmark import near_ties

# Seven comparisons decide ``correct`` (reference_check), on the run's
# own weights at the published widths on ONE row of the timed length
# (16 384 tokens) of the run's own data. FORWARD, every one is of the
# model AS IT RAN: one evaluation of the whole model (``head_io``)
# hands over, layer by layer, what each router consumed and chose, what
# each routed layer gave, the six operands each sparse mixer handed its
# kernels, every token's L_I, each mixer's output and the final hidden
# states; the reference gets the same captured inputs. That program is
# compiled AS STATED (``as_stated`` says why: under the compiler's
# default a layer consumes more precision than the capture of its input
# shows); the whole model under the default, as the step runs it, is
# held by its two losses (comparison 1). BACKWARD, a model cannot hand
# over one layer's gradients: the routed layer, the kernels and the
# mixer run alone on the captured inputs, under the step's own compiler
# defaults, against ``jax.grad`` of the reference. The selection is a
# DISCRETE choice of 2048 among thousands, where a near-tie flips
# membership between bfloat16 operands and float32: so the kernels are
# compared ON THE OPERANDS THE MODEL HANDED THEM and the sets they
# applied, where nothing can flip and what is compared is the
# arithmetic, not the operands' rounding. No limit stands nearer than
# 1.5 x above its worst reading (my chip runs, PR 34, TPU v5 lite;
# PERF.md section 6 says which seeds read which): "first" is what the
# system gave, "second" what the reference computed in lower precision
# on purpose gave against the reference itself
# (benchmark/tests/keye_precision.py prints both). An rms averages
# 16 384 tokens and moves by a hundredth between seeds; a worst token
# is a maximum with a heavy tail, so it is taken as a share of the
# token's OWN norm where that is larger (``layer_error``) and its
# limits (3 to 4 x the worst reading) are for wrong mathematics, not
# for precision.
#
# 1. Whole model: |system - reference| / reference of the mean L_LM
#    over the row and of L_I, the mean over layers and tokens (the two
#    sides' sets differ at near-ties), from the trainer's own
#    ``loss_fn`` under the compiler's defaults (first 1.8e-7 .. 2.2e-5
#    and 5.8e-5 .. 2.1e-4) and from the model compiled as stated
#    (2.4e-6 .. 2.1e-5 and 2.8e-4 .. 4.1e-4).
LM_LOSS_RTOL = 1e-4
INDEX_LOSS_RTOL = 3e-3
# 2. The head, token by token: alone on the hidden states the model
#    hands to it (the untied table rounded to bfloat16, float32
#    accumulation, logits, softmax and loss) against the reference on
#    the same: first 9.5e-7 on every seed; logits rounded to bfloat16
#    read ~1e-2 (PR 30's reading of the same head arithmetic). And the
#    head INSIDE the model against the head alone: equal to the last
#    bit (under the compiler's default 1.9e-2 .. 2.3e-2 nats: the final
#    norm takes the residual stream unrounded).
HEAD_TOKEN_LOSS_ATOL = 1e-4  # max |token loss - reference|, nats
HEAD_IN_MODEL_GAP = 1e-4  # max |token loss in the model - alone|, nats
# 3. Every router INSIDE the model, token by token, on the input the
#    capture shows: float32 softmax over 128 at "highest", top 8,
#    divided by their sum. First: not one of 4 x 16 384 tokens chose
#    another set, weights equal to the last bit, on every seed (under
#    the compiler's default 4.0e-2 .. 4.1e-2 of them do, because the
#    router is then handed the norm's output unrounded: ``as_stated``).
#    bfloat16 logits: 3.3e-2 .. 3.7e-2 of the tokens choose another
#    set (8 of 128 at fresh weights lie close), weights off by 2.9e-3
#    .. 4.1e-3: REFUSED by both.
ROUTER_SET_MISMATCH_SHARE = 1e-3  # tokens whose eight experts differ
ROUTER_WEIGHT_ATOL = 2e-5  # max |weight - reference|, sets agreeing
# Exactly: no row dropped, held + left-out rows = tokens x 8 per layer.
# 4. Every routed layer, the reference's expert weights rounded to
#    bfloat16 as the configuration states its operands
#    (``operands_as_stated``). Forward, the layer's output INSIDE the
#    model (``layer_error``: worst token, rms over tokens): first 7.9e-3
#    .. 8.5e-3, rms 5.377e-3 .. 5.393e-3 (compiled as stated, gate, up,
#    their product, the rows' results and the combine each round to
#    bfloat16; the same layer alone under the compiler's default read
#    6.3e-3 .. 7.5e-3 / 4.21e-3 .. 4.23e-3). Backward, the layer alone
#    (``routed_grad_errors``): worst expert's weight-gradient slice
#    2.77e-3 .. 3.15e-3, router 2.48e-3 .. 2.84e-3, input 4.46e-3 ..
#    4.49e-3. Second (partial sums of 256 rounded to and added in
#    bfloat16, on the row's first quarter): rms 5.63e-3 .. 5.68e-3 and
#    input 5.07e-3 .. 5.16e-3, both BELOW 1.5 x the first readings,
#    which are the system's own stated roundings; weight-gradient slice
#    5.23e-3 .. 5.82e-3 and router 4.63e-3 .. 5.42e-3: REFUSED by
#    those two, which stand 1.52 x and 1.51 x over their worst first
#    readings and 8% under their second.
ROUTED_LIMITS = (0.03, 0.0085)
EXPERT_GRAD_RTOL = 0.0048
ROUTER_GRAD_RTOL = 0.0043
INPUT_GRAD_RMS = 0.007
# 5. The indexer INSIDE the model. (a) Its projections with those of
#    attention: the operands the model's mixer handed its kernels (q,
#    k, v, qI, kI, w) against the reference's float32 projections of
#    the captured block input (``layer_error``, the worst of the six):
#    first 5.5e-3 .. 6.4e-3 / rms 3.794e-3 .. 3.796e-3, bfloat16's rounding of the
#    projections' results, of the head norms' and of the operands. (b)
#    The index scores I and the selection ON THOSE OPERANDS: scores as
#    ``layer_error`` over query rows; the share of the reference's
#    memberships the system's sets lack; every disputed key's
#    REFERENCE score within INDEX_DISPUTE_GAP (in units of the layer's
#    rms score) of that query's 2048th reference score. First: the
#    kernel's scores EQUAL the reference's (0 on every seed and layer:
#    products of bfloat16 operands are exact in float32 and both add 64
#    of them in one order), so not one of 31.5 M memberships a layer
#    differs. Scores rounded to and accumulated in bfloat16: rows
#    6.8e-3 .. 8.3e-3, rms 2.34e-3 .. 2.35e-3, 1.2e-3 of the
#    memberships differ, gaps 1.7e-2 .. 2.3e-2: REFUSED by all four.
PROJECTION_LIMITS = (0.02, 0.006)
INDEX_SCORE_LIMITS = (1e-3, 2e-4)
INDEX_SET_MISMATCH_SHARE = 1e-4
INDEX_DISPUTE_GAP = 2e-3
# 6. Sparse attention GIVEN THE SYSTEM'S OWN SETS. (a) The kernels on
#    the operands the model handed them: the output token by token, as
#    the step gets it and as the forward kernel's float32 accumulator
#    holds it before its last rounding (``out_dtype``); dq, dk, dv of
#    ``sum(out * q)`` as ``layer_error``'s rms, the worst; L_I token
#    by token; dqI, dkI, dw of ``mean(L_I)``, each |diff| /
#    |reference|, the worst; against ``jax.grad`` of the reference over
#    the same operands and sets. First: out worst token 2.29e-3 ..
#    2.36e-3, rms 2.047e-3 .. 2.071e-3 (the probabilities and the
#    output leave in bfloat16); unrounded 1.53e-3 .. 1.60e-3, rms
#    1.247e-3 .. 1.266e-3 (the probabilities' rounding alone); dq / dk
#    / dv 2.51e-3 .. 2.58e-3 (they read 1.1e-2 .. 1.7e-2 while the
#    embedding was flax's 1 / d: the residual stream was then the
#    attention's token-independent mean, every v nearly the same, and
#    dS = P (dP - delta) cancelled to its rounding; ``build`` says what
#    was cured), L_I 2.3e-5 .. 4.5e-5 nats (a maximum over tokens), its
#    gradients 1.87e-3 .. 1.91e-3. Partial sums over 256 keys rounded
#    to and added in bfloat16: out rms 2.43e-3 .. 2.46e-3, 1.18 x the
#    ROUNDED output's first reading and under its limit (the last
#    rounding is as large as the fault), 1.94 x the unrounded one's:
#    REFUSED by ``kernel_f32_rms_err``, 25% over its limit, which
#    stands 1.54 x over its worst first reading. (b) The whole mixer.
#    Forward, its output and every token's L_I INSIDE the model against
#    the reference over the sets the model's kernels applied: first
#    worst token 6.9e-3 .. 7.4e-3, rms 5.941e-3 .. 6.006e-3, L_I 1.7e-3
#    .. 4.6e-3 nats (a maximum over tokens). Backward and its own
#    forward, the mixer alone on the captured block input: output; the
#    gradients of ``sum(y * input)`` to the q / kv / out projections
#    and to the input; L_I per token and its gradient to W_qI, W_kI,
#    W_w. First: worst token 6.2e-3 .. 6.5e-3, rms 5.21e-3 .. 5.29e-3,
#    5.25e-3 .. 5.35e-3, 6.60e-3 .. 6.67e-3, 1.2e-3 .. 4.2e-3 nats,
#    1.61e-2 .. 2.62e-2 (three leaves a layer, each |diff| /
#    |reference|: a maximum, and no control needs it narrow).
KERNEL_LIMITS = (0.01, 0.00315)
KERNEL_F32_LIMITS = (0.005, 0.00195)
KERNEL_QKV_GRAD_RMS = 0.005
KERNEL_INDEX_LOSS_ATOL = 2e-4
KERNEL_INDEX_GRAD_RTOL = 0.004
MIXER_LIMITS = (0.03, 0.0095)
SPARSE_LIMITS = (0.03, 0.0085)
SPARSE_PARAM_GRAD_RTOL = 0.009
SPARSE_INPUT_GRAD_RMS = 0.011
INDEX_LOSS_TOKEN_ATOL = 0.015  # max |L_I[t] - reference|, nats
INDEX_GRAD_RTOL = 0.05
# 7. No gradient of L_LM reaches the indexer's parameters and none of
#    L_I leaves them: exact zeros, at the kernels (neither cotangent
#    reaches the other's operands) and in the mixer alone (the
#    indexer's input is stopped), on every layer.
REFERENCE_SEQUENCES = 1
QUERY_BLOCK = 128  # queries of one step of the reference's scan


def units_per_sample(sizes: dict) -> int:
    return int(sizes["sequence_length"])


def selected_pairs_per_row(seq_len: int, topk: int) -> int:
    """Pairs the selection keeps in one row (the yardstick's count)."""
    from benchmark.sparse_attention import selected_pairs

    return selected_pairs(seq_len, topk)


def forward_flops_per_token(sizes: dict) -> dict[str, float]:
    """Forward matmul FLOPs per token, by part: 2 FLOPs per
    multiply-accumulate; the index scores over the CAUSAL pairs at the
    timed length, attention over the SELECTED pairs only (pairs the
    model does not use earn nothing, whatever a kernel multiplies);
    routed experts at UNIFORM routing (``num_experts_per_tok x
    experts_held / router_width`` experts a token a layer); no
    recomputation."""
    d, layers = sizes["hidden_size"], sizes["num_hidden_layers"]
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head_dim, seq = sizes["head_dim"], sizes["sequence_length"]
    sa = sizes["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    selected = selected_pairs_per_row(seq, sa["topk"]) / seq
    causal = (seq + 1) / 2
    per_token_experts = (
        sizes["num_experts_per_tok"] * sizes["experts_held"]
        / sizes["router_width"]
    )
    return {
        "attention_projections": float(
            layers * 2 * d * head_dim * (2 * heads + 2 * kv_heads)
        ),
        "indexer_projections": float(
            layers * 2 * d * (ih * idim + idim + ih)
        ),
        "index_scores": float(layers * 2 * ih * idim * causal),
        "attention_scores": float(
            layers * 2 * 2 * heads * head_dim * selected
        ),
        "router": float(layers * 2 * d * sizes["router_width"]),
        "routed_experts": float(
            layers * per_token_experts * 2 * 3 * d
            * sizes["moe_intermediate_size"]
        ),
        "head": float(2 * d * sizes["vocab_size"]),
    }


def train_flops_per_unit(sizes: dict) -> float:
    """Forward + backward (3x forward) model FLOPs per trained token."""
    return 3.0 * sum(forward_flops_per_token(sizes).values())


def make_dataset(sizes: dict, seed: int, samples: int) -> dict:
    """Packed token rows from the seed, as the other configurations':
    documents of lognormal length (median ~400 tokens), each an
    arithmetic progression modulo the vocabulary SLICE with its own
    start and stride, packed back to back into rows of
    ``sequence_length + 1`` tokens, no padding."""
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


def model_config(sizes: dict):
    """The ``TransformerConfig`` of these sizes."""
    import jax.numpy as jnp

    from adaptdl_tpu.models import TransformerConfig

    sa = sizes["sa_config"]
    return TransformerConfig(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        d_model=sizes["hidden_size"],
        d_ff=sizes["intermediate_size"],
        max_seq_len=sizes["sequence_length"],
        dtype=jnp.dtype(sizes.get("compute_dtype", "bfloat16")).type,
        remat=True,
        norm="rmsnorm",
        norm_eps=sizes["rms_norm_eps"],
        ffn="swiglu",
        qk_norm=True,
        rope_theta=float(sizes["rope_theta"]),
        layer_types=("sparse_attention",) * sizes["num_hidden_layers"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        experts_total=sizes["router_width"],
        experts_held=sizes["experts_held"],
        first_expert=sizes["first_expert"],
        experts_top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["moe_intermediate_size"],
        experts_router="softmax",
        expert_weight_eps=sizes["expert_weight_eps"],
        tie_embeddings=sizes["tie_word_embeddings"],
    )


def build(sizes: dict, geometry: dict, seed: int) -> dict:
    """The system under test for one cell: model, weights made on the
    device in one jitted call from the seed, loss, trainer."""
    import jax
    import jax.numpy as jnp
    import optax

    from adaptdl_tpu.models.transformer import (
        RoutedFFN,
        SparseAttention,
        TransformerLM,
        moe_load_counters,
        routed_lm_loss_fn,
        sparse_select_counters,
        untied_logits,
    )
    from adaptdl_tpu.ops import sparse_attention as sparse_ops
    from adaptdl_tpu.scaling_rules import AdamScale
    from adaptdl_tpu.trainer import ElasticTrainer

    cfg = model_config(sizes)
    model = TransformerLM(cfg)
    # Parameter shapes do not depend on the sequence: init on a short
    # row.
    dummy = jnp.zeros((1, min(128, sizes["sequence_length"])), jnp.int32)

    def fresh(key):
        """flax's initialisers, and the embedding table at UNIT
        variance (what an embedding gets by default elsewhere), not
        flax's 1 / d: under the smaller table the residual stream of a
        freshly initialised model is the attention's token-independent
        mean, every token prefers the same few experts
        (``held_rows_max_over_mean`` 11 .. 16 at the published widths)
        and the step time follows which experts the seed made popular
        (``tokens_per_s`` spread 1.7%; my chip runs, PR 34), where the
        deployment this share stands for sees ~1024 rows a held
        expert."""
        params = model.init(key, dummy, train=False)["params"]
        table = params["embed"]["embedding"]
        params["embed"]["embedding"] = table * table.shape[1] ** 0.5
        return params

    # (A lambda: benchmark/tests/compile_cell_v5e.py turns the jit of
    # one into shapes.)
    params = jax.jit(lambda key: fresh(key))(jax.random.key(seed))

    layers = range(sizes["num_hidden_layers"])
    captured_paths = {("RMSNorm_0",)} | {
        (f"layer_{i}", name)
        for i in layers
        for name in ("RMSNorm_0", "RMSNorm_1", "attention", "moe")
    }

    def head_io(params, batch, rng):
        """From ONE evaluation of the whole model, as it runs: the
        final hidden states and every token's loss; every layer's mean
        L_I and the selection's counters; of every routed layer its
        input, its output, the router's choice (``experts``,
        ``weights``) and the load counters; of every sparse mixer its
        input, what its kernels were handed (``SparseAttention.
        project``: the six operands), every token's L_I and its
        output."""
        logits, captured = model.apply(
            {"params": params}, batch["inputs"], train=True, rng=rng,
            capture_intermediates=lambda module, _method: module.path
            in captured_paths,
            mutable=[
                "moe_load", "moe_routing", "indexer_loss",
                "sparse_select", "intermediates",
            ],
        )
        (hidden,) = captured["intermediates"]["RMSNorm_0"]["__call__"]
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["targets"]
        )
        load = moe_load_counters(cfg, captured)
        load.update(sparse_select_counters(cfg, captured))

        def seen(layer, module, method="__call__"):
            return captured["intermediates"][f"layer_{layer}"][module][
                method
            ][0]

        # Per layer, not stacked: the comparisons let go of a layer's
        # captures when they are done with it.
        for name, module in (
            ("inputs", "RMSNorm_1"), ("outputs", "moe"),
            ("mixer_inputs", "RMSNorm_0"), ("mixer_outputs", "attention"),
        ):
            load[name] = [seen(i, module) for i in layers]
        load["operands"] = [
            tuple(seen(i, "attention", "project")) for i in layers
        ]
        for name in ("experts", "weights"):
            load[name] = [
                captured["moe_routing"][f"layer_{i}"]["moe"][name][0]
                for i in layers
            ]
        load["index_loss_rows"] = [
            captured["indexer_loss"][f"layer_{i}"]["attention"]["loss"][0]
            for i in layers
        ]
        return hidden, losses, load

    def head_losses(params, hidden, targets):
        """The system's head alone on ``hidden``: every token's loss."""
        return optax.softmax_cross_entropy_with_integer_labels(
            untied_logits(hidden, params["lm_head"]), targets
        )

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

    def positions(x):
        return jnp.arange(x.shape[1])

    def operands(attention_params, x):
        """What the system's mixer ALONE hands its kernels of a block
        input ``x`` [1, seq, d]: ``(q, k, v, index_q, index_k,
        index_w)`` (``SparseAttention.project``)."""
        return SparseAttention(cfg).apply(
            {"params": attention_params}, x, positions(x),
            method=SparseAttention.project,
        )

    def selection(operands):
        """The system's indexer alone on its own operands: the
        kernels' index scores float32 [seq (query), seq (key)] and the
        membership they apply, bool, same shape."""
        pairs, scores, _, _ = sparse_ops.selected_pairs(
            *operands[3:], cfg.index_topk
        )
        return scores[0], pairs[0].astype(bool)

    def kernels_vjp(operands, cotangent):
        """The system's kernels alone on ``operands``: the output
        [1, heads, seq, hd], every token's L_I, and the gradients of
        ``sum(out * cotangent)`` and of ``mean(L_I)`` apart, each with
        respect to all six operands."""
        (out, index_loss), pull = jax.vjp(
            lambda *ops: sparse_ops.sparse_attention(
                *ops, cfg.index_topk
            )[:2],
            *operands,
        )
        zero = jnp.zeros_like(index_loss)
        return {
            "out": out,
            # The forward kernel's float32 accumulator without its
            # last rounding (``out_dtype``).
            "out_f32": sparse_ops.sparse_attention(
                *operands, cfg.index_topk, out_dtype=jnp.float32
            )[0],
            "index_loss": index_loss,
            "attend_grads": pull((cotangent.astype(out.dtype), zero)),
            "index_grads": pull(
                (jnp.zeros_like(out), jnp.ones_like(zero) / zero.size)
            ),
        }

    def sparse_vjp(attention_params, x, cotangent):
        """The system's sparse mixer alone on ``x`` [1, seq, d]: its
        output, every token's L_I, and the gradients of ``sum(y *
        cotangent)`` and of ``mean(L_I)`` apart, each with respect to
        (the mixer's parameters, x)."""

        def mixer(attention_params, x):
            y, sown = SparseAttention(cfg).apply(
                {"params": attention_params}, x, positions(x),
                mutable=["indexer_loss", "sparse_select"],
            )
            return y.astype(jnp.float32), sown["indexer_loss"]["loss"][0]

        (y, index_loss), pull = jax.vjp(mixer, attention_params, x)
        zero = jnp.zeros_like(index_loss)
        return {
            "y": y,
            "index_loss": index_loss,  # [1, seq]
            "lm_grads": pull((cotangent, zero)),
            "index_grads": pull(
                (jnp.zeros_like(y), jnp.ones_like(zero) / zero.size)
            ),
        }

    recipe = sizes["recipe"]
    loss_fn = routed_lm_loss_fn(model)
    trainer = ElasticTrainer(
        loss_fn=loss_fn,
        params=params,
        optimizer=optax.adamw(recipe["learning_rate"]),
        init_batch_size=geometry["global_batch"],
        scaling_rule=AdamScale(),
        precondition="adam",
        seed=seed,
    )
    return {
        "trainer": trainer,
        "loss_fn": loss_fn,
        "head_io": head_io,
        "head_losses": head_losses,
        "routed_vjp": routed_vjp,
        "operands": operands,
        "selection": selection,
        "kernels_vjp": kernels_vjp,
        "sparse_vjp": sparse_vjp,
        "checkpoint_transforms": None,
    }


# ---- the plain reference --------------------------------------------


def reference_weights(params, sizes: dict) -> dict:
    """The system's parameter tree in the reference's own layout."""
    layers = []
    for i in range(sizes["num_hidden_layers"]):
        block = params[f"layer_{i}"]
        attn, moe = block["attention"], block["moe"]
        indexer = attn["indexer"]
        layers.append(
            {
                "norm_op": block["RMSNorm_0"]["scale"],
                "norm_ffn": block["RMSNorm_1"]["scale"],
                "wq": attn["q"]["kernel"],  # [d, heads, hd]
                "wk": attn["kv"]["kernel"][:, 0],  # [d, kv_heads, hd]
                "wv": attn["kv"]["kernel"][:, 1],
                "q_norm": attn["q_norm"]["scale"],
                "k_norm": attn["k_norm"]["scale"],
                "wo": attn["out"]["kernel"],  # [heads * hd, d]
                "wqi": indexer["index_q"]["kernel"],  # [d, ih, idim]
                "wki": indexer["index_k"]["kernel"],  # [d, idim]
                "ww": indexer["index_w"]["kernel"],  # [d, ih]
                "router": moe["router"],  # [d, router_width]
                "w1": moe["w_gate"],  # [held, d, f]
                "w3": moe["w_up"],
                "w2": moe["w_down"],  # [held, f, d]
            }
        )
    return {
        "embedding": params["embed"]["embedding"],
        "head": params["lm_head"],  # [vocab, d]
        "layers": layers,
        "norm_out": params["RMSNorm_0"]["scale"],
    }


# What the comparisons can tell apart is MEASURED: the reference
# functions take a ``variant`` that computes in LOWER PRECISION than
# the configuration states, on purpose (never used by
# ``reference_check``; benchmark/tests/keye_precision.py reads each
# against the right one).
ROUTER_FAULTS = ("bf16_scores",)
ROUTED_FAULTS = ("bf16_accumulate",)
INDEX_FAULTS = ("bf16_index_scores",)
ATTENTION_FAULTS = ("bf16_attention_sums",)


def _product(a, b, variant: str):
    """``a @ b``; under "bf16_accumulate" in chunks of 256 along the
    contraction whose results are rounded to bfloat16 and added in
    bfloat16."""
    import jax.numpy as jnp

    if variant != "bf16_accumulate":
        return a @ b
    total = None
    for start in range(0, a.shape[-1], 256):
        part = a[..., start:start + 256].astype(jnp.bfloat16) @ b[
            start:start + 256
        ].astype(jnp.bfloat16)
        total = part if total is None else total + part
    return total.astype(jnp.float32)


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
    """The published router alone on ``x`` [..., d]: float32 logits
    over all experts, softmax over all of them, the 8 largest, divided
    by their sum. Returns (experts [..., top_k] ascending, weights).
    With ``system``, the sets the system chose: a near-tied token's
    experts are the system's (``near_ties.settle``), and a third result,
    the ``Ties``."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    if variant == "bf16_scores":
        logits = (
            x.astype(jnp.bfloat16) @ layer["router"].astype(jnp.bfloat16)
        ).astype(jnp.float32)
    else:
        with jax.default_matmul_precision("highest"):
            logits = x @ layer["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    picked, chosen = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    if system is not None:
        chosen, ties = near_ties.settle(probs, chosen, system)
        picked = jnp.take_along_axis(probs, chosen, -1)
    weights = picked / (
        picked.sum(-1, keepdims=True) + sizes["expert_weight_eps"]
    )
    found = in_expert_order(chosen, weights)
    return found if system is None else (*found, ties)


def reference_routed_ffn(
    layer: dict, x, sizes: dict, first_expert: int | None = None,
    variant: str = "", system=None,
):
    """The published routed FFN, this share of it: the router over all
    experts, and the sum over the experts chosen AND held
    (``first_expert ..`` + the number of expert weights the layer has)
    of weight x gated FFN. Returns (y, rows each of ALL experts was
    chosen for), and with ``system`` the router's ``Ties``."""
    import jax
    import jax.numpy as jnp

    first = sizes["first_expert"] if first_expert is None else first_expert
    chosen, weights, *ties = reference_router(
        layer, x, sizes, variant if variant in ROUTER_FAULTS else "",
        system,
    )

    # One held expert a step of a scan whose body is rematerialised:
    # ``jax.grad`` holds one expert's intermediates at a time, and the
    # program is one expert long.
    @jax.checkpoint
    def expert(y, held):
        index, w1, w3, w2 = held
        weight = jnp.where(chosen == first + index, weights, 0.0).sum(
            -1, keepdims=True
        )
        up = jax.nn.silu(_product(x, w1, variant)) * _product(x, w3, variant)
        return y + weight * _product(up, w2, variant), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (
            jnp.arange(layer["w1"].shape[0]), layer["w1"], layer["w3"],
            layer["w2"],
        ),
    )
    counts = jnp.sum(
        chosen[..., None] == jnp.arange(layer["router"].shape[1]),
        axis=tuple(range(chosen.ndim)),
    )
    return (y, counts, *ties)


def operands_as_stated(layer: dict, dtype) -> dict:
    """``layer`` with the experts' weights rounded to the compute
    ``dtype``, which is what the configuration states the grouped
    products multiply (the router's stay float32): the routed layer's
    comparison is then of the arithmetic on equal operands, as the
    head's is (``reference_head``). ``reduce_precision``, not a cast
    there and back: the compiler may drop such a pair."""
    import jax
    import jax.numpy as jnp

    kind = jnp.finfo(dtype)
    return {
        **layer,
        **{
            k: jax.lax.reduce_precision(layer[k], kind.nexp, kind.nmant)
            for k in ("w1", "w3", "w2")
        },
    }


def reference_routed_vjp(
    layer: dict, x, cotangent, sizes: dict, variant: str = "",
    system=None,
):
    """Gradients of ``sum(y * cotangent)`` of the routed FFN with
    respect to ({w1, w3, w2, router}, x), by ``jax.grad``; with
    ``system`` (those gradients, the router's ``Ties``)."""
    import jax
    import jax.numpy as jnp

    def objective(weights, x):
        y, _, *ties = reference_routed_ffn(
            {**layer, **weights}, x, sizes, variant=variant, system=system
        )
        return jnp.sum(y * cotangent), ties

    weights = {k: layer[k] for k in ("w1", "w3", "w2", "router")}
    grads, ties = jax.grad(objective, argnums=(0, 1), has_aux=True)(
        weights, x
    )
    return grads if system is None else (grads, *ties)


def _rotary(x, theta: float):
    """Interleaved pairs; x [seq, heads, dim]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half) / half)
    angle = jnp.arange(x.shape[0])[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(angle)[:, None, :], jnp.cos(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1
    ).reshape(x.shape)


def reference_indexer(layer: dict, u, sizes: dict):
    """The indexer's projections of ``u`` [seq, d] (no gradient to
    ``u``): qI [seq, heads, dim] and kI [seq, dim] with rotary, w
    [seq, heads]."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.stop_gradient(u)
    theta = float(sizes["rope_theta"])
    qi = _rotary(jnp.einsum("sd,dhk->shk", u, layer["wqi"]), theta)
    ki = _rotary((u @ layer["wki"])[:, None, :], theta)[:, 0]
    return qi, ki, u @ layer["ww"]


def reference_scores(qi, ki, w, variant: str = ""):
    """``I[t, s] = sum_j w[t, j] / sqrt(heads) * relu(qI[t, j] . kI[s]
    / sqrt(dim))`` for a block of queries against all keys; a zero of
    either sign is one score."""
    import jax.numpy as jnp

    heads, dim = qi.shape[1], qi.shape[2]
    if variant == "bf16_index_scores":
        dots = jnp.einsum(
            "thk,sk->ths", qi.astype(jnp.bfloat16),
            ki.astype(jnp.bfloat16),
            preferred_element_type=jnp.bfloat16,
        )
        scores = jnp.einsum(
            "th,ths->ts", (w * heads**-0.5).astype(jnp.bfloat16),
            jnp.maximum(dots * jnp.bfloat16(dim**-0.5), 0),
            preferred_element_type=jnp.bfloat16,
        ).astype(jnp.float32)
    else:
        dots = jnp.einsum("thk,sk->ths", qi, ki) * dim**-0.5
        scores = jnp.einsum(
            "th,ths->ts", w * heads**-0.5, jnp.maximum(dots, 0.0)
        )
    return scores + 0.0


def reference_select(scores, first_query: int, topk: int):
    """Membership [queries, keys] of a block of queries starting at
    ``first_query``: the ``topk`` largest scores among the keys at or
    before the query (``lax.top_k``: ties to the lower key), all of
    them while there are at most ``topk``; and each query's
    ``topk``-th score (-inf while it keeps every key)."""
    import jax
    import jax.numpy as jnp

    queries, keys = scores.shape
    visible = (
        jnp.arange(keys)[None, :]
        <= (first_query + jnp.arange(queries))[:, None]
    )
    values, chosen = jax.lax.top_k(
        jnp.where(visible, scores, -jnp.inf), min(topk, keys)
    )
    member = jnp.zeros((queries, keys), bool).at[
        jnp.arange(queries)[:, None], chosen
    ].set(True)
    return member & visible, values[:, -1]


def reference_operands(layer: dict, u, sizes: dict) -> dict:
    """What attention and the indexer take of a block's input ``u``
    [seq, d]: q [seq, kv_heads, group, hd] (query head ``g * group +
    r`` on kv head ``g``), k and v [seq, kv_heads, hd] (RMSNorm over
    each head of q and k, then rotary), and the indexer's qi, ki, w
    (``reference_indexer``)."""
    import jax
    import jax.numpy as jnp

    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]

    def head_norm(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale

    q = _rotary(
        head_norm(jnp.einsum("sd,dhk->shk", u, layer["wq"]), layer["q_norm"]),
        theta,
    )
    k = _rotary(
        head_norm(jnp.einsum("sd,dhk->shk", u, layer["wk"]), layer["k_norm"]),
        theta,
    )
    qi, ki, w = reference_indexer(layer, u, sizes)
    return {
        "q": q.reshape(
            q.shape[0], kv_heads, heads // kv_heads, sizes["head_dim"]
        ),
        "k": k,
        "v": jnp.einsum("sd,dhk->shk", u, layer["wv"]),
        "qi": qi, "ki": ki, "w": w,
    }


def reference_attend(
    operands: dict, sizes: dict, member=None, variant: str = "",
):
    """Sparse attention and the indexer's loss on ``operands``
    (``reference_operands``), by query blocks of ``QUERY_BLOCK``
    against all keys (a scan whose body is rematerialised, so that
    ``jax.grad`` holds one block at a time). ``member`` [seq, seq]
    bool: the sets to attend over; None = select by the operands' own
    index scores. Returns (out [seq, heads * hd], L_I [seq])."""
    import jax
    import jax.numpy as jnp

    q, k, v = operands["q"], operands["k"], operands["v"]
    qi, ki, w = operands["qi"], operands["ki"], operands["w"]
    seq, _, _, head_dim = q.shape
    topk = sizes["sa_config"]["topk"]
    block = min(QUERY_BLOCK, seq)
    blocks = seq // block

    def attend(probs, v):
        """sum_s probs[g, r, t, s] v[s, g] -> [t, g, r, k]."""
        if variant != "bf16_attention_sums":
            return jnp.einsum("grts,sgk->tgrk", probs, v)
        total = None
        for start in range(0, probs.shape[-1], 256):
            part = jnp.einsum(
                "grts,sgk->tgrk",
                probs[..., start:start + 256].astype(jnp.bfloat16),
                v[start:start + 256].astype(jnp.bfloat16),
                preferred_element_type=jnp.bfloat16,
            )
            total = part if total is None else total + part
        return total.astype(jnp.float32)

    @jax.checkpoint
    def step(_, xs):
        first, q_b, qi_b, w_b, member_b = xs
        scores = reference_scores(qi_b, ki, w_b, variant)
        chosen = (
            reference_select(jax.lax.stop_gradient(scores), first, topk)[0]
            if member is None else member_b
        )
        logits = jnp.einsum("tgrk,sgk->grts", q_b, k) / jnp.sqrt(
            jnp.float32(head_dim)
        )
        probs = jax.nn.softmax(
            jnp.where(chosen[None, None], logits, -jnp.inf), axis=-1
        )
        out = attend(probs, v).reshape(block, -1)
        target = jax.lax.stop_gradient(probs.mean((0, 1)))
        log_index = jax.nn.log_softmax(
            jnp.where(chosen, scores, -jnp.inf), axis=-1
        )
        index_loss = jnp.sum(
            jnp.where(
                chosen,
                target * (
                    jnp.log(jnp.where(target > 0, target, 1.0))
                    - jnp.where(chosen, log_index, 0.0)
                ),
                0.0,
            ),
            axis=-1,
        )
        return None, (out, index_loss)

    by_block = _by_block(blocks)
    _, (out, index_loss) = jax.lax.scan(
        step, None,
        (
            jnp.arange(blocks) * block, by_block(q), by_block(qi),
            by_block(w),
            by_block(
                jnp.zeros((seq, 1), bool) if member is None else member
            ),
        ),
    )
    return out.reshape(seq, -1), index_loss.reshape(seq)


def reference_sparse_attention(
    layer: dict, u, sizes: dict, member=None, variant: str = "",
):
    """The sparse mixer on ``u`` [seq, d]: ``reference_attend`` of
    ``reference_operands``, then the output projection. Returns (y
    [seq, d], L_I [seq])."""
    out, index_loss = reference_attend(
        reference_operands(layer, u, sizes), sizes, member, variant
    )
    return out @ layer["wo"], index_loss


def _by_block(blocks: int):
    """x [seq, ...] -> [blocks, seq / blocks, ...]."""
    return lambda x: x.reshape((blocks, x.shape[0] // blocks) + x.shape[1:])


def reference_selection(operands: dict, sizes: dict, variant: str = ""):
    """The index scores and the selection alone on ``operands`` (their
    qi, ki, w): (index scores [seq (query), seq (key)], membership
    bool, each query's topk-th score), by query blocks."""
    import jax
    import jax.numpy as jnp

    qi, ki, w = operands["qi"], operands["ki"], operands["w"]
    seq = qi.shape[0]
    blocks = seq // min(QUERY_BLOCK, seq)

    def step(_, xs):
        first, qi_b, w_b = xs
        scores = reference_scores(qi_b, ki, w_b, variant)
        return None, (
            scores,
            *reference_select(scores, first, sizes["sa_config"]["topk"]),
        )

    by_block = _by_block(blocks)
    _, found = jax.lax.scan(
        step, None,
        (jnp.arange(blocks) * (seq // blocks), by_block(qi), by_block(w)),
    )
    return tuple(x.reshape((seq,) + x.shape[2:]) for x in found)


def _rms_norm(x, scale, eps: float):
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def reference_block(layer: dict, x, sizes: dict, variant: str = ""):
    """One block on ``x`` [seq, d]: ``x += Mixer(RMSNorm(x))``, ``x +=
    Experts(RMSNorm(x))``. Returns (x, the block's mean L_I, rows each
    of ALL experts was chosen for). Float32, "highest"."""
    import jax

    eps = sizes["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        y, index_loss = reference_sparse_attention(
            layer, _rms_norm(x, layer["norm_op"], eps), sizes,
            variant=variant,
        )
        x = x + y
        y, chosen = reference_routed_ffn(
            layer, _rms_norm(x, layer["norm_ffn"], eps), sizes,
            variant=variant,
        )
        return x + y, index_loss.mean(), chosen


def reference_embed(embedding, inputs):
    import jax.numpy as jnp

    return embedding[inputs].astype(jnp.float32)


def reference_lm_head(x, norm_out, head, targets, sizes: dict):
    """Final RMSNorm, the untied table, every token's loss."""
    import jax

    with jax.default_matmul_precision("highest"):
        logits = _rms_norm(x, norm_out, sizes["rms_norm_eps"]) @ head.T
        picked = jax.numpy.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), targets[..., None], axis=-1
        )
        return -picked[..., 0]


def reference_loss(
    weights: dict, inputs, targets, sizes: dict, per_token: bool = False,
    variant: str = "", compiled: bool = False,
):
    """Of ONE row ``inputs`` / ``targets`` [seq]: the next-token
    cross-entropy of the share (mean, or every token's with
    ``per_token``), L_I (mean over layers and tokens), and the routed
    layers' expert counts ``[layers, router_width]``. Float32,
    "highest" matmul precision, no kernel, no remat. ``compiled``: the
    embedding, ONE block and the head each as a small program of its
    own (the layers share theirs), for a caller that is not traced
    itself: a monolithic program of every layer is tens of MiB in a
    compile cache that holds 192."""
    import jax
    import jax.numpy as jnp

    run = jax.jit if compiled else (lambda f: f)
    block = run(lambda layer, x: reference_block(layer, x, sizes, variant))
    head = run(
        lambda x, norm, table, targets: reference_lm_head(
            x, norm, table, targets, sizes
        )
    )
    x = run(reference_embed)(weights["embedding"], inputs)
    counts, index_losses = [], []
    for layer in weights["layers"]:
        x, index_loss, chosen = block(layer, x)
        index_losses.append(index_loss)
        counts.append(chosen)
    loss = head(x, weights["norm_out"], weights["head"], targets)
    return (
        loss if per_token else loss.mean(),
        jnp.mean(jnp.stack(index_losses)),
        jnp.stack(counts),
    )


def reference_head(hidden, table, targets):
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
        logits = hidden.astype(jnp.float32) @ table.T
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), targets[..., None], axis=-1
        )
        return -picked[..., 0]


def router_disagreement(got, want):
    """(share of tokens whose expert sets differ, max |weight
    difference| over the tokens whose sets agree) of two (experts,
    weights) pairs in ascending expert order."""
    import jax.numpy as jnp

    same = jnp.all(got[0] == want[0], axis=-1)
    diff = jnp.where(same[..., None], jnp.abs(got[1] - want[1]), 0.0)
    return 1.0 - same.mean(), diff.max()


def layer_error(got, want):
    """How far ``got`` [..., d] is from ``want``, row by row. Returns
    (the worst row's |got - want| over the larger of that row's own
    |want| and the root mean square of |want| over the rows; the root
    mean square over the rows of |got - want|, over that of |want|)."""
    import jax.numpy as jnp

    want = want.reshape(-1, want.shape[-1])
    got = got.astype(jnp.float32).reshape(want.shape)
    err = jnp.sqrt(jnp.sum((got - want) ** 2, axis=-1))
    norm = jnp.sqrt(jnp.sum(want ** 2, axis=-1))
    scale = jnp.sqrt(jnp.mean(norm ** 2))
    scale = jnp.where(scale > 0, scale, 1.0)
    return (
        jnp.max(err / jnp.maximum(norm, scale)),
        jnp.sqrt(jnp.mean(err ** 2)) / scale,
    )


def slice_error(got, want):
    """Worst |got[e] - want[e]| / |want[e]| over the leading axis."""
    import jax.numpy as jnp

    axes = tuple(range(1, want.ndim))
    diff = jnp.sqrt(jnp.sum((got.astype(jnp.float32) - want) ** 2, axes))
    norm = jnp.sqrt(jnp.sum(want ** 2, axes))
    return jnp.max(jnp.where(norm > 0, diff / norm, diff))


def leaf_error(got, want):
    """|got - want| / |want| of one whole leaf."""
    return slice_error(got[None], want[None])


def routed_grad_errors(got, want) -> dict:
    """The system's (parameter gradients, input gradient) of a routed
    layer against the reference's: worst expert's slice of a weight
    leaf, the router leaf, the input."""
    import jax.numpy as jnp

    (got_w, got_x), (want_w, want_x) = got, want
    names = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
    return {
        "expert_grad_err": jnp.max(
            jnp.stack(
                [slice_error(got_w[a], want_w[b]) for a, b in names.items()]
            )
        ),
        "router_grad_err": leaf_error(got_w["router"], want_w["router"]),
        "input_grad_err": layer_error(got_x, want_x)[1],
    }


def selection_errors(operands: dict, sizes: dict, scores, member):
    """Comparison 5 of one layer: index scores and sets ``[seq
    (query), seq (key)]`` (the system's) against the reference's from
    the SAME ``operands`` (qi, ki, w: the system's own, as float32),
    by query blocks so that the reference's never exist whole. The scores as ``layer_error`` over query rows (keys
    after the query zeroed on both sides); the share of the
    reference's memberships the other sets lack (both keep the same
    number a query, so as many are extra); and the largest distance of
    a DISPUTED key's reference score from its query's topk-th
    reference score, in units of the reference's rms score."""
    import jax
    import jax.numpy as jnp

    qi, ki, w = operands["qi"], operands["ki"], operands["w"]
    seq = qi.shape[0]
    blocks = seq // min(QUERY_BLOCK, seq)

    def step(_, xs):
        first, qi_b, w_b, scores_b, member_b = xs
        want = reference_scores(qi_b, ki, w_b)
        own, kth = reference_select(want, first, sizes["sa_config"]["topk"])
        visible = (
            jnp.arange(seq)[None, :]
            <= (first + jnp.arange(want.shape[0]))[:, None]
        )
        want = jnp.where(visible, want, 0.0)
        err = jnp.sum(
            (jnp.where(visible, scores_b, 0.0) - want) ** 2, axis=-1
        )
        return None, {
            "err_max": err.max(),
            "err_sum": err.sum(),
            "want_sum": jnp.sum(want**2),
            "lacking": jnp.sum(own & ~member_b),
            "kept": jnp.sum(own),
            "gap": jnp.where(
                own != member_b, jnp.abs(want - kth[:, None]), 0.0
            ).max(),
            "size_err": jnp.abs(member_b.sum(-1) - own.sum(-1)).max(),
        }

    by_block = _by_block(blocks)
    _, found = jax.lax.scan(
        step, None,
        (
            jnp.arange(blocks) * (seq // blocks), by_block(qi), by_block(w),
            by_block(scores), by_block(member),
        ),
    )
    # layer_error's scale: the rms over query rows of the row's norm.
    row_scale = jnp.sqrt(found["want_sum"].sum() / seq)
    pairs = seq * (seq + 1) / 2
    return {
        "index_score_row_err": jnp.sqrt(found["err_max"].max()) / row_scale,
        "index_score_rms_err": jnp.sqrt(found["err_sum"].sum() / seq)
        / row_scale,
        "index_set_mismatch_share": found["lacking"].sum()
        / found["kept"].sum(),
        "index_dispute_gap": found["gap"].max()
        / jnp.sqrt(found["want_sum"].sum() / pairs),
        "index_sets_size_err": found["size_err"].max(),
    }


def as_reference_operands(operands, sizes: dict) -> dict:
    """The system's operands ``(q, k, v, index_q, index_k, index_w)``
    of ONE row, as float32 in the reference's layout
    (``reference_operands``): the same VALUES, so that what is
    compared next is the arithmetic on them and not their rounding.
    ``index_w`` comes with both scales folded in; the reference applies
    them itself."""
    import jax
    import jax.numpy as jnp

    def exactly(x):
        # ``reduce_precision``, not the cast alone: where the operands
        # were computed in this very program the compiler may drop the
        # rounding between their producer and this cast, and the
        # reference would then see MORE than the kernels were given
        # (my chip run, PR 34: 0.3% of the scores' rms).
        kind = jnp.finfo(x.dtype)
        return jax.lax.reduce_precision(
            x[0].astype(jnp.float32), kind.nexp, kind.nmant
        )

    q, k, v, qi, ki, w = (exactly(x) for x in operands)
    heads, seq, head_dim = q.shape
    kv_heads = k.shape[0]
    folded = qi.shape[0] ** -0.5 * qi.shape[2] ** -0.5
    by_token = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
    return {
        "q": by_token(q).reshape(seq, kv_heads, heads // kv_heads, head_dim),
        "k": by_token(k), "v": by_token(v), "qi": by_token(qi), "ki": ki,
        "w": w / folded,
    }


def operand_errors(got: dict, want: dict) -> dict:
    """The system's operands against the reference's projections of
    the same block input, each as ``layer_error`` over tokens: the
    worst of the six."""
    import jax.numpy as jnp

    seq = want["ki"].shape[0]
    errors = [
        layer_error(got[k].reshape(seq, -1), want[k].reshape(seq, -1))
        for k in want
    ]
    return {
        "projection_token_err": jnp.max(jnp.stack([e[0] for e in errors])),
        "projection_rms_err": jnp.max(jnp.stack([e[1] for e in errors])),
    }


def reference_kernels_vjp(operands: dict, member, cotangent, sizes: dict,
                          variant: str = ""):
    """``reference_attend`` on ``operands`` over the sets ``member``:
    output, L_I rows, the gradients of ``sum(out * cotangent)`` and of
    ``mean(L_I)`` to the operands, by ``jax.grad``."""
    import jax
    import jax.numpy as jnp

    def run(operands):
        return reference_attend(operands, sizes, member, variant)

    out, index_loss = run(operands)
    return {
        "out": out,
        "index_loss": index_loss,
        "attend_grads": jax.grad(
            lambda o: jnp.sum(run(o)[0] * cotangent)
        )(operands),
        "index_grads": jax.grad(lambda o: run(o)[1].mean())(operands),
    }


def kernel_errors(got: dict, want: dict) -> dict:
    """Comparison 6 at the kernels: two ``*_kernels_vjp`` results in
    the reference's layout. The output token by token; dq, dk, dv as
    ``layer_error``'s rms over tokens, the worst; L_I token by token;
    the gradients of L_I to qi, ki, w, each |got - want| / |want|, the
    worst; and, exactly, that neither gradient crosses."""
    import jax.numpy as jnp

    seq = want["index_loss"].shape[0]
    flat = lambda x: x.reshape(seq, -1)  # noqa: E731
    token, rms = layer_error(got["out"], want["out"])
    crossed = sum(
        jnp.sum(got["attend_grads"][k] != 0) for k in ("qi", "ki", "w")
    ) + sum(jnp.sum(got["index_grads"][k] != 0) for k in ("q", "k", "v"))
    return {
        "kernel_token_err": token,
        "kernel_rms_err": rms,
        "kernel_qkv_grad_err": jnp.max(
            jnp.stack(
                [
                    layer_error(
                        flat(got["attend_grads"][k]),
                        flat(want["attend_grads"][k]),
                    )[1]
                    for k in ("q", "k", "v")
                ]
            )
        ),
        "kernel_index_loss_err": jnp.abs(
            got["index_loss"] - want["index_loss"]
        ).max(),
        "kernel_index_grad_err": jnp.max(
            jnp.stack(
                [
                    leaf_error(got["index_grads"][k], want["index_grads"][k])
                    for k in ("qi", "ki", "w")
                ]
            )
        ),
        "kernel_gradients_crossed": crossed,
    }


def system_kernels_as_reference(found: dict, sizes: dict) -> dict:
    """The builder's ``kernels_vjp`` result in the reference's layout
    (gradients to the folded ``index_w`` turned into gradients to the
    unfolded one)."""
    import jax.numpy as jnp

    def grads(operand_grads):
        tree = as_reference_operands(operand_grads, sizes)
        qi = operand_grads[3]
        folded = qi.shape[1] ** -0.5 * qi.shape[3] ** -0.5
        tree["w"] = tree["w"] * folded * folded
        return tree

    out = found["out"][0].astype(jnp.float32)
    return {
        "out": jnp.swapaxes(out, 0, 1).reshape(out.shape[1], -1),
        "index_loss": found["index_loss"][0],
        "attend_grads": grads(found["attend_grads"]),
        "index_grads": grads(found["index_grads"]),
    }


SPARSE_LEAVES = {  # the mixer's parameter leaves -> the reference's
    "q": ("wq",), "kv": ("wk", "wv"), "out": ("wo",),
}
INDEX_LEAVES = {"index_q": "wqi", "index_k": "wki", "index_w": "ww"}


def sparse_errors(system: dict, want: dict) -> dict:
    """Comparisons 6 and 7 of one layer: ``system`` is the builder's
    ``sparse_vjp``; ``want`` the reference's output, L_I rows and
    gradients over the same sets."""
    import jax
    import jax.numpy as jnp

    token, rms = layer_error(system["y"][0], want["y"])
    lm_params, lm_x = system["lm_grads"]
    index_params, index_x = system["index_grads"]
    want_lm, want_lm_x = want["lm_grads"]
    kv = lm_params["kv"]["kernel"]
    got = {
        "wq": lm_params["q"]["kernel"], "wk": kv[:, 0], "wv": kv[:, 1],
        "wo": lm_params["out"]["kernel"],
    }
    index_leaves = index_params["indexer"]
    outside = {k: v for k, v in index_params.items() if k != "indexer"}
    leaked = sum(
        jnp.sum(x != 0) for x in jax.tree.leaves(lm_params["indexer"])
    ) + sum(jnp.sum(x != 0) for x in jax.tree.leaves(outside)) + jnp.sum(
        index_x != 0
    )
    return {
        "sparse_token_err": token,
        "sparse_rms_err": rms,
        "sparse_param_grad_err": jnp.max(
            jnp.stack([leaf_error(got[k], want_lm[k]) for k in got])
        ),
        "sparse_input_grad_err": layer_error(lm_x[0], want_lm_x)[1],
        "index_loss_token_err": jnp.abs(
            system["index_loss"][0] - want["index_loss"]
        ).max(),
        "index_grad_err": jnp.max(
            jnp.stack(
                [
                    leaf_error(
                        index_leaves[a]["kernel"], want["index_grads"][b]
                    )
                    for a, b in INDEX_LEAVES.items()
                ]
            )
        ),
        "gradients_leaked": leaked,
    }


def reference_sparse_vjp(layer: dict, u, member, cotangent, sizes: dict,
                         variant: str = ""):
    """The reference's mixer on ``u`` [seq, d] over the sets
    ``member``: output, L_I rows, the gradients of ``sum(y *
    cotangent)`` to ({wq, wk, wv, wo}, u) and of ``mean(L_I)`` to
    {wqi, wki, ww}, by ``jax.grad``."""
    import jax
    import jax.numpy as jnp

    def run(weights, u):
        return reference_sparse_attention(
            {**layer, **weights}, u, sizes, member=member, variant=variant
        )

    def lm(weights, u):
        return jnp.sum(run(weights, u)[0] * cotangent)

    def index(weights):
        return run(weights, u)[1].mean()

    y, index_loss = run({}, u)
    return {
        "y": y,
        "index_loss": index_loss,
        "lm_grads": jax.grad(lm, argnums=(0, 1))(
            {k: layer[k] for k in ("wq", "wk", "wv", "wo")}, u
        ),
        "index_grads": jax.grad(index)(
            {k: layer[k] for k in ("wqi", "wki", "ww")}
        ),
    }


# The compiler's default (``xla_allow_excess_precision``) keeps a
# float32 value where the program rounds to bfloat16 between two
# operations it fuses. The whole model's program then hands its router
# the UNROUNDED output of the norm before it while a capture of that
# output shows the rounded one, and the residual stream reaches the
# final norm unrounded: on the chip the router's sets differ from the
# reference's on the captured input for 4.0-4.1% of the tokens and the
# head inside the model from the head alone by 1.9e-2-2.3e-2 nats; the
# same program compiled with the option off reads 0 and 0.0 (my chip
# run, PR 34: ``keye_precision.py --excess``; the optimized HLO shows
# the ``reduce-precision`` that the default drops). More precision than
# stated is no fault, but a comparison layer by layer needs what a
# layer CONSUMED to be what the capture shows: every system-side
# program of comparisons 2 to 7 is compiled as stated. Comparison 1
# takes the trainer's own ``loss_fn`` under the default, as the step
# does.
AS_STATED = {"xla_allow_excess_precision": False}


def as_stated(fn):
    """``jax.jit(fn)`` compiled once, for its first arguments, with
    ``AS_STATED``."""
    import jax

    programs = []

    def run(*args):
        if not programs:
            programs.append(
                jax.jit(fn).lower(*args).compile(compiler_options=AS_STATED)
            )
        return programs[0](*args)

    return run


CAPTURED = (  # of every layer, from the model as it ran (``head_io``)
    "inputs", "outputs", "experts", "weights", "mixer_inputs", "operands",
    "index_loss_rows", "mixer_outputs",
)


def layer_checks(built: dict, params, load: dict, sizes: dict) -> dict:
    """Comparisons 3 to 7, layer by layer. What the model computed AS
    IT RAN (``load``: ``head_io``'s captures of one evaluation of the
    whole model) against the reference on the same captured inputs:
    every router's choice and every routed layer's output; what every
    sparse mixer handed its kernels, every token's L_I and the mixer's
    output. The system's kernels alone ON THOSE CAPTURED OPERANDS
    (scores, sets, output, gradients), and the backward of the routed
    layer and of the mixer, each alone on the captured input, against
    ``jax.grad`` of the reference. One small program a kind (the
    layers share it), so that no two layers' float32 intermediates are
    alive together."""
    import jax
    import jax.numpy as jnp

    weights = reference_weights(params, sizes)["layers"]

    @jax.jit
    def routed(layer, moe_params, x, y, experts, weights):
        x = x.reshape(-1, x.shape[-1])
        x32 = x.astype(jnp.float32)
        got, own = built["routed_vjp"](moe_params, x, x32, sets=True)
        layer = operands_as_stated(layer, x.dtype)
        with jax.default_matmul_precision("highest"):
            # (Each on the sets its system side chose where a token is
            # near-tied: ``near_ties``.)
            want, _, forward = reference_routed_ffn(
                layer, x32, sizes, system=experts
            )
            grads, backward = reference_routed_vjp(
                layer, x32, x32, sizes, system=own
            )
        token, rms = layer_error(y, want)
        set_mismatch, weight_err = router_disagreement(
            in_expert_order(experts, weights),
            reference_router(layer, x32, sizes),
        )
        return {
            "router_set_mismatch_share": set_mismatch,
            "router_weight_err": weight_err,
            "routed_token_err": token, "routed_rms_err": rms,
            **routed_grad_errors(got, grads),
            **near_ties.worst(forward, backward),
        }

    @jax.jit
    def kernels(layer, x, operands):
        """Comparisons 5 and 6 at the kernels, on the operands the
        model's own kernels were handed."""
        scores, member = built["selection"](operands)
        same = as_reference_operands(operands, sizes)
        cotangent = same["q"].reshape(same["q"].shape[0], -1)
        found = built["kernels_vjp"](operands, operands[0])
        out_f32 = found.pop("out_f32")[0]
        found = system_kernels_as_reference(found, sizes)
        with jax.default_matmul_precision("highest"):
            errors = operand_errors(
                same, reference_operands(layer, x[0].astype(jnp.float32), sizes)
            )
            errors.update(selection_errors(same, sizes, scores, member))
            want = reference_kernels_vjp(same, member, cotangent, sizes)
            errors.update(kernel_errors(found, want))
        token, rms = layer_error(
            jnp.swapaxes(out_f32, 0, 1).reshape(want["out"].shape),
            want["out"],
        )
        errors.update(kernel_f32_token_err=token, kernel_f32_rms_err=rms)
        return member, errors

    @jax.jit
    def sparse(layer, attention_params, x, member, y, index_loss):
        """Comparison 6 of the whole mixer. Forward: what the model's
        own mixer gave (``y``, ``index_loss``) against the reference
        over the sets its kernels applied (``member``). Backward, and
        its own forward: the mixer alone, against the reference over
        the sets the mixer alone selects (a model has no other way to
        hand over one layer's gradients; alone it is compiled under
        the step's defaults, so its operands and with them its sets
        need not be the captured model's to the bit)."""
        x32 = x.astype(jnp.float32)
        _, alone = built["selection"](built["operands"](attention_params, x))
        system = built["sparse_vjp"](attention_params, x, x32)
        with jax.default_matmul_precision("highest"):
            want = reference_sparse_vjp(layer, x32[0], alone, x32[0], sizes)
            want_y, want_loss = reference_sparse_attention(
                layer, x32[0], sizes, member=member
            )
        token, rms = layer_error(y[0], want_y)
        return {
            **sparse_errors(system, want),
            "mixer_token_err": token, "mixer_rms_err": rms,
            "mixer_index_loss_err": jnp.abs(index_loss[0] - want_loss).max(),
        }

    found = []
    for i in range(sizes["num_hidden_layers"]):
        x = load["mixer_inputs"][i]
        member, errors = kernels(weights[i], x, load["operands"][i])
        errors.update(
            sparse(
                weights[i], params[f"layer_{i}"]["attention"], x, member,
                load["mixer_outputs"][i], load["index_loss_rows"][i],
            )
        )
        del member
        errors.update(
            routed(
                weights[i], params[f"layer_{i}"]["moe"], load["inputs"][i],
                load["outputs"][i], load["experts"][i], load["weights"][i],
            )
        )
        found.append({k: float(v) for k, v in errors.items()})
        for name in CAPTURED:
            load[name][i] = None
    return near_ties.worst_layer(found)


# Every reading that has a limit, beside it: (reading, limit name).
LIMITS = {
    "lm_loss_rel": "LM_LOSS_RTOL",
    "index_loss_rel": "INDEX_LOSS_RTOL",
    "stated_lm_loss_rel": "LM_LOSS_RTOL",
    "stated_index_loss_rel": "INDEX_LOSS_RTOL",
    "head_token_loss_err": "HEAD_TOKEN_LOSS_ATOL",
    "head_in_model_gap": "HEAD_IN_MODEL_GAP",
    "router_set_mismatch_share": "ROUTER_SET_MISMATCH_SHARE",
    "router_weight_err": "ROUTER_WEIGHT_ATOL",
    "routed_token_err": ("ROUTED_LIMITS", 0),
    "routed_rms_err": ("ROUTED_LIMITS", 1),
    "expert_grad_err": "EXPERT_GRAD_RTOL",
    "router_grad_err": "ROUTER_GRAD_RTOL",
    "input_grad_err": "INPUT_GRAD_RMS",
    "projection_token_err": ("PROJECTION_LIMITS", 0),
    "projection_rms_err": ("PROJECTION_LIMITS", 1),
    "index_score_row_err": ("INDEX_SCORE_LIMITS", 0),
    "index_score_rms_err": ("INDEX_SCORE_LIMITS", 1),
    "index_set_mismatch_share": "INDEX_SET_MISMATCH_SHARE",
    "index_dispute_gap": "INDEX_DISPUTE_GAP",
    "kernel_token_err": ("KERNEL_LIMITS", 0),
    "kernel_rms_err": ("KERNEL_LIMITS", 1),
    "kernel_f32_token_err": ("KERNEL_F32_LIMITS", 0),
    "kernel_f32_rms_err": ("KERNEL_F32_LIMITS", 1),
    "kernel_qkv_grad_err": "KERNEL_QKV_GRAD_RMS",
    "kernel_index_loss_err": "KERNEL_INDEX_LOSS_ATOL",
    "kernel_index_grad_err": "KERNEL_INDEX_GRAD_RTOL",
    "sparse_token_err": ("SPARSE_LIMITS", 0),
    "sparse_rms_err": ("SPARSE_LIMITS", 1),
    "mixer_token_err": ("MIXER_LIMITS", 0),
    "mixer_rms_err": ("MIXER_LIMITS", 1),
    "mixer_index_loss_err": "INDEX_LOSS_TOKEN_ATOL",
    "sparse_param_grad_err": "SPARSE_PARAM_GRAD_RTOL",
    "sparse_input_grad_err": "SPARSE_INPUT_GRAD_RMS",
    "index_loss_token_err": "INDEX_LOSS_TOKEN_ATOL",
    "index_grad_err": "INDEX_GRAD_RTOL",
}
EXACT_ZEROS = (
    "rows_dropped", "rows_unaccounted", "index_sets_size_err",
    "keys_selected_err", "gradients_leaked", "kernel_gradients_crossed",
)


def limit_of(name: str) -> float:
    at = LIMITS[name]
    return globals()[at] if isinstance(at, str) else globals()[at[0]][at[1]]


def verdict(result: dict) -> dict:
    """Which readings pass: ``{reading: [value, limit, ok]}``."""
    judged = {
        name: [result[name], limit_of(name), result[name] <= limit_of(name)]
        for name in LIMITS
    }
    judged.update(
        {name: [result[name], 0, result[name] == 0] for name in EXACT_ZEROS}
    )
    return judged


def reference_check(built: dict, params, dataset: dict, sizes: dict) -> dict:
    """The system against the plain reference on the run's own weights
    and ONE row of the seeded data, both computed on this device: the
    two mean losses of the whole model as the trainer's ``loss_fn``
    gives them; from one evaluation of the model compiled as stated
    (``head_io``, ``as_stated``) the head and, layer by layer, every
    router, routed layer, indexer and sparse mixer as they ran inside
    it; and every routed layer's, kernel's and mixer's backward alone
    on the captured inputs (``layer_checks``)."""
    import sys

    import jax
    import jax.numpy as jnp

    sample = {k: v[:REFERENCE_SEQUENCES] for k, v in dataset.items()}
    # The whole model as the step runs it: the trainer's own loss.
    total, counted = jax.jit(built["loss_fn"])(
        params, sample, jax.random.key(0)
    )
    system_index_loss = counted["indexer.loss"]["loss"].mean()
    hidden, token_losses, load = as_stated(built["head_io"])(
        params, sample, jax.random.key(0)
    )

    # Everything is an argument: data closed over would be constants of
    # the program and make its compile-cache key follow the seed.
    loss, index_loss, _ = reference_loss(
        reference_weights(params, sizes), sample["inputs"][0],
        sample["targets"][0], sizes, compiled=True,
    )

    def compare(head, hidden, targets, token_losses, load):
        head_losses = reference_head(hidden[0], head, targets[0])
        system_head_losses = built["head_losses"](
            {"lm_head": head}, hidden, targets
        )
        assignments = targets.size * sizes["num_experts_per_tok"]
        select = load["sparse.select"]
        return {
            # The model compiled as stated, beside the trainer's.
            "stated_loss": token_losses.mean(),
            "stated_index_loss": load["indexer.loss"]["loss"].mean(),
            "head_token_loss_err": jnp.max(
                jnp.abs(system_head_losses[0] - head_losses)
            ),
            # The head INSIDE the whole model against the head alone on
            # the captured hidden states.
            "head_in_model_gap": jnp.max(
                jnp.abs(token_losses - system_head_losses)
            ),
            "rows_dropped": jnp.sum(load["dropped"]),
            "rows_unaccounted": jnp.sum(
                jnp.abs(
                    load["held_rows"].sum(-1) + load["left_out"]
                    - assignments
                )
            ),
            "held_rows_max_over_mean": jnp.max(
                load["held_rows"].max(-1)
                / jnp.maximum(load["held_rows"].mean(-1), 1.0)
            ),
            "keys_selected_err": jnp.max(
                jnp.abs(
                    select["keys_selected"]
                    - targets.shape[0] * selected_pairs_per_row(
                        sizes["sequence_length"], sizes["sa_config"]["topk"]
                    )
                )
            ),
            "tied_queries": jnp.sum(select["tied_queries"]),
        }

    counters = {
        k: load[k] for k in (
            "dropped", "held_rows", "left_out", "sparse.select",
            "indexer.loss",
        )
    }
    result = {
        k: float(v)
        for k, v in as_stated(compare)(
            params["lm_head"], hidden, sample["targets"], token_losses,
            counters,
        ).items()
    }
    result.update(
        system_loss=float(total - system_index_loss),
        system_index_loss=float(system_index_loss),
        reference_loss=float(loss), reference_index_loss=float(index_loss),
    )
    result.update(layer_checks(built, params, load, sizes))
    for name, key in (("lm", "loss"), ("index", "index_loss")):
        want = result[f"reference_{key}"]
        for side, reading in (("system", ""), ("stated", "stated_")):
            result[f"{reading}{name}_loss_rel"] = (
                abs(result[f"{side}_{key}"] - want) / abs(want)
            )
    judged = verdict(result)
    result["limits"] = {name: row[1] for name, row in judged.items()}
    result["near_tie_margin"] = near_ties.NEAR_TIE_MARGIN
    result["ok"] = bool(
        np.isfinite(result["system_loss"])
        and np.isfinite(result["system_index_loss"])
        and all(row[2] for row in judged.values())
        and near_ties.within(
            result, ROUTER_SET_MISMATCH_SHARE, sample["inputs"].size
        )
    )
    if not result["ok"]:
        for name, (value, limit, ok) in judged.items():
            print(
                f"[keye reference] {'ok    ' if ok else 'FAILED'} "
                f"{name} = {value!r} (limit {limit!r})",
                file=sys.stderr, flush=True,
            )
    return result
