"""lfm2-8b-a1b: builder of the system under test, and its plain reference.

One chip's share of LFM2-8B-A1B under expert parallelism over four
chips (``lfm2-8b-a1b.json``: published widths, 5 of 24 layers, 8 of
each layer's 32 experts held, a quarter of the vocabulary). The system
side goes through the program's own entry points (``TransformerConfig``
/ ``TransformerLM`` with the block options the configuration forced,
the Pallas flash kernels, the grouped products of
``adaptdl_tpu/ops/grouped_matmul.py``, ``ElasticTrainer``). The
reference side is written from the published equations with the
departures the JSON lists, in plain float32 ``jax.numpy`` at "highest"
matmul precision, and imports nothing from ``adaptdl_tpu``: experts as
a Python loop over the held ones with a boolean mask, attention by
query blocks, no kernel, no remat, the same share.
"""

from __future__ import annotations

import numpy as np

from benchmark import near_ties

# Six comparisons decide ``correct`` (reference_check), on the run's
# own weights at the published widths on 2 rows of the timed length.
# Readings (my chip runs, PR 30, TPU v5 lite; benchmark/tests/
# lfm2_precision.py prints both readings of each; PERF.md section 6):
# "first" is the largest the system gave over the seeds, "second" what
# a reference computed WRONG on purpose gave against the reference.
#
# 1. Whole model: |system mean loss - reference mean loss| / reference.
#    bfloat16 blocks against float32 "highest"; over 2 x 8192 tokens
#    the rounding noise is unbiased and small: first 2.6e-6 .. 7.6e-5.
#    A mean hardly sees anything (a bfloat16 head moves it 3e-6, a
#    softmax router at these fresh weights 1.5e-5 .. 1.4e-4), so this
#    holds only what moves every token; the mechanisms are held one by
#    one, by 5 and 6. About three times the first reading alone.
REFERENCE_RTOL = 2.5e-4
# 2. The head alone, token by token, on the hidden states the SYSTEM
#    hands to it: float32 accumulation, logits, softmax and loss.
#    First 2.2e-4 .. 2.9e-4 nats; logits rounded to bfloat16 7.8e-3 ..
#    1.2e-2.
HEAD_TOKEN_LOSS_ATOL = 1e-3  # max |token loss - reference|, nats
# 3. The router alone, token by token, on the inputs the SYSTEM hands
#    to each routed layer (as the head): the reference router —
#    float32 sigmoid scores at "highest", top 4 of score + bias,
#    weights = chosen scores over their sum — on the same bfloat16
#    inputs. Against it the system's choice may differ by accumulation
#    order only: first, not one of 4 x 16 384 tokens chose another SET
#    of four, weights within 1.0e-7. Scores from bfloat16 operands and
#    rounded to bfloat16: 6.3e-3 .. 7.6e-3 of the tokens choose another
#    set, weights off by 3.9e-4 .. 4.1e-4. A softmax router keeps the
#    set (it is monotone too) and moves the weights by 0.58 .. 0.60.
ROUTER_SET_MISMATCH_SHARE = 1e-3  # tokens whose four experts differ
ROUTER_WEIGHT_ATOL = 2e-5  # max |weight - reference|, sets agreeing
# 4. Per routed layer, the rows each held expert received against the
#    WHOLE reference's count for the same expert, as a share of the
#    layer's held rows: sum_e |system_e - reference_e| / sum_e
#    reference_e, worst layer. The whole reference routes ITS float32
#    hidden states, the system its bfloat16 ones: a token whose fourth
#    and fifth scores are closer than that noise flips, so this is not
#    0 (first 3.2e-3 .. 5.9e-3), and it is blunt — flips cancel in a
#    count, bfloat16 scores read 1.6e-3 .. 2.3e-3 and a softmax router
#    6.5e-3 .. 1.0e-2 against the reference itself — so it holds the
#    bookkeeping, at about three times its first reading. Exactly: no
#    row dropped, and held + left-out rows = tokens x 4 in every layer.
ROUTING_L1_SHARE = 0.02
# 5. Every routed layer, one conv mixer and the attention mixer, each
#    ALONE, token by token, on the inputs the SYSTEM hands it (the
#    output of the RMSNorm before it) against the reference's function
#    of the same inputs: ``layer_error`` = |system - reference| of a
#    token's output vector over the layer's root-mean-square output
#    norm; the worst token, and the root mean square over the tokens.
#    This is what holds the grouped products over the held experts,
#    the dispatch and the combine, the short convolution, and GQA +
#    q/k norms + rotary through the flash kernel at 8192 keys.
#    First, 7 seeds (worst token / rms over tokens): routed 1.13e-2
#    .. 1.22e-2 / 5.141e-3 .. 5.148e-3; conv 8.6e-3 .. 1.17e-2 /
#    5.525e-3 .. 5.530e-3; attention 6.0e-2 .. 6.9e-2 / 5.33e-3 ..
#    5.42e-3 (an rms averages 16 384 tokens and hardly moves between
#    seeds; the worst token is a maximum and does).
#    Second, planted in the reference, read against the reference:
#    512 rows of one expert through another's weights 1.8 / 0.25; the
#    silu gate dropped 3.4 / 1.6; the router's weights dropped 6.3 /
#    3.0; partial sums of 256 rounded to and added in bfloat16
#    1.5e-2 / 6.9e-3 (the nearest precision below: the rms limit is
#    what refuses it); conv taps reversed 1.9 / 1.1, its C gate
#    dropped 2.0 / 1.4; query head i on kv head i % 8 23.6 / 1.3,
#    rotary base 1e4 3.4 / 0.75, no q/k norm 1.1 / 0.17.
# 6. Every routed layer ALONE, backward: the gradients of ``sum(y *
#    cotangent)`` (cotangent = the layer's input) with respect to each
#    held expert's three weights, the router and the input, from the
#    system's own layer (the weight-gradient kernel ``moe_tgmm``, the
#    input-gradient product ``dy W^T``, the routing's transposes)
#    against ``jax.grad`` of the reference, on the first row:
#    |system - reference| / |reference| of each expert's slice of each
#    leaf, the worst; the router leaf; the input as 5's rms. First,
#    7 seeds: 4.2e-3 .. 4.6e-3, 4.3e-3 .. 4.7e-3, 5.588e-3 .. 5.593e-3.
#    Second: the swapped rows 0.63 .. 0.70, 0.29 .. 0.37, 0.35; the
#    gate dropped 1.9, 1.6, 1.3; the weights dropped 3.0, 1.0, 3.0;
#    bfloat16 partial sums 5.6e-3 .. 6.1e-3, 6.3e-3 .. 7.0e-3,
#    5.97e-3 .. 5.98e-3. The input's rms is as steady as 5's, so its
#    limit lies between its readings and refuses the low precision;
#    the other two are maxima over slices, too near their first
#    readings for a limit between: they sit at about twice the first
#    reading, for wrong mathematics.
LAYER_LIMITS = {
    # kind: (worst token, rms over tokens)
    "routed": (0.03, 0.006),
    "conv": (0.03, 0.008),
    "attention": (0.2, 0.008),
}
EXPERT_GRAD_RTOL = 0.01  # worst expert's slice of a weight leaf
ROUTER_GRAD_RTOL = 0.012  # the router leaf
INPUT_GRAD_RMS = 0.0058  # the input's gradient, as layer_error's rms
REFERENCE_SEQUENCES = 2
ATTENTION_QUERY_BLOCK = 512


def units_per_sample(sizes: dict) -> int:
    return int(sizes["sequence_length"])


def forward_flops_per_token(sizes: dict) -> dict[str, float]:
    """Forward matmul FLOPs per token, by part: 2 FLOPs per
    multiply-accumulate, the causal half of attention at the timed
    length, routed experts at UNIFORM routing (``num_experts_per_tok x
    experts_held / num_experts`` experts a token a layer), no
    recomputation — counted as ``benchmark/flops.py`` counts."""
    d = sizes["hidden_size"]
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head_dim = d // heads
    kinds = sizes["layer_types"]
    attn = sum(k == "full_attention" for k in kinds)
    conv = sum(k == "conv" for k in kinds)
    dense = sizes["num_dense_layers"]
    routed = sizes["num_hidden_layers"] - dense
    per_token_experts = (
        sizes["num_experts_per_tok"] * sizes["experts_held"]
        / sizes["num_experts"]
    )
    return {
        "attention_projections": float(
            attn * 2 * (2 * d * d + 2 * d * kv_heads * head_dim)
        ),
        "attention_scores": float(
            attn * 2 * 2 * sizes["sequence_length"] * d * 0.5
        ),
        "conv_projections": float(conv * 2 * (3 * d * d + d * d)),
        "dense_ffn": float(dense * 2 * 3 * d * sizes["intermediate_size"]),
        "router": float(routed * 2 * d * sizes["num_experts"]),
        "routed_experts": float(
            routed * per_token_experts * 2 * 3 * d
            * sizes["moe_intermediate_size"]
        ),
        "head": float(2 * d * sizes["vocab_size"]),
    }


def train_flops_per_unit(sizes: dict) -> float:
    """Forward + backward (3x forward) model FLOPs per trained token."""
    return 3.0 * sum(forward_flops_per_token(sizes).values())


def make_dataset(sizes: dict, seed: int, samples: int) -> dict:
    """Packed token rows from the seed, as gpt2-124m's: documents of
    lognormal length (median ~400 tokens), each an arithmetic
    progression modulo the vocabulary SLICE with its own start and
    stride, packed back to back into rows of ``sequence_length + 1``
    tokens, no padding."""
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

    return TransformerConfig(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        d_model=sizes["hidden_size"],
        d_ff=sizes["intermediate_size"],
        max_seq_len=sizes["sequence_length"],
        dtype=jnp.dtype(sizes.get("compute_dtype", "bfloat16")).type,
        remat=True,
        attention_fn=attention_fn,
        norm="rmsnorm",
        norm_eps=sizes["norm_eps"],
        ffn="swiglu",
        qk_norm=True,
        rope_theta=float(sizes["rope_theta"]),
        layer_types=tuple(sizes["layer_types"]),
        conv_kernel=sizes["conv_L_cache"],
        experts_total=sizes["num_experts"],
        experts_held=sizes["experts_held"],
        first_expert=sizes["first_expert"],
        experts_top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["moe_intermediate_size"],
        num_dense_layers=sizes["num_dense_layers"],
        expert_weight_eps=sizes["expert_weight_eps"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
    )


def build(sizes: dict, geometry: dict, seed: int) -> dict:
    """The system under test for one cell: model, weights made on the
    device in one jitted call from the seed, loss, trainer."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    from adaptdl_tpu.models.transformer import (
        RoutedFFN,
        TransformerLM,
        moe_load_counters,
        routed_lm_loss_fn,
    )
    from adaptdl_tpu.ops.flash_attention import flash_attention
    from adaptdl_tpu.scaling_rules import AdamScale
    from adaptdl_tpu.trainer import ElasticTrainer

    block = min(128, sizes["sequence_length"])
    model = TransformerLM(
        model_config(
            sizes,
            functools.partial(flash_attention, block_q=block, block_k=block),
        )
    )
    # Parameter shapes depend on neither the attention function nor
    # the sequence: init through plain attention on a short row.
    init_model = TransformerLM(model_config(sizes))
    dummy = jnp.zeros((1, min(128, sizes["sequence_length"])), jnp.int32)
    params = jax.jit(
        lambda key: init_model.init(key, dummy, train=False)["params"]
    )(jax.random.key(seed))

    routed = range(sizes["num_dense_layers"], sizes["num_hidden_layers"])
    # One mixer of each kind, with the RMSNorm before it.
    mixers = {
        "conv": (sizes["layer_types"].index("conv"), "short_conv"),
        "attention": (
            sizes["layer_types"].index("full_attention"), "attention"
        ),
    }
    captured_paths = (
        {("RMSNorm_0",)}
        | {
            (f"layer_{i}", name)
            for i in routed
            for name in ("RMSNorm_1", "moe")
        }
        | {
            (f"layer_{i}", name)
            for i, mixer in mixers.values()
            for name in ("RMSNorm_0", mixer)
        }
    )

    def head_io(params, batch, rng):
        """What enters the system's head and what leaves it, from one
        evaluation: the final hidden states (the output of the last,
        top-level RMSNorm) and the loss of every token; and the same
        of every routed layer's router: its inputs (the output of the
        block's second RMSNorm), the experts it chose and their
        weights, beside the layers' load counters; every routed
        layer's output; and one conv mixer's and the attention mixer's
        input and output."""
        logits, captured = model.apply(
            {"params": params}, batch["inputs"], train=True, rng=rng,
            capture_intermediates=lambda module, _method: module.path
            in captured_paths,
            mutable=["moe_load", "moe_routing", "intermediates"],
        )
        (hidden,) = captured["intermediates"]["RMSNorm_0"]["__call__"]
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["targets"]
        )
        load = moe_load_counters(model.config, captured)
        for name in ("experts", "weights"):
            load[name] = jnp.stack(
                [
                    captured["moe_routing"][f"layer_{i}"]["moe"][name][0]
                    for i in routed
                ]
            )
        def seen(layer, module):
            return captured["intermediates"][f"layer_{layer}"][module][
                "__call__"
            ][0]

        for name, module in (("inputs", "RMSNorm_1"), ("outputs", "moe")):
            load[name] = jnp.stack(
                [
                    seen(i, module).reshape(-1, sizes["hidden_size"])
                    for i in routed
                ]
            )
        for kind, (i, mixer) in mixers.items():
            load[kind] = (seen(i, "RMSNorm_0"), seen(i, mixer))
        return hidden, losses, load

    def routed_vjp(moe_params, x, cotangent, sets=False):
        """The system's routed layer alone, backward: the gradients of
        ``sum(y * cotangent)`` with respect to the layer's parameters
        and its input ``x`` [tokens, d]; with ``sets`` also the experts
        ITS router chose [tokens, top_k] (``near_ties``)."""

        def objective(moe_params, x):
            y, sown = RoutedFFN(model.config).apply(
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
        "routed_vjp": routed_vjp,
        "checkpoint_transforms": None,
    }


# ---- the plain reference --------------------------------------------


def reference_weights(params, sizes: dict) -> dict:
    """The system's parameter tree in the reference's own layout."""
    layers = []
    for i, kind in enumerate(sizes["layer_types"]):
        block = params[f"layer_{i}"]
        layer = {
            "norm_op": block["RMSNorm_0"]["scale"],
            "norm_ffn": block["RMSNorm_1"]["scale"],
        }
        if kind == "conv":
            conv = block["short_conv"]
            layer.update(
                w_in=conv["in_proj"]["kernel"],  # [d, 3 (B, C, X), d]
                taps=conv["conv"],  # [taps, d]; the last is z_t's
                w_out=conv["out_proj"]["kernel"],
            )
        else:
            attn = block["attention"]
            layer.update(
                wq=attn["q"]["kernel"],  # [d, heads, hd]
                wk=attn["kv"]["kernel"][:, 0],  # [d, kv_heads, hd]
                wv=attn["kv"]["kernel"][:, 1],
                q_norm=attn["q_norm"]["scale"],
                k_norm=attn["k_norm"]["scale"],
                wo=attn["out"]["kernel"],  # [d, d]
            )
        if i < sizes["num_dense_layers"]:
            ffn = block["ffn"]
            layer.update(
                w1=ffn["ff_gate"]["kernel"],
                w3=ffn["ff_up"]["kernel"],
                w2=ffn["ff_down"]["kernel"],
            )
        else:
            moe = block["moe"]
            layer.update(
                router=moe["router"],  # [d, num_experts]
                bias=moe["expert_bias"],
                w1=moe["w_gate"],  # [held, d, f]
                w3=moe["w_up"],
                w2=moe["w_down"],  # [held, f, d]
            )
        layers.append(layer)
    return {
        "embedding": params["embed"]["embedding"],
        "layers": layers,
        "norm_out": params["RMSNorm_0"]["scale"],
    }


# What the comparisons can tell apart is MEASURED: every reference
# function takes a ``variant`` that computes it WRONG on purpose
# (never used by ``reference_check``; benchmark/tests/lfm2_precision.py
# reads each against the right one, tests/ hold that each differs).
ROUTER_FAULTS = ("bf16_scores", "softmax", "weights_with_bias")
ROUTED_FAULTS = (
    "swapped_expert",  # 512 rows of held expert 0 through expert 1
    "no_gate",  # W2 (W3 x): the silu(W1 x) gate dropped
    "unit_weights",  # the router's weights dropped from the sum
    "bf16_accumulate",  # partial sums rounded to, and added in, bf16
)
CONV_FAULTS = ("reversed_taps", "no_c_gate")
ATTENTION_FAULTS = ("kv_head_modulo", "rope_theta_1e4", "no_qk_norm")
SWAPPED_ROWS = 512  # a row tile of the grouped products


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


def reference_routed_ffn(
    layer: dict, x, sizes: dict, first_expert: int | None = None,
    variant: str = "", system=None,
):
    """The published routed FFN, this share of it: the router
    (``reference_router``) over all experts, and the sum over the
    experts chosen AND held (``first_expert ..`` + the number of
    expert weights the layer has) of weight x gated FFN. Returns (y,
    rows each of ALL experts was chosen for), and with ``system`` the
    router's ``Ties``. ``variant``: one of ``ROUTER_FAULTS`` or
    ``ROUTED_FAULTS``."""
    import jax
    import jax.numpy as jnp

    first = sizes["first_expert"] if first_expert is None else first_expert
    total = sizes["num_experts"]
    chosen, weights, *ties = reference_router(
        layer, x, sizes, variant if variant in ROUTER_FAULTS else "",
        system,
    )

    def expert(held):
        up = _product(x, layer["w3"][held], variant)
        if variant != "no_gate":
            up = jax.nn.silu(_product(x, layer["w1"][held], variant)) * up
        return _product(up, layer["w2"][held], variant)

    y = jnp.zeros_like(x)
    for held in range(layer["w1"].shape[0]):
        mask = chosen == first + held  # [..., top_k]
        weight = jnp.where(
            mask, 1.0 if variant == "unit_weights" else weights, 0.0
        ).sum(-1, keepdims=True)
        out = expert(held)
        if variant == "swapped_expert" and held == 0:
            mine = mask.any(-1).reshape(-1)
            early = (jnp.cumsum(mine) <= SWAPPED_ROWS) & mine
            early = early.reshape(mask.shape[:-1] + (1,))
            out = jnp.where(early, expert(1), out)
        y = y + weight * out
    counts = jnp.sum(
        chosen[..., None] == jnp.arange(total),
        axis=tuple(range(chosen.ndim)),
    )
    return (y, counts, *ties)


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


def reference_short_conv(layer: dict, u, variant: str = ""):
    """The gated short convolution on ``u`` [batch, seq, d].
    ``variant``: one of ``CONV_FAULTS``."""
    import jax.numpy as jnp

    bcx = jnp.einsum("bsd,dge->bsge", u, layer["w_in"])
    gate_b, gate_c, inner = bcx[:, :, 0], bcx[:, :, 1], bcx[:, :, 2]
    z = gate_b * inner
    taps = layer["taps"]  # taps[j] multiplies z[t - (n - 1 - j)]
    n, seq = taps.shape[0], z.shape[1]
    mixed = jnp.zeros_like(z)
    for j in range(n):
        back = j if variant == "reversed_taps" else n - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, : seq - back]], axis=1
        )
        mixed = mixed + taps[j] * shifted
    if variant != "no_c_gate":
        mixed = gate_c * mixed
    return mixed @ layer["w_out"]


def reference_attention(layer: dict, u, sizes: dict, variant: str = ""):
    """Grouped-query causal attention with per-head RMSNorm on q and k
    and rotary on interleaved pairs, by query blocks. ``variant``: one
    of ``ATTENTION_FAULTS``."""
    import jax
    import jax.numpy as jnp

    eps, theta = sizes["norm_eps"], float(sizes["rope_theta"])
    if variant == "rope_theta_1e4":
        theta = 1e4
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    group = heads // kv_heads

    def head_norm(x, scale):
        if variant == "no_qk_norm":
            return x
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale

    def rotary(x):  # [batch, seq, heads, head_dim]
        half = x.shape[-1] // 2
        inv_freq = theta ** (-jnp.arange(half) / half)
        angle = jnp.arange(x.shape[1])[:, None] * inv_freq[None, :]
        sin = jnp.sin(angle)[None, :, None, :]
        cos = jnp.cos(angle)[None, :, None, :]
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [even * cos - odd * sin, even * sin + odd * cos], axis=-1
        ).reshape(x.shape)

    q = rotary(head_norm(jnp.einsum("bsd,dhk->bshk", u, layer["wq"]), layer["q_norm"]))
    k = rotary(head_norm(jnp.einsum("bsd,dhk->bshk", u, layer["wk"]), layer["k_norm"]))
    v = jnp.einsum("bsd,dhk->bshk", u, layer["wv"])
    batch, seq, _, head_dim = q.shape
    # [.., g, r, :] is query head g * group + r, on kv head g (the
    # fault: query head r * kv_heads + g, that is head i on i % kv).
    modulo = variant == "kv_head_modulo"
    q = (
        q.reshape(batch, seq, group, kv_heads, head_dim).swapaxes(2, 3)
        if modulo
        else q.reshape(batch, seq, kv_heads, group, head_dim)
    )
    block = min(ATTENTION_QUERY_BLOCK, seq)
    key_at = jnp.arange(seq)
    outs = []
    for start in range(0, seq, block):
        scores = jnp.einsum(
            "bqgrk,bsgk->bgrqs", q[:, start:start + block], k
        ) / jnp.sqrt(jnp.float32(head_dim))
        visible = key_at[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(visible[None, None, None], scores, -jnp.inf)
        outs.append(
            jnp.einsum(
                "bgrqs,bsgk->bqgrk", jax.nn.softmax(scores, axis=-1), v
            )
        )
    attended = jnp.concatenate(outs, axis=1)
    if modulo:
        attended = attended.swapaxes(2, 3)
    return attended.reshape(batch, seq, -1) @ layer["wo"]


def reference_loss(
    weights: dict, inputs, targets, sizes: dict, per_token: bool = False,
    variant: str = "",
):
    """Next-token cross-entropy of the share (mean, or every token's
    with ``per_token``) and the routed layers' expert counts
    ``[routed layers, num_experts]``. Float32, "highest" matmul
    precision, no kernel, no remat."""
    import jax
    import jax.numpy as jnp

    eps = sizes["norm_eps"]

    def rms_norm(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale

    with jax.default_matmul_precision("highest"):
        x = weights["embedding"][inputs].astype(jnp.float32)
        counts = []
        for layer in weights["layers"]:
            u = rms_norm(x, layer["norm_op"])
            if "w_in" in layer:
                x = x + reference_short_conv(layer, u)
            else:
                x = x + reference_attention(layer, u, sizes)
            u = rms_norm(x, layer["norm_ffn"])
            if "router" in layer:
                y, chosen = reference_routed_ffn(
                    layer, u, sizes, variant=variant
                )
                counts.append(chosen)
                x = x + y
            else:
                x = x + (
                    jax.nn.silu(u @ layer["w1"]) * (u @ layer["w3"])
                ) @ layer["w2"]
        hidden = rms_norm(x, weights["norm_out"])
        if variant == "bf16_head":
            logits = (
                hidden.astype(jnp.bfloat16)
                @ weights["embedding"].T.astype(jnp.bfloat16)
            ).astype(jnp.float32)
        else:
            logits = hidden @ weights["embedding"].T
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), targets[..., None], axis=-1
        )
        loss = -picked[..., 0] if per_token else -picked.mean()
        return loss, jnp.stack(counts)


def reference_head(hidden, embedding, targets):
    """Tied head and next-token loss in float32 on the operands the
    system's head gets: the hidden states as handed over, the table
    rounded to their type. Returns (logits, loss of every token)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        # reduce_precision, not a cast there and back: the compiler may
        # drop such a pair.
        kind = jnp.finfo(hidden.dtype)
        table = jax.lax.reduce_precision(embedding, kind.nexp, kind.nmant)
        logits = hidden.astype(jnp.float32) @ table.T
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), targets[..., None], axis=-1
        )
        return logits, -picked[..., 0]


def reference_router(
    layer: dict, x, sizes: dict, variant: str = "", system=None
):
    """The published router alone on ``x`` [..., d]: float32 sigmoid
    scores over all experts, the top 4 of ``score + bias``, weights =
    the chosen scores WITHOUT the bias over their sum (+ epsilon) times
    ``routed_scaling_factor``. Returns (experts [..., top_k] in
    ascending order, their weights in that order). With ``system``,
    the sets the system chose: a near-tied token's experts are the
    system's (``near_ties.settle``), and a third result, the ``Ties``.

    ``variant``: one of ``ROUTER_FAULTS``."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    if variant == "bf16_scores":
        scores = jax.nn.sigmoid(
            x.astype(jnp.bfloat16) @ layer["router"].astype(jnp.bfloat16)
        ).astype(jnp.float32)
    else:
        with jax.default_matmul_precision("highest"):
            logits = x @ layer["router"]
        scores = (
            jax.nn.softmax(logits, axis=-1)
            if variant == "softmax"
            else jax.nn.sigmoid(logits)
        )
    _, chosen = jax.lax.top_k(
        scores + layer["bias"], sizes["num_experts_per_tok"]
    )
    if system is not None:
        chosen, ties = near_ties.settle(
            scores + layer["bias"], chosen, system
        )
    picked = jnp.take_along_axis(
        scores + layer["bias"] if variant == "weights_with_bias"
        else scores,
        chosen, -1,
    )
    weights = (
        picked
        / (picked.sum(-1, keepdims=True) + sizes["expert_weight_eps"])
        * sizes["routed_scaling_factor"]
    )
    found = in_expert_order(chosen, weights)
    return found if system is None else (*found, ties)


def in_expert_order(experts, weights):
    """A token's chosen experts in ascending order, and their weights
    in that order."""
    import jax.numpy as jnp

    order = jnp.argsort(experts, axis=-1)
    return (
        jnp.take_along_axis(experts, order, -1),
        jnp.take_along_axis(weights, order, -1),
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
        "router_grad_err": slice_error(
            got_w["router"][None], want_w["router"][None]
        ),
        "input_grad_err": layer_error(got_x, want_x)[1],
    }


def mixer_reference(kind: str, layer: dict, u, sizes: dict, variant=""):
    """The reference's conv or attention mixer on the system's ``u``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        u = u.astype(jnp.float32)
        if kind == "conv":
            return reference_short_conv(layer, u, variant)
        return reference_attention(layer, u, sizes, variant)


def routed_check(built: dict, sizes: dict):
    """The program of comparisons 5 and 6 for ONE routed layer:
    ``check(reference layer, the system's layer parameters, the
    system's input x [tokens, d], its output y, the experts its router
    chose)``. The backward runs on the first row only. Without the
    experts the reference routes for itself alone, as before PR 62."""
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


def layer_checks(built: dict, params, load: dict, sizes: dict) -> dict:
    """Comparisons 5 and 6: every routed layer forward and backward,
    one conv mixer and the attention mixer, each alone on the system's
    own inputs. One small program a layer (the routed layers share
    theirs), so that no two layers' float32 intermediates are alive
    together."""
    import functools

    import jax

    weights = reference_weights(params, sizes)["layers"]
    kinds = sizes["layer_types"]
    routed = jax.jit(routed_check(built, sizes))

    @functools.partial(jax.jit, static_argnames="kind")
    def mixer(layer, u, y, kind):
        return layer_error(y, mixer_reference(kind, layer, u, sizes))

    found = [
        routed(
            weights[at], params[f"layer_{at}"]["moe"],
            load["inputs"][i], load["outputs"][i], load["experts"][i],
        )
        for i, at in enumerate(
            range(sizes["num_dense_layers"], sizes["num_hidden_layers"])
        )
    ]
    worst = near_ties.worst_layer(found)
    for kind, name in (("conv", "conv"), ("attention", "full_attention")):
        token, rms = mixer(weights[kinds.index(name)], *load[kind], kind)
        worst[f"{kind}_token_err"] = float(token)
        worst[f"{kind}_rms_err"] = float(rms)
    return worst


def reference_check(built: dict, params, dataset: dict, sizes: dict) -> dict:
    """The system against the plain reference on the run's own weights
    and a sample of the seeded data, both computed on this device: the
    mean loss of the whole model, the head and every router token by
    token on the system's own inputs to them, the routed layers'
    per-expert row counts, and every routed layer (forward and
    backward), a conv mixer and the attention mixer alone on the
    system's own inputs (``layer_checks``)."""
    import jax
    import jax.numpy as jnp

    sample = {
        k: v[:REFERENCE_SEQUENCES] for k, v in dataset.items()
    }
    hidden, token_losses, load = jax.jit(built["head_io"])(
        params, sample, jax.random.key(0)
    )

    # Everything is an argument: data closed over would be constants of
    # the program and make its compile-cache key follow the seed.
    def compare(weights, sample, hidden, token_losses, load):
        _, head_losses = reference_head(
            hidden, weights["embedding"], sample["targets"]
        )
        loss, counts = reference_loss(
            weights, sample["inputs"], sample["targets"], sizes
        )
        assignments = sample["inputs"].size * sizes["num_experts_per_tok"]
        routers = [
            layer for layer in weights["layers"] if "router" in layer
        ]
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
        return {
            "router_set_mismatch_share": jnp.max(jnp.stack(set_mismatch)),
            "router_weight_err": jnp.max(jnp.stack(weight_err)),
            "system_loss": token_losses.mean(),
            "reference_loss": loss,
            "head_token_loss_err": jnp.max(
                jnp.abs(token_losses - head_losses)
            ),
            "routing_l1_share": routing_l1_share(
                load["held_rows"], counts, sizes
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
        }

    result = {
        k: float(v)
        for k, v in jax.jit(compare)(
            reference_weights(params, sizes), sample, hidden,
            token_losses, load,
        ).items()
    }
    result.update(layer_checks(built, params, load, sizes))
    rel = abs(result["system_loss"] - result["reference_loss"]) / abs(
        result["reference_loss"]
    )
    result.update(
        rel_diff=rel,
        rtol=REFERENCE_RTOL,
        head_atol=HEAD_TOKEN_LOSS_ATOL,
        routing_tol=ROUTING_L1_SHARE,
        router_set_tol=ROUTER_SET_MISMATCH_SHARE,
        router_weight_atol=ROUTER_WEIGHT_ATOL,
        near_tie_margin=near_ties.NEAR_TIE_MARGIN,
        layer_limits=LAYER_LIMITS,
        grad_limits=[EXPERT_GRAD_RTOL, ROUTER_GRAD_RTOL, INPUT_GRAD_RMS],
        ok=bool(
            np.isfinite(result["system_loss"])
            and rel <= REFERENCE_RTOL
            and result["head_token_loss_err"] <= HEAD_TOKEN_LOSS_ATOL
            and result["router_set_mismatch_share"]
            <= ROUTER_SET_MISMATCH_SHARE
            and result["router_weight_err"] <= ROUTER_WEIGHT_ATOL
            and near_ties.within(
                result, ROUTER_SET_MISMATCH_SHARE, sample["inputs"].size
            )
            and result["routing_l1_share"] <= ROUTING_L1_SHARE
            and result["rows_dropped"] == 0
            and result["rows_unaccounted"] == 0
            and all(
                result[f"{kind}_token_err"] <= token
                and result[f"{kind}_rms_err"] <= rms
                for kind, (token, rms) in LAYER_LIMITS.items()
            )
            and result["expert_grad_err"] <= EXPERT_GRAD_RTOL
            and result["router_grad_err"] <= ROUTER_GRAD_RTOL
            and result["input_grad_err"] <= INPUT_GRAD_RMS
        ),
    )
    return result
