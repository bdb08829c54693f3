"""kimi-linear-48b-a3b: builder of the system under test, and its plain
reference.

One chip's share of Kimi-Linear-48B-A3B-Instruct under expert
parallelism over 32 chips (``kimi-linear-48b-a3b.json``: published
widths, published layers 1-5 = [kda + dense FFN, kda, kda, mla, kda],
8 of each routed layer's 256 experts held beside the shared expert, an
eighth of both vocabulary tables). The system side goes through the
program's own entry points (``TransformerConfig`` / ``TransformerLM``
with ``layer_types`` "kda" and "mla", ``ops/kda.py``'s chunked delta
rule and its Pallas kernels, the flash kernels at a q/k width of 192
and a v width of 128, the grouped products, ``ElasticTrainer``). The
reference side is written from the published equations with the
departures the JSON lists, in plain float32 ``jax.numpy`` at "highest"
matmul precision, and imports nothing from ``adaptdl_tpu``: the delta
rule token by token as a ``lax.scan``, latent attention as a dense
masked softmax by query blocks, experts as a Python loop over the held
ones, no kernel, no remat, the same share.
"""

from __future__ import annotations

import numpy as np

from benchmark import near_ties

# What decides ``correct`` (reference_check), on the run's own weights
# at the published widths on ONE row of the timed length. Readings: my
# chip runs, PR 46, TPU v5 lite (PERF.md section 6). "first" is the
# largest the system gave over its seeds (the cell's own runs print
# them: ``compared.reference``). "second" is what the reference
# computed in the nearest precision BELOW the stated one gave against
# the reference itself on the system's own inputs, compiled as stated
# (benchmark/tests/kimi_precision.py --controls, two seeds): the
# routers with bfloat16 scores, the kda mixer with a bfloat16 state,
# and with a bfloat16 running sum of the decay. Only the limits of 3,
# and of 5 and 6 for "kda", have a second reading; the others are a
# first reading and room, and say so. Which limit refuses which fault:
# bf16 scores — both of 3, by 200 and 50 times; a bf16 decay sum — the
# kda mixer's worst token and its input gradient; a bf16 state — the
# input gradient ALONE, by 5 to 15%: on every other number a state
# rounded after every token reads UNDER the system's own error, which
# is that of bfloat16 operands (the decay forgets a rounding within
# tens of tokens), so no limit on an output can refuse it and admit
# the system.
#
# 1. Whole model: |system mean loss - reference mean loss| / reference.
#    A mean over 16 384 tokens hardly sees rounding; it holds what
#    moves every token.
REFERENCE_RTOL = 2.5e-4
# 2. The head alone, token by token, on the hidden states the SYSTEM
#    hands to it: float32 accumulation, logits, softmax and loss.
HEAD_TOKEN_LOSS_ATOL = 1e-3  # max |token loss - reference|, nats
# 3. Every router alone, token by token, on the inputs the SYSTEM hands
#    to it: sets of eight and their weights against the float32
#    "highest" router on the same inputs. bfloat16 scores choose other
#    sets for a share of the tokens and move the weights.
#    First: 0 and 0 on every seed (equal sets, equal weights); second
#    (bf16 scores): 0.201 / 0.205 of the tokens and 2.58e-3 / 2.59e-3.
ROUTER_SET_MISMATCH_SHARE = 1e-3
ROUTER_WEIGHT_ATOL = 5e-5
# 4. Rows each held expert received against the whole reference's
#    count (the reference routes ITS float32 hidden states): blunt, it
#    holds the bookkeeping; exactly: no row dropped, held + left-out =
#    tokens x 8, and the shared expert multiplied every token.
ROUTING_L1_SHARE = 0.05
# 5. Every routed layer (with its shared expert), one kda mixer and the
#    mla mixer, each ALONE, token by token, on the inputs the SYSTEM
#    hands it: ``layer_error`` = (worst token, rms over tokens) of
#    |system - reference| over the layer's rms output norm.
# 6. Backward, each alone on the first row: gradients of ``sum(y *
#    cotangent)`` (cotangent = the layer's input) with respect to every
#    parameter leaf and the input, against ``jax.grad`` of the
#    reference: |system - reference| / |reference| of a leaf (routed:
#    of each expert's slice), the worst; the input as 5's rms.
#    "kda", first readings over seven seeds / second readings (bf16
#    state; bf16 decay sum) on seeds 4600000101, 4600000203:
#      worst token   0.0102-0.0111 / 0.0079, 0.0080; 0.0326, 0.0431
#      rms           0.0086-0.0088 / 0.0059, 0.0060; 0.0090, 0.0081
#      worst leaf    0.0045-0.0060 / 0.0072, 0.0050; 0.0081, 0.0077
#      input's rms   0.0086-0.0087 / 0.0106, 0.0097; 0.0108, 0.0101
#    The worst token's limit lies between the system and the decay
#    sum's fault (1.8 times the first, 0.6 of the lowest second); the
#    input gradient's between the system and BOTH faults, with 5-6% to
#    either side — the system's reading moved by 1.5% over its seeds.
#    The rms and the worst leaf separate nothing (a fault reads under
#    or among the system's own): blunt guards, a first reading and
#    room (1.25 and 2 times). "routed" and "mla": first readings and
#    room, no second reading was made (rms 0.0056, 0.0040; gradients
#    0.0044 / 0.0047 / 0.0048 and 0.0035 / 0.0050; worst tokens
#    0.010-0.015).
LAYER_LIMITS = {
    # kind: (worst token, rms over tokens)
    "routed": (0.04, 0.0075),
    "kda": (0.02, 0.011),
    "mla": (0.2, 0.0075),
}
EXPERT_GRAD_RTOL = 0.012  # worst expert's slice of a weight leaf
ROUTER_GRAD_RTOL = 0.02  # the router leaf
INPUT_GRAD_RMS = 0.0075  # a routed layer's input gradient
MIXER_GRAD_LIMITS = {
    # kind: (worst parameter leaf, the input's rms)
    "kda": (0.012, 0.0092),
    "mla": (0.012, 0.0085),
}
REFERENCE_SEQUENCES = 1
ATTENTION_QUERY_BLOCK = 128
SCAN_BLOCK = 128  # tokens of one checkpointed block of the recurrence
HEAD_GROUP = 4  # heads of the recurrence the reference runs at once
KDA_L2_EPS = 1e-6


def units_per_sample(sizes: dict) -> int:
    return int(sizes["sequence_length"])


def layer_kinds(sizes: dict) -> list[str]:
    """The kept layers' mixer kinds in order, from the published
    1-indexed lists."""
    linear = sizes["linear_attn_config"]
    kinds = []
    for layer in range(1, sizes["num_hidden_layers"] + 1):
        if layer in linear["kda_layers"]:
            kinds.append("kda")
        elif layer in linear["full_attn_layers"]:
            kinds.append("mla")
        else:
            raise ValueError(f"layer {layer} is in neither list")
    return kinds


def forward_flops_per_token(sizes: dict) -> dict[str, float]:
    """Forward matmul FLOPs per token, by part: 2 FLOPs per
    multiply-accumulate, the causal half of attention at the timed
    length, the delta rule as its chunked form multiplies it
    (``benchmark/kda.py``), routed experts at UNIFORM routing, no
    recomputation — counted as ``benchmark/flops.py`` counts."""
    from benchmark import kda as kda_count

    d = sizes["hidden_size"]
    linear = sizes["linear_attn_config"]
    heads, hd = linear["num_heads"], linear["head_dim"]
    width, rank = heads * hd, sizes["kda_gate_rank"]
    kinds = layer_kinds(sizes)
    n_kda, n_mla = kinds.count("kda"), kinds.count("mla")
    a_heads = sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    v_dim, latent = sizes["v_head_dim"], sizes["kv_lora_rank"]
    dense = sizes["first_k_dense_replace"]
    routed = sizes["num_hidden_layers"] - dense
    per_token_experts = (
        sizes["num_experts_per_token"] * sizes["experts_held"]
        / sizes["router_width"]
    )
    expert = 2 * 3 * d * sizes["moe_intermediate_size"]
    return {
        "kda_projections": float(
            n_kda * 2 * (
                3 * d * width + 2 * (d * rank + rank * width)
                + d * heads + width * d
            )
        ),
        "kda_mixing": float(
            n_kda * kda_count.forward_flops_per_token(
                heads, hd, hd, sizes["kda_chunk"]
            )
        ),
        "mla_projections": float(
            n_mla * 2 * (
                d * a_heads * qk + d * (latent + sizes["qk_rope_head_dim"])
                + latent * a_heads * (sizes["qk_nope_head_dim"] + v_dim)
                + a_heads * v_dim * d
            )
        ),
        "mla_attention": float(
            n_mla * 2 * sizes["sequence_length"] * a_heads * (qk + v_dim)
            * 0.5
        ),
        "dense_ffn": float(dense * 2 * 3 * d * sizes["intermediate_size"]),
        "router": float(routed * 2 * d * sizes["router_width"]),
        "shared_experts": float(
            routed * sizes["num_shared_experts"] * expert
        ),
        "routed_experts": float(routed * per_token_experts * expert),
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


def model_config(sizes: dict, attention_fn=None):
    """The ``TransformerConfig`` of these sizes."""
    import jax.numpy as jnp

    from adaptdl_tpu.models import TransformerConfig
    from adaptdl_tpu.ops import kda as kda_op

    linear = sizes["linear_attn_config"]
    # What the file says of the system's chunk (the counts of
    # benchmark/kda.py take it from there) is the program's constant.
    assert sizes["kda_chunk"] == kda_op.CHUNK
    assert linear["num_heads"] == sizes["num_attention_heads"]
    assert linear["head_dim"] == sizes["v_head_dim"]
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
        rope=False,
        head_dim=linear["head_dim"],
        layer_types=tuple(layer_kinds(sizes)),
        conv_kernel=linear["short_conv_kernel_size"],
        kda_gate_rank=sizes["kda_gate_rank"],
        kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        experts_total=sizes["router_width"],
        experts_held=sizes["experts_held"],
        first_expert=sizes["first_expert"],
        experts_top_k=sizes["num_experts_per_token"],
        d_expert=sizes["moe_intermediate_size"],
        d_shared_expert=sizes["num_shared_experts"]
        * sizes["moe_intermediate_size"],
        num_dense_layers=sizes["first_k_dense_replace"],
        expert_weight_eps=sizes["expert_weight_eps"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        experts_router=sizes["moe_router_activation_func"],
        tie_embeddings=sizes["tie_word_embeddings"],
    )


def checked_mixers(sizes: dict) -> dict[str, int]:
    """kind -> the layer whose mixer is checked alone: the LAST kda
    layer (its input has passed every kind of layer) and the mla
    layer."""
    kinds = layer_kinds(sizes)
    return {
        "kda": len(kinds) - 1 - kinds[::-1].index("kda"),
        "mla": kinds.index("mla"),
    }


def build(sizes: dict, geometry: dict, seed: int) -> dict:
    """The system under test for one cell: model, weights made on the
    device in one jitted call from the seed, loss, trainer."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    from adaptdl_tpu.models.transformer import (
        KDA,
        LatentAttention,
        RoutedFFN,
        TransformerLM,
        moe_load_counters,
        routed_lm_loss_fn,
    )
    from adaptdl_tpu.ops.chunked_xent import chunked_softmax_xent
    from adaptdl_tpu.ops.flash_attention import flash_attention
    from adaptdl_tpu.scaling_rules import AdamScale
    from adaptdl_tpu.trainer import ElasticTrainer

    block = min(128, sizes["sequence_length"])
    cfg = model_config(
        sizes,
        functools.partial(flash_attention, block_q=block, block_k=block),
    )
    model = TransformerLM(cfg)
    # Parameter shapes depend on neither the attention function nor
    # the sequence: init through plain attention on a short row.
    init_model = TransformerLM(model_config(sizes))
    dummy = jnp.zeros((1, min(128, sizes["sequence_length"])), jnp.int32)

    def fresh(key):
        """flax's initialisers, and the embedding table at UNIT
        variance (as keye-vl-2.0-30b-a3b: at flax's 1 / d a fresh
        model's routing collapses onto a few experts)."""
        params = init_model.init(key, dummy, train=False)["params"]
        table = params["embed"]["embedding"]
        params["embed"]["embedding"] = table * table.shape[1] ** 0.5
        return params

    params = jax.jit(lambda key: fresh(key))(jax.random.key(seed))

    routed = range(
        sizes["first_k_dense_replace"], sizes["num_hidden_layers"]
    )
    mixers = checked_mixers(sizes)
    captured_paths = (
        {("RMSNorm_0",)}
        | {
            (f"layer_{i}", name)
            for i in routed
            for name in ("RMSNorm_1", "moe")
        }
        | {
            (f"layer_{i}", name)
            for kind, i in mixers.items()
            for name in ("RMSNorm_0", kind)
        }
    )

    def head_io(params, batch, rng):
        """From ONE evaluation of the whole model, as it runs: the
        final hidden states and every token's loss; of every routed
        layer its input, its output (shared expert included), the
        router's choice and the load counters; of one kda mixer and
        the mla mixer their input and output."""
        hidden, captured = model.apply(
            {"params": params}, batch["inputs"], train=True, rng=rng,
            return_hidden=True,
            capture_intermediates=lambda module, _method: module.path
            in captured_paths,
            mutable=["moe_load", "moe_routing", "intermediates"],
        )
        losses = head_losses(params, hidden, batch["targets"])
        load = moe_load_counters(cfg, captured)

        def seen(layer, module):
            return captured["intermediates"][f"layer_{layer}"][module][
                "__call__"
            ][0]

        for name in ("experts", "weights"):
            load[name] = [
                captured["moe_routing"][f"layer_{i}"]["moe"][name][0]
                for i in routed
            ]
        for name, module in (("inputs", "RMSNorm_1"), ("outputs", "moe")):
            load[name] = [
                seen(i, module).reshape(-1, sizes["hidden_size"])
                for i in routed
            ]
        for kind, i in mixers.items():
            load[kind] = (seen(i, "RMSNorm_0"), seen(i, kind))
        return hidden, losses, load

    def head_losses(params, hidden, targets):
        """The system's head on ``hidden``, as the timed loss runs it
        (streamed ``head_chunk_rows`` rows at a time): every token's
        loss."""
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

    def mixer_vjp(kind, mixer_params, x, cotangent):
        """The system's kda or mla mixer alone on ``x`` [1, seq, d]:
        the gradients of ``sum(y * cotangent)`` with respect to (its
        parameters, x)."""
        module = {"kda": KDA, "mla": LatentAttention}[kind](cfg)

        def objective(mixer_params, x):
            y = module.apply({"params": mixer_params}, x, None)
            return jnp.sum(y.astype(jnp.float32) * cotangent)

        return jax.grad(objective, argnums=(0, 1))(mixer_params, x)

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
        "loss_fn": loss_fn,
        "head_io": head_io,
        "head_losses": head_losses,
        "routed_vjp": routed_vjp,
        "mixer_vjp": mixer_vjp,
        "checkpoint_transforms": None,
    }


# ---- the plain reference --------------------------------------------


def reference_weights(params, sizes: dict) -> dict:
    """The system's parameter tree in the reference's own layout."""
    layers = []
    for i, kind in enumerate(layer_kinds(sizes)):
        block = params[f"layer_{i}"]
        layer = {
            "norm_op": block["RMSNorm_0"]["scale"],
            "norm_ffn": block["RMSNorm_1"]["scale"],
        }
        if kind == "kda":
            layer["kda"] = kda_weights(block["kda"])
        else:
            layer["mla"] = mla_weights(block["mla"])
        if i < sizes["first_k_dense_replace"]:
            ffn = block["ffn"]
            layer.update(
                w1=ffn["ff_gate"]["kernel"],
                w3=ffn["ff_up"]["kernel"],
                w2=ffn["ff_down"]["kernel"],
            )
        else:
            layer.update(routed_weights(block["moe"]))
        layers.append(layer)
    return {
        "embedding": params["embed"]["embedding"],
        "head": params["lm_head"],  # [vocab, d]
        "layers": layers,
        "norm_out": params["RMSNorm_0"]["scale"],
    }


def kda_weights(mixer) -> dict:
    return {
        "w_qkv": mixer["qkv"]["kernel"],  # [d, 3 (q, k, v), heads * hd]
        "taps": mixer["conv"],  # [3, taps, heads * hd]; the last is t's
        "f_a": mixer["f_a"]["kernel"],
        "f_b": mixer["f_b"],
        "A_log": mixer["A_log"],
        "dt_bias": mixer["dt_bias"],
        "w_beta": mixer["beta"],
        "g_a": mixer["g_a"]["kernel"],
        "g_b": mixer["g_b"],
        "o_norm": mixer["o_norm"]["scale"],
        "w_out": mixer["out"]["kernel"],
    }


# A system mixer's parameter leaves under the reference's names.
KDA_LEAVES = {
    ("qkv", "kernel"): "w_qkv", ("conv",): "taps",
    ("f_a", "kernel"): "f_a", ("f_b",): "f_b", ("A_log",): "A_log",
    ("dt_bias",): "dt_bias", ("beta",): "w_beta",
    ("g_a", "kernel"): "g_a", ("g_b",): "g_b",
    ("o_norm", "scale"): "o_norm", ("out", "kernel"): "w_out",
}
MLA_LEAVES = {
    ("q", "kernel"): "wq", ("kv_a", "kernel"): "w_kv_a",
    ("kv_norm", "scale"): "kv_norm", ("kv_b", "kernel"): "w_kv_b",
    ("out", "kernel"): "w_out",
}


def mla_weights(mixer) -> dict:
    return {
        "wq": mixer["q"]["kernel"],  # [d, heads, nope + pe]
        "w_kv_a": mixer["kv_a"]["kernel"],  # [d, latent + pe]
        "kv_norm": mixer["kv_norm"]["scale"],
        "w_kv_b": mixer["kv_b"]["kernel"],  # [latent, heads, nope + v]
        "w_out": mixer["out"]["kernel"],
    }


def routed_weights(moe) -> dict:
    shared = moe["shared"]
    return {
        "router": moe["router"],  # [d, router_width]
        "bias": moe["expert_bias"],
        "w1": moe["w_gate"],  # [held, d, f]
        "w3": moe["w_up"],
        "w2": moe["w_down"],  # [held, f, d]
        "s1": shared["ff_gate"]["kernel"],
        "s3": shared["ff_up"]["kernel"],
        "s2": shared["ff_down"]["kernel"],
    }


# What the comparisons can tell apart is MEASURED: the reference
# functions take a ``variant`` that computes in the nearest precision
# below the stated one (never used by ``reference_check``;
# benchmark/tests/kimi_precision.py reads each against the right one,
# the tests hold that each differs).
ROUTER_FAULTS = ("bf16_scores",)
KDA_FAULTS = (
    "bf16_state",  # the state rounded to bfloat16 after every token
    "bf16_decay",  # the log-decay's running sum of a chunk in bfloat16
)


def _rms_norm(x, scale, eps: float):
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _gated(x, w1, w3, w2):
    import jax

    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def reference_kda(layer: dict, u, sizes: dict, variant: str = ""):
    """The gated delta-rule mixer on ``u`` [batch, seq, d], token by
    token. ``HEAD_GROUP`` heads at a time, one group after another (a
    ``lax.map`` whose body is checkpointed, so that a gradient holds a
    group's float32 arrays and not the layer's); inside a group a
    ``lax.scan`` over blocks of ``SCAN_BLOCK`` tokens, each block a
    checkpointed scan over its tokens. ``variant``: one of
    ``KDA_FAULTS``."""
    import jax
    import jax.numpy as jnp

    linear = sizes["linear_attn_config"]
    heads, hd = linear["num_heads"], linear["head_dim"]
    batch, seq, _ = u.shape
    held = min(HEAD_GROUP, heads)
    groups = heads // held
    assert groups * held == heads
    block = min(SCAN_BLOCK, seq)
    assert seq % block == 0

    def heads_apart(x, axis):  # a [.., heads * hd, ..] axis, by group
        shape = x.shape[:axis] + (groups, held, hd) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    def token(state, at):  # state [b, held, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + k_t[..., None] * (
            beta_t[..., None] * (v_t - seen)
        )[..., None, :]
        if variant == "bf16_state":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    @jax.checkpoint
    def tokens_of_block(state, rows):
        return jax.lax.scan(token, state, rows)

    def by_block(x):  # [b, s, ...] -> [blocks, block, b, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((seq // block, block) + x.shape[1:])

    def unit(x):
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, -1, keepdims=True) + KDA_L2_EPS
        )

    @jax.checkpoint
    def some_heads(operands):
        w_qkv, taps, f_b, dt_bias, a_log, w_beta = operands
        # w_qkv [d, 3, held, hd]; taps [3, n, held, hd]; taps[i, j]
        # multiplies z[t - (n - 1 - j)].
        qkv = jnp.einsum("bsd,dghe->bsghe", u, w_qkv)
        n = taps.shape[1]

        def conv(z, w):
            mixed = jnp.zeros_like(z)
            for j in range(n):
                back = n - 1 - j
                shifted = jnp.concatenate(
                    [jnp.zeros_like(z[:, :back]), z[:, : seq - back]],
                    axis=1,
                )
                mixed = mixed + w[j] * shifted
            return jax.nn.silu(mixed)

        q, k, v = (conv(qkv[:, :, i], taps[i]) for i in range(3))
        q, k = unit(q) * hd**-0.5, unit(k)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            jnp.einsum("bsr,rhe->bshe", u @ layer["f_a"], f_b) + dt_bias
        )
        if variant == "bf16_decay":
            # The running sum of a chunk of 64 held in bfloat16: every
            # partial sum rounded, then differenced back into steps.
            chunk = sizes["kda_chunk"]
            sums = g.reshape(batch, seq // chunk, chunk, held, hd)

            def rounded_sum(total, step):
                total = (total + step).astype(jnp.bfloat16).astype(
                    jnp.float32
                )
                return total, total

            _, sums = jax.lax.scan(
                rounded_sum, jnp.zeros_like(sums[:, :, 0]),
                jnp.moveaxis(sums, 2, 0),
            )
            sums = jnp.moveaxis(sums, 0, 2)
            g = jnp.concatenate(
                [sums[:, :, :1], sums[:, :, 1:] - sums[:, :, :-1]], axis=2
            ).reshape(batch, seq, held, hd)
        beta = jax.nn.sigmoid(u @ w_beta)  # [b, s, held]
        _, out = jax.lax.scan(
            tokens_of_block,
            jnp.zeros((batch, held, hd, hd), jnp.float32),
            tuple(by_block(x) for x in (q, k, v, g, beta)),
        )
        return jnp.moveaxis(out.reshape((seq,) + out.shape[2:]), 0, 1)

    out = jax.lax.map(
        some_heads,
        (
            heads_apart(layer["w_qkv"], 2), heads_apart(layer["taps"], 2),
            heads_apart(layer["f_b"], 1), heads_apart(layer["dt_bias"], 0),
            layer["A_log"].reshape(groups, held),
            jnp.moveaxis(
                layer["w_beta"].reshape(-1, groups, held), 1, 0
            ),
        ),
    )  # [groups, b, s, held, hd]
    out = jnp.moveaxis(out, 0, 2).reshape(batch, seq, heads, hd)
    out = _rms_norm(out, layer["o_norm"], sizes["rms_norm_eps"])
    gate = jax.nn.sigmoid((u @ layer["g_a"]) @ layer["g_b"])
    return (out.reshape(batch, seq, -1) * gate) @ layer["w_out"]


def reference_mla(layer: dict, u, sizes: dict):
    """Latent attention without positions on ``u`` [batch, seq, d]: a
    dense masked softmax, q and k 192 wide, v 128, one block of
    ``ATTENTION_QUERY_BLOCK`` queries after another (a ``lax.map``
    whose body is checkpointed: a gradient holds one block's
    scores)."""
    import jax
    import jax.numpy as jnp

    rank, nope = sizes["kv_lora_rank"], sizes["qk_nope_head_dim"]
    q = jnp.einsum("bsd,dhk->bshk", u, layer["wq"])
    kv_a = u @ layer["w_kv_a"]
    latent = _rms_norm(
        kv_a[..., :rank], layer["kv_norm"], sizes["rms_norm_eps"]
    )
    kv = jnp.einsum("bsr,rhk->bshk", latent, layer["w_kv_b"])
    k_pe = jnp.broadcast_to(
        kv_a[:, :, None, rank:], kv.shape[:3] + (kv_a.shape[-1] - rank,)
    )
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    v = kv[..., nope:]
    batch, seq, heads, width = q.shape
    block = min(ATTENTION_QUERY_BLOCK, seq)
    assert seq % block == 0
    key_at = jnp.arange(seq)

    @jax.checkpoint
    def attend(operands):
        q_block, start = operands
        scores = jnp.einsum("bqhk,bshk->bhqs", q_block, k) * width**-0.5
        visible = key_at[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum(
            "bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v
        )

    out = jax.lax.map(
        attend,
        (
            jnp.moveaxis(
                q.reshape(batch, seq // block, block, heads, width), 1, 0
            ),
            jnp.arange(0, seq, block),
        ),
    )  # [blocks, b, block, heads, v]
    out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, -1)
    return out @ layer["w_out"]


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
    scores over all experts, the top 8 of ``score + bias``, weights =
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
        scores + layer["bias"], sizes["num_experts_per_token"]
    )
    if system is not None:
        chosen, ties = near_ties.settle(
            scores + layer["bias"], chosen, system
        )
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = (
        picked
        / (picked.sum(-1, keepdims=True) + sizes["expert_weight_eps"])
        * sizes["routed_scaling_factor"]
    )
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
    import jax.numpy as jnp

    first = sizes["first_expert"] if first_expert is None else first_expert
    total = sizes["router_width"]
    chosen, weights, *ties = reference_router(
        layer, x, sizes, variant, system
    )
    y = jnp.zeros_like(x)
    for held in range(layer["w1"].shape[0]):
        mask = chosen == first + held  # [..., top_k]
        weight = jnp.where(mask, weights, 0.0).sum(-1, keepdims=True)
        y = y + weight * _gated(
            x, layer["w1"][held], layer["w3"][held], layer["w2"][held]
        )
    if shared:
        y = y + _gated(x, layer["s1"], layer["s3"], layer["s2"])
    counts = jnp.sum(
        chosen[..., None] == jnp.arange(total),
        axis=tuple(range(chosen.ndim)),
    )
    return (y, counts, *ties)


ROUTED_LEAVES = {  # the reference's names -> the system's leaves
    "w1": ("w_gate",), "w3": ("w_up",), "w2": ("w_down",),
    "router": ("router",), "s1": ("shared", "ff_gate", "kernel"),
    "s3": ("shared", "ff_up", "kernel"),
    "s2": ("shared", "ff_down", "kernel"),
}


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


def reference_mixer(kind: str, layer: dict, u, sizes: dict, variant=""):
    """The reference's kda or mla mixer on the system's ``u``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        u = u.astype(jnp.float32)
        if kind == "kda":
            return reference_kda(layer, u, sizes, variant)
        return reference_mla(layer, u, sizes)


def reference_mixer_vjp(kind: str, layer: dict, u, cotangent, sizes: dict):
    """Gradients of ``sum(y * cotangent)`` of a mixer with respect to
    (its weights, u), by ``jax.grad``."""
    import jax
    import jax.numpy as jnp

    def objective(layer, u):
        return jnp.sum(reference_mixer(kind, layer, u, sizes) * cotangent)

    return jax.grad(objective, argnums=(0, 1))(layer, u.astype(jnp.float32))


def reference_loss(
    weights: dict, inputs, targets, sizes: dict, per_token: bool = False,
    variant: str = "",
):
    """Next-token cross-entropy of the share (mean, or every token's
    with ``per_token``) and the routed layers' expert counts ``[routed
    layers, router_width]``. Float32, "highest" matmul precision, no
    kernel, no remat."""
    import jax
    import jax.numpy as jnp

    eps = sizes["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = weights["embedding"][inputs].astype(jnp.float32)
        counts = []
        for layer in weights["layers"]:
            u = _rms_norm(x, layer["norm_op"], eps)
            if "kda" in layer:
                x = x + reference_kda(
                    layer["kda"], u, sizes,
                    variant if variant in KDA_FAULTS else "",
                )
            else:
                x = x + reference_mla(layer["mla"], u, sizes)
            u = _rms_norm(x, layer["norm_ffn"], eps)
            if "router" in layer:
                y, chosen = reference_routed_ffn(
                    layer, u, sizes,
                    variant=variant if variant in ROUTER_FAULTS else "",
                )
                counts.append(chosen)
                x = x + y
            else:
                x = x + _gated(u, layer["w1"], layer["w3"], layer["w2"])
        hidden = _rms_norm(x, weights["norm_out"], eps)
        logits = hidden @ weights["head"].T
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), targets[..., None], axis=-1
        )
        loss = -picked[..., 0] if per_token else -picked.mean()
        return loss, jnp.stack(counts)


def reference_head(hidden, table, targets):
    """The untied head and next-token loss in float32 on the operands
    the system's head gets: the hidden states as handed over, the
    table rounded to their type. Returns (logits, loss of every
    token)."""
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
        return logits, -picked[..., 0]


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


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def routed_grad_errors(got, want) -> dict:
    """The system's (parameter gradients, input gradient) of a routed
    layer against the reference's: worst expert's slice of a held
    expert's leaf (a shared expert's leaf as one slice), the router
    leaf, the input."""
    import jax.numpy as jnp

    (got_w, got_x), (want_w, want_x) = got, want

    def err(name):
        g, w = _leaf(got_w, ROUTED_LEAVES[name]), want_w[name]
        return slice_error(g, w) if name[0] == "w" else slice_error(
            g[None], w[None]
        )

    return {
        "expert_grad_err": jnp.max(
            jnp.stack([err(n) for n in ROUTED_LEAVES if n != "router"])
        ),
        "router_grad_err": err("router"),
        "input_grad_err": layer_error(got_x, want_x)[1],
    }


def mixer_grad_errors(kind: str, got, want) -> dict:
    """A mixer's (parameter gradients, input gradient) against the
    reference's: the worst leaf's |got - want| / |want|, the input as
    ``layer_error``'s rms."""
    import jax.numpy as jnp

    (got_w, got_x), (want_w, want_x) = got, want
    leaves = KDA_LEAVES if kind == "kda" else MLA_LEAVES
    return {
        f"{kind}_param_grad_err": jnp.max(
            jnp.stack(
                [
                    slice_error(_leaf(got_w, path)[None], want_w[name][None])
                    for path, name in leaves.items()
                ]
            )
        ),
        f"{kind}_input_grad_err": layer_error(got_x, want_x)[1],
    }


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


def mixer_check(built: dict, sizes: dict, kind: str):
    """Comparisons 5 and 6 for one mixer: ``check(reference mixer, the
    system's mixer parameters, the system's input u [1, seq, d], its
    output y)``. Four programs, one after another: the reference's
    forward, the system's gradients, the reference's, the comparison —
    the device holds the run's train state beside them."""
    import functools

    import jax
    import jax.numpy as jnp

    def forward(layer, u, y):
        return layer_error(y, reference_mixer(kind, layer, u, sizes))

    def system(mixer_params, u):
        return built["mixer_vjp"](kind, mixer_params, u, u.astype(jnp.float32))

    def reference(layer, u):
        return reference_mixer_vjp(
            kind, layer, u, u.astype(jnp.float32), sizes
        )

    def check(layer, mixer_params, u, y):
        token, rms = jax.jit(forward)(layer, u, y)
        errors = jax.jit(functools.partial(mixer_grad_errors, kind))(
            jax.jit(system)(mixer_params, u), jax.jit(reference)(layer, u)
        )
        return {
            f"{kind}_token_err": token, f"{kind}_rms_err": rms, **errors
        }

    return check


def layer_checks(built: dict, params, load: dict, sizes: dict) -> dict:
    """Comparisons 5 and 6: every routed layer, one kda mixer and the
    mla mixer, forward and backward, each alone on the system's own
    inputs. One program a layer kind, so that no two layers' float32
    intermediates are alive together."""
    import jax

    weights = reference_weights(params, sizes)["layers"]
    routed = jax.jit(routed_check(built, sizes))
    found = [
        routed(
            weights[at], params[f"layer_{at}"]["moe"],
            load["inputs"][i], load["outputs"][i], load["experts"][i],
        )
        for i, at in enumerate(
            range(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
        )
    ]
    worst = near_ties.worst_layer(found)
    for kind, at in checked_mixers(sizes).items():
        u, y = load[kind]
        errors = mixer_check(built, sizes, kind)(
            weights[at][kind], params[f"layer_{at}"][kind], u[:1], y[:1]
        )
        worst.update({k: float(v) for k, v in errors.items()})
    return worst


# The TPU compiler's default (``xla_allow_excess_precision``) keeps a
# value in float32 where the program rounds it to bfloat16 on the way
# to the next operation (the final norm's output on its way into the
# head: 1e-2 nats a token). More precision than stated is no fault, but
# a comparison layer by layer needs what a layer CONSUMED to be what
# the capture shows: the model's program of the comparisons is compiled
# as stated, as keye-vl-2.0-30b-a3b's. The mean loss takes the
# trainer's own ``loss_fn`` under the default, as the step does.
AS_STATED = {"xla_allow_excess_precision": False}


def reference_check(built: dict, params, dataset: dict, sizes: dict) -> dict:
    """The system against the plain reference on the run's own weights
    and a sample of the seeded data, both computed on this device: the
    mean loss of the whole model, the head and every router token by
    token on the system's own inputs to them, the routed layers'
    per-expert row counts, and every routed layer, a kda mixer and the
    mla mixer alone, forward and backward, on the system's own inputs
    (``layer_checks``)."""
    import jax
    import jax.numpy as jnp

    sample = {
        k: v[:REFERENCE_SEQUENCES] for k, v in dataset.items()
    }
    hidden, token_losses, load = (
        jax.jit(built["head_io"])
        .lower(params, sample, jax.random.key(0))
        .compile(compiler_options=AS_STATED)
    )(params, sample, jax.random.key(0))
    step_loss = jax.jit(lambda *a: built["loss_fn"](*a)[0])(
        params, sample, jax.random.key(0)
    )
    # Once more from the hidden states alone, outside the model's
    # program: what the loss streams is what the model hands over.
    alone = jax.jit(built["head_losses"])(
        params, hidden, sample["targets"]
    )

    # Everything is an argument: data closed over would be constants of
    # the program and make its compile-cache key follow the seed.
    def compare(weights, sample, hidden, token_losses, alone, load, step_loss):
        _, head_losses = reference_head(
            hidden, weights["head"], sample["targets"]
        )
        loss, counts = reference_loss(
            weights, sample["inputs"], sample["targets"], sizes
        )
        assignments = sample["inputs"].size * sizes["num_experts_per_token"]
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
            "system_loss": step_loss,
            "as_stated_loss": token_losses.mean(),
            "reference_loss": loss,
            "head_token_loss_err": jnp.maximum(
                jnp.max(jnp.abs(token_losses - head_losses)),
                jnp.max(jnp.abs(alone - head_losses)),
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
            reference_weights(params, sizes), sample, hidden,
            token_losses, alone, small, step_loss,
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
        mixer_grad_limits=MIXER_GRAD_LIMITS,
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
            and result["shared_rows_missing"] == 0
            and all(
                result[f"{kind}_token_err"] <= token
                and result[f"{kind}_rms_err"] <= rms
                for kind, (token, rms) in LAYER_LIMITS.items()
            )
            and result["expert_grad_err"] <= EXPERT_GRAD_RTOL
            and result["router_grad_err"] <= ROUTER_GRAD_RTOL
            and result["input_grad_err"] <= INPUT_GRAD_RMS
            and all(
                result[f"{kind}_param_grad_err"] <= leaf
                and result[f"{kind}_input_grad_err"] <= rms
                for kind, (leaf, rms) in MIXER_GRAD_LIMITS.items()
            )
        ),
    )
    return result
