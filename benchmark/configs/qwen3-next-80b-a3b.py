"""qwen3-next-80b-a3b: builder of the system under test, and its plain
reference.

One chip's share of Qwen3-Next-80B-A3B-Instruct under expert
parallelism over 16 chips (``qwen3-next-80b-a3b.json``: published
widths, published layers 0-3 = [gdn, gdn, gdn, full_attention], 32 of
each routed layer's 512 experts held beside the gated shared expert,
an eighth of both vocabulary tables). The system side goes through the
program's own entry points (``TransformerConfig`` / ``TransformerLM``
with ``layer_types`` "gdn" and "full_attention", ``ops/kda.py``'s
chunked delta rule with one decay a head, the flash kernels at head
256, the grouped products, ``ElasticTrainer``). The reference side is
written from the published equations (``Qwen3NextGatedDeltaNet``,
``Qwen3NextAttention``, ``Qwen3NextSparseMoeBlock``,
``Qwen3NextRMSNorm``, ``Qwen3NextRMSNormGated``) with the departures
the JSON lists, in plain float32 ``jax.numpy`` at "highest" matmul
precision, and imports nothing from ``adaptdl_tpu``: the delta rule
token by token as a ``lax.scan``, attention as a dense masked softmax
by query blocks, experts as a Python loop over the held ones, no
kernel, no remat, the same share.
"""

from __future__ import annotations

import numpy as np

from benchmark import near_ties

# What decides ``correct`` (reference_check), on the run's own weights
# at the published widths on ONE row of the timed length. Readings: my
# chip runs, PR 49, TPU v5 lite (PERF.md section 6). "first" is the
# smallest and largest the system gave in eight runs on seven seeds
# (2149000101, 2149001001 .. 2149001006; the cell's own runs print
# them: ``compared.reference``). "second" is what the reference WITH A FAULT
# gave against the reference itself on the system's own inputs,
# compiled as stated (benchmark/tests/qwen3_next_precision.py
# --controls, seeds 2149001001 and 2149001003): the router with
# bfloat16 scores, the gdn mixer with a bfloat16 state and with a
# bfloat16 running sum of the decay, the attention mixer with rotary
# over all 256 lanes and with the gate left out, the routed layer with
# the shared expert ungated. Which limit refuses which fault: bf16
# scores - both of 3, by 60 and 85 times; a bf16 decay sum - all four
# of "gdn"; rotary over every lane, the gate left out, the shared
# expert ungated - every limit of their layer, by 45 times and more. A
# bfloat16 STATE is refused by NONE: with one decay a head it reads
# UNDER the system's own error on every number (rms 0.0017 for the
# system's 0.0093, the input's gradient 0.0025 for 0.0114: the system's
# error is that of bfloat16 operands, and a decay of a whole head
# forgets a rounding faster than kimi's a channel), so no limit on an
# output or a gradient can refuse it and admit the system.
#
# 1. Whole model: |system mean loss - reference mean loss| / reference.
#    First 8.0e-6 .. 2.3e-5.
REFERENCE_RTOL = 2.5e-4
# 2. The head alone, token by token, on the hidden states the SYSTEM
#    hands to it: float32 accumulation, logits, softmax and loss.
#    First 5.7e-6 .. 8.6e-6 nats.
HEAD_TOKEN_LOSS_ATOL = 1e-3  # max |token loss - reference|, nats
# 3. Every router alone, token by token, on the inputs the SYSTEM hands
#    to it: sets of ten and their weights against the float32 "highest"
#    router on the same inputs. First 0 and 0 on every seed; second
#    (bf16 scores) 0.0599 / 0.0609 of the tokens and 4.2e-3 / 4.3e-3.
ROUTER_SET_MISMATCH_SHARE = 1e-3
ROUTER_WEIGHT_ATOL = 5e-5
# 4. Rows each held expert received against the whole reference's
#    count (first 0.0071 .. 0.0107); exactly: no row dropped, held +
#    left-out = tokens x 10, the shared expert multiplied every token.
ROUTING_L1_SHARE = 0.05
# 5. Every routed layer (with its gated shared expert), one gdn mixer
#    and the attention mixer, each ALONE, token by token, on the inputs
#    the SYSTEM hands it: ``layer_error`` = (worst token, rms over
#    tokens) of |system - reference| over the layer's rms output norm.
# 6. Backward, each alone on the first row: gradients of ``sum(y *
#    cotangent)`` (cotangent = the layer's input) with respect to every
#    parameter leaf and the input, against ``jax.grad`` of the
#    reference: |system - reference| / |reference| of a leaf (routed:
#    of each expert's slice), the worst; the input as 5's rms.
#    First (eight runs) / second readings (two seeds):
#      "gdn", second = a bf16 decay sum (a bf16 state: 0.0054, 0.0059;
#      0.0017, 0.0018; 0.0050, 0.0049; 0.0025, 0.0025 - under the first)
#        worst token   0.0268 .. 0.0334 / 0.189, 0.359
#        rms           0.00931 .. 0.00939 / 0.0156, 0.0173
#        worst leaf    0.0139 .. 0.0150 / 0.145, 0.120
#        input's rms   0.01134 .. 0.01151 / 0.0201, 0.0222
#      "attention", second = rotary over all lanes; the gate left out
#        worst token   0.0465 .. 0.0530 / 2.18, 2.15; 11.2, 11.8
#        rms           0.00459 .. 0.00474 / 0.341, 0.337; 1.00, 1.00
#        worst leaf    0.0041 .. 0.0049 / 0.734, 0.761; 1.03, 1.02
#        input's rms   0.00531 .. 0.00551 / 0.492, 0.494; 1.02, 1.00
#      "routed", second = the shared expert ungated
#        worst token   0.0154 .. 0.0195 / 2.35, 2.21
#        rms           0.00617 .. 0.00618 / 1.006, 1.071
#        input's rms   0.00535 .. 0.00537 / 1.004, 1.065
#        expert slice  0.0043 .. 0.0045, router and gate 0.0049 ..
#        0.0051: a first reading and room (no fault of theirs read)
#    Every limit lies between its two readings: 1.3 times the first
#    and three quarters of the second where the two are within a factor
#    of two (the gdn rms and input gradient), at least 1.4 times the
#    first elsewhere.
LAYER_LIMITS = {
    # kind: (worst token, rms over tokens)
    "routed": (0.04, 0.009),
    "gdn": (0.08, 0.012),
    "attention": (0.2, 0.0075),
}
EXPERT_GRAD_RTOL = 0.012  # worst expert's slice of a weight leaf
ROUTER_GRAD_RTOL = 0.02  # the router leaf, and the shared expert's gate
INPUT_GRAD_RMS = 0.0075  # a routed layer's input gradient
MIXER_GRAD_LIMITS = {
    # kind: (worst parameter leaf, the input's rms)
    "gdn": (0.04, 0.015),
    "attention": (0.012, 0.0085),
}
REFERENCE_SEQUENCES = 1
ATTENTION_QUERY_BLOCK = 128
SCAN_BLOCK = 128  # tokens of one checkpointed block of the recurrence
HEAD_GROUP = 4  # value heads of the recurrence the reference runs at once
GDN_L2_EPS = 1e-6
# (The two checked mixers' kinds, "gdn" and "attention", are also the
# system's module names.)
BLOCK_NORMS = ("ZeroCentredRMSNorm_0", "ZeroCentredRMSNorm_1")


def units_per_sample(sizes: dict) -> int:
    return int(sizes["sequence_length"])


def layer_kinds(sizes: dict) -> list[str]:
    """The kept layers' mixer kinds in order, from the published
    ``full_attention_interval`` (layer i is full attention where
    ``(i + 1) % interval == 0``); the file's ``layer_types`` says the
    same."""
    interval = sizes["full_attention_interval"]
    kinds = [
        "full_attention" if (i + 1) % interval == 0 else "gdn"
        for i in range(sizes["num_hidden_layers"])
    ]
    assert kinds == list(sizes["layer_types"]), (kinds, sizes["layer_types"])
    return kinds


def rotary_dims(sizes: dict) -> int:
    return int(sizes["partial_rotary_factor"] * sizes["head_dim"])


def forward_flops_per_token(sizes: dict) -> dict[str, float]:
    """Forward matmul FLOPs per token, by part: 2 FLOPs per
    multiply-accumulate, the causal half of attention at the timed
    length, the delta rule as its chunked form multiplies it
    (``benchmark/kda.py``), routed experts at UNIFORM routing, no
    recomputation — counted as ``benchmark/flops.py`` counts."""
    from benchmark import kda as kda_count

    d = sizes["hidden_size"]
    k_heads, v_heads = (
        sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    )
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    k_width, v_width = k_heads * dk, v_heads * dv
    kinds = layer_kinds(sizes)
    n_gdn, n_attn = kinds.count("gdn"), kinds.count("full_attention")
    heads, kv_heads, hd = (
        sizes["num_attention_heads"], sizes["num_key_value_heads"],
        sizes["head_dim"],
    )
    routed = sizes["num_hidden_layers"]
    per_token_experts = (
        sizes["num_experts_per_tok"] * sizes["experts_held"]
        / sizes["router_width"]
    )
    expert = 2 * 3 * d * sizes["moe_intermediate_size"]
    return {
        "gdn_projections": float(
            n_gdn * 2 * (
                d * (2 * k_width + 2 * v_width) + d * 2 * v_heads
                + v_width * d
            )
        ),
        "gdn_mixing": float(
            n_gdn * kda_count.forward_flops_per_token(
                v_heads, dk, dv, sizes["kda_chunk"]
            )
        ),
        "attention_projections": float(
            n_attn * 2 * (
                d * heads * 2 * hd + d * 2 * kv_heads * hd + heads * hd * d
            )
        ),
        "attention": float(
            n_attn * 2 * sizes["sequence_length"] * heads * 2 * hd * 0.5
        ),
        "router": float(routed * 2 * d * sizes["router_width"]),
        "shared_expert": float(
            routed * (
                2 * 3 * d * sizes["shared_expert_intermediate_size"] + 2 * d
            )
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
    import dataclasses

    import jax.numpy as jnp

    from adaptdl_tpu.models import TransformerConfig

    needed = {"linear_value_heads", "attention_gate", "norm_zero_centred",
              "rotary_dims", "shared_expert_gate"}
    missing = needed - {f.name for f in dataclasses.fields(TransformerConfig)}
    if missing:
        # A program from before the configuration: said at once.
        raise NotImplementedError(
            "this adaptdl_tpu cannot build qwen3-next-80b-a3b: "
            f"TransformerConfig lacks {sorted(missing)}"
        )
    from adaptdl_tpu.ops import kda as kda_op

    # What the file says of the system's chunk (the counts of
    # benchmark/kda.py take it from there) is the program's constant.
    assert sizes["kda_chunk"] == kda_op.CHUNK
    linear = sizes["linear_attn_config"]
    assert linear["num_heads"] == sizes["linear_num_value_heads"]
    assert linear["head_dim"] == sizes["linear_key_head_dim"]
    assert linear["head_dim"] == sizes["linear_value_head_dim"]
    assert sizes["norm_topk_prob"] and not sizes["mlp_only_layers"]
    assert sizes["decoder_sparse_step"] == 1
    return TransformerConfig(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        d_model=sizes["hidden_size"],
        d_ff=sizes["intermediate_size"],  # used by no layer: all routed
        max_seq_len=sizes["sequence_length"],
        dtype=jnp.dtype(sizes.get("compute_dtype", "bfloat16")).type,
        remat=True,
        attention_fn=attention_fn,
        norm="rmsnorm",
        norm_eps=sizes["rms_norm_eps"],
        norm_zero_centred=True,
        ffn="swiglu",
        qk_norm=True,
        rope_theta=float(sizes["rope_theta"]),
        rotary_dims=rotary_dims(sizes),
        attention_gate=True,
        head_dim=sizes["head_dim"],
        layer_types=tuple(layer_kinds(sizes)),
        conv_kernel=sizes["linear_conv_kernel_dim"],
        linear_key_heads=sizes["linear_num_key_heads"],
        linear_value_heads=sizes["linear_num_value_heads"],
        linear_key_head_dim=sizes["linear_key_head_dim"],
        linear_value_head_dim=sizes["linear_value_head_dim"],
        experts_total=sizes["router_width"],
        experts_held=sizes["experts_held"],
        first_expert=sizes["first_expert"],
        experts_top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["moe_intermediate_size"],
        d_shared_expert=sizes["shared_expert_intermediate_size"],
        shared_expert_gate=True,
        num_dense_layers=0,
        expert_weight_eps=sizes["expert_weight_eps"],
        routed_scaling_factor=1.0,
        experts_router="softmax",
        tie_embeddings=sizes["tie_word_embeddings"],
    )


def checked_mixers(sizes: dict) -> dict[str, int]:
    """kind -> the layer whose mixer is checked alone: the LAST gdn
    layer and the attention layer."""
    kinds = layer_kinds(sizes)
    return {
        "gdn": len(kinds) - 1 - kinds[::-1].index("gdn"),
        "attention": kinds.index("full_attention"),
    }


def build(sizes: dict, geometry: dict, seed: int) -> dict:
    """The system under test for one cell: model, weights made on the
    device in one jitted call from the seed, loss, trainer."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    model_config(sizes)  # a program without the fields says so here
    from adaptdl_tpu.models.transformer import (
        GatedDeltaNet,
        GroupedQueryAttention,
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
        variance (as keye-vl-2.0-30b-a3b and kimi-linear-48b-a3b)."""
        params = init_model.init(key, dummy, train=False)["params"]
        table = params["embed"]["embedding"]
        params["embed"]["embedding"] = table * table.shape[1] ** 0.5
        return params

    params = jax.jit(lambda key: fresh(key))(jax.random.key(seed))

    routed = range(sizes["num_hidden_layers"])
    mixers = checked_mixers(sizes)
    captured_paths = (
        {(BLOCK_NORMS[0],)}
        | {
            (f"layer_{i}", name)
            for i in routed
            for name in (BLOCK_NORMS[1], "moe")
        }
        | {
            (f"layer_{i}", name)
            for kind, i in mixers.items()
            for name in (BLOCK_NORMS[0], kind)
        }
    )

    def head_io(params, batch, rng):
        """From ONE evaluation of the whole model, as it runs: the
        final hidden states and every token's loss; of every routed
        layer its input, its output (shared expert included), the
        router's choice and the load counters; of one gdn mixer and
        the attention mixer their input and output."""
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
        for name, module in (("inputs", BLOCK_NORMS[1]), ("outputs", "moe")):
            load[name] = [
                seen(i, module).reshape(-1, sizes["hidden_size"])
                for i in routed
            ]
        for kind, i in mixers.items():
            load[kind] = (
                seen(i, BLOCK_NORMS[0]), seen(i, kind)
            )
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
        """The system's gdn or attention mixer alone on ``x`` [1, seq,
        d]: the gradients of ``sum(y * cotangent)`` with respect to
        (its parameters, x)."""
        module = {
            "gdn": GatedDeltaNet, "attention": GroupedQueryAttention
        }[kind](cfg)
        positions = jnp.arange(x.shape[1])

        def objective(mixer_params, x):
            y = module.apply({"params": mixer_params}, x, positions)
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
            "norm_op": block[BLOCK_NORMS[0]]["scale"],
            "norm_ffn": block[BLOCK_NORMS[1]]["scale"],
        }
        if kind == "gdn":
            layer["gdn"] = gdn_weights(block["gdn"])
        else:
            layer["attention"] = attention_weights(block["attention"])
        layer.update(routed_weights(block["moe"]))
        layers.append(layer)
    return {
        "embedding": params["embed"]["embedding"],
        "head": params["lm_head"],  # [vocab, d]
        "layers": layers,
        "norm_out": params[BLOCK_NORMS[0]]["scale"],
    }


# A system mixer's parameter leaves under the reference's names.
GDN_LEAVES = {
    ("in_proj", "kernel"): "w_in", ("conv",): "taps", ("ba",): "w_ba",
    ("A_log",): "A_log", ("dt_bias",): "dt_bias",
    ("o_norm", "scale"): "o_norm", ("out", "kernel"): "w_out",
}
ATTENTION_LEAVES = {
    ("q", "kernel"): "wq", ("kv", "kernel"): "wkv",
    ("q_norm", "scale"): "q_norm", ("k_norm", "scale"): "k_norm",
    ("out", "kernel"): "w_out",
}
MIXER_LEAVES = {"gdn": GDN_LEAVES, "attention": ATTENTION_LEAVES}


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def gdn_weights(mixer) -> dict:
    # w_in [d, q | k | v | z]; taps [taps, q | k | v], the last is t's;
    # w_ba [d, 2 (b, a), value heads].
    return {name: _leaf(mixer, path) for path, name in GDN_LEAVES.items()}


def attention_weights(mixer) -> dict:
    # wq [d, heads, q | gate]; wkv [d, 2 (k, v), kv heads, hd];
    # w_out [heads * hd, d].
    return {
        name: _leaf(mixer, path) for path, name in ATTENTION_LEAVES.items()
    }


ROUTED_LEAVES = {  # the reference's names -> the system's leaves
    "w1": ("w_gate",), "w3": ("w_up",), "w2": ("w_down",),
    "router": ("router",), "s1": ("shared", "ff_gate", "kernel"),
    "s3": ("shared", "ff_up", "kernel"),
    "s2": ("shared", "ff_down", "kernel"),
    "sg": ("shared_gate", "kernel"),
}


def routed_weights(moe) -> dict:
    # router [d, router_width]; w1, w3 [held, d, f]; w2 [held, f, d];
    # sg [d, 1].
    return {name: _leaf(moe, path) for name, path in ROUTED_LEAVES.items()}


# What the comparisons can tell apart is MEASURED: the reference
# functions take a ``variant`` that computes with a fault (never used
# by ``reference_check``; benchmark/tests/qwen3_next_precision.py reads
# each against the right one, the tests hold that each differs).
ROUTER_FAULTS = ("bf16_scores",)
ROUTED_FAULTS = ("shared_ungated",)
GDN_FAULTS = (
    "bf16_state",  # the state rounded to bfloat16 after every token
    "bf16_decay",  # the log-decay's running sum of a chunk in bfloat16
)
ATTENTION_FAULTS = (
    "rotary_all",  # rotary over all 256 lanes
    "no_gate",  # the output gate left out
)


def _rms_norm(x, weight, eps: float):
    """The zero-centred norm: ``x * rsqrt(mean(x^2) + eps) * (1 +
    w)``."""
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (
        1.0 + weight
    )


def _gated(x, w1, w3, w2):
    import jax

    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def reference_gdn(layer: dict, u, sizes: dict, variant: str = ""):
    """The gated delta net mixer on ``u`` [batch, seq, d], token by
    token. ``HEAD_GROUP`` value heads (and the key heads that serve
    them) at a time, one group after another (a ``lax.map`` whose body
    is checkpointed, so that a gradient holds a group's float32 arrays
    and not the layer's); inside a group a ``lax.scan`` over blocks of
    ``SCAN_BLOCK`` tokens, each block a checkpointed scan over its
    tokens. ``variant``: one of ``GDN_FAULTS``."""
    import jax
    import jax.numpy as jnp

    k_heads, v_heads = (
        sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    )
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    k_width, v_width = k_heads * dk, v_heads * dv
    serves = v_heads // k_heads
    batch, seq, _ = u.shape
    held = min(HEAD_GROUP, v_heads)
    held = max(held // serves, 1) * serves  # whole key heads
    groups = v_heads // held
    assert groups * held == v_heads
    block = min(SCAN_BLOCK, seq)
    assert seq % block == 0

    def grouped(x, axis, heads, width):
        """An axis of ``heads * width`` channels, by group: [groups,
        .., heads / groups, width, ..]."""
        shape = (
            x.shape[:axis] + (groups, heads // groups, width)
            + x.shape[axis + 1:]
        )
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    def token(state, at):  # state [b, held, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None, None]
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
            jnp.sum(x * x, -1, keepdims=True) + GDN_L2_EPS
        )

    def conv(z, w):  # z [b, s, h, e]; w [taps, h, e], the last is t's
        n = w.shape[0]
        mixed = jnp.zeros_like(z)
        for j in range(n):
            back = n - 1 - j
            shifted = jnp.concatenate(
                [jnp.zeros_like(z[:, :back]), z[:, : seq - back]], axis=1
            )
            mixed = mixed + w[j] * shifted
        return jax.nn.silu(mixed)

    @jax.checkpoint
    def some_heads(operands):
        (w_q, w_k, w_v, t_q, t_k, t_v, w_b, w_a, a_log, dt_bias) = operands
        # w_q, w_k [d, key heads of the group, dk]; w_v [d, held, dv].
        q = conv(jnp.einsum("bsd,dhe->bshe", u, w_q), t_q)
        k = conv(jnp.einsum("bsd,dhe->bshe", u, w_k), t_k)
        v = conv(jnp.einsum("bsd,dhe->bshe", u, w_v), t_v)
        q, k = unit(q) * dk**-0.5, unit(k)
        # Key head j serves the value heads j * serves ..
        q, k = (jnp.repeat(x, serves, axis=2) for x in (q, k))
        g = -jnp.exp(a_log) * jax.nn.softplus(u @ w_a + dt_bias)  # [b, s, h]
        if variant == "bf16_decay":
            # The running sum of a chunk of 64 held in bfloat16: every
            # partial sum rounded, then differenced back into steps.
            chunk = sizes["kda_chunk"]
            sums = g.reshape(batch, seq // chunk, chunk, held)

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
            ).reshape(batch, seq, held)
        beta = jax.nn.sigmoid(u @ w_b)  # [b, s, held]
        _, out = jax.lax.scan(
            tokens_of_block,
            jnp.zeros((batch, held, dk, dv), jnp.float32),
            tuple(by_block(x) for x in (q, k, v, g, beta)),
        )
        return jnp.moveaxis(out.reshape((seq,) + out.shape[2:]), 0, 1)

    w_in, taps = layer["w_in"], layer["taps"]
    parts = (
        (0, k_heads, dk), (k_width, k_heads, dk), (2 * k_width, v_heads, dv)
    )
    out = jax.lax.map(
        some_heads,
        tuple(
            grouped(w_in[:, at:at + heads * width], 1, heads, width)
            for at, heads, width in parts
        ) + tuple(
            grouped(taps[:, at:at + heads * width], 1, heads, width)
            for at, heads, width in parts
        ) + (
            grouped(layer["w_ba"][:, 0], 1, v_heads, 1)[..., 0],
            grouped(layer["w_ba"][:, 1], 1, v_heads, 1)[..., 0],
            layer["A_log"].reshape(groups, held),
            layer["dt_bias"].reshape(groups, held),
        ),
    )  # [groups, b, s, held, dv]
    out = jnp.moveaxis(out, 0, 2).reshape(batch, seq, v_heads, dv)
    # Qwen3NextRMSNormGated: a PLAIN scale, then silu(z).
    out = out * jax.lax.rsqrt(
        (out * out).mean(-1, keepdims=True) + sizes["rms_norm_eps"]
    ) * layer["o_norm"]
    z = (u @ w_in[:, 2 * k_width + v_width:]).reshape(out.shape)
    return (out * jax.nn.silu(z)).reshape(batch, seq, -1) @ layer["w_out"]


def _rotary(x, theta: float, lanes: int):
    """Adjacent pairs ``(x[2i], x[2i + 1])`` of the first ``lanes``
    lanes of ``x`` [b, s, h, d] turned by ``position * theta ** (-2i /
    lanes)``; the other lanes untouched."""
    import jax.numpy as jnp

    seq = x.shape[1]
    turned, rest = x[..., :lanes], x[..., lanes:]
    pairs = turned.reshape(turned.shape[:-1] + (lanes // 2, 2))
    freqs = theta ** (-jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    even, odd = pairs[..., 0], pairs[..., 1]
    pairs = jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    )
    return jnp.concatenate([pairs.reshape(turned.shape), rest], axis=-1)


def reference_attention(layer: dict, u, sizes: dict, variant: str = ""):
    """Gated softmax attention on ``u`` [batch, seq, d]: 16 query heads
    of 256 on 2 key/value heads, zero-centred head norms on q and k,
    rotary over the first 64 lanes, a dense masked softmax one block
    of ``ATTENTION_QUERY_BLOCK`` queries after another (a ``lax.map``
    whose body is checkpointed: a gradient holds one block's scores),
    the output times ``sigmoid(gate)``. ``variant``: one of
    ``ATTENTION_FAULTS``."""
    import jax
    import jax.numpy as jnp

    hd, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    theta = float(sizes["rope_theta"])
    lanes = hd if variant == "rotary_all" else rotary_dims(sizes)
    q_gate = jnp.einsum("bsd,dhk->bshk", u, layer["wq"])
    q, gate = q_gate[..., :hd], q_gate[..., hd:]
    kv = jnp.einsum("bsd,dghk->bsghk", u, layer["wkv"])
    k, v = kv[:, :, 0], kv[:, :, 1]  # [b, s, kv heads, hd]
    q = _rotary(_rms_norm(q, layer["q_norm"], eps), theta, lanes)
    k = _rotary(_rms_norm(k, layer["k_norm"], eps), theta, lanes)
    batch, seq, heads, _ = q.shape
    serves = heads // k.shape[2]
    k, v = (jnp.repeat(x, serves, axis=2) for x in (k, v))
    block = min(ATTENTION_QUERY_BLOCK, seq)
    assert seq % block == 0
    key_at = jnp.arange(seq)

    @jax.checkpoint
    def attend(operands):
        q_block, start = operands
        scores = jnp.einsum("bqhk,bshk->bhqs", q_block, k) * hd**-0.5
        visible = key_at[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum(
            "bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v
        )

    out = jax.lax.map(
        attend,
        (
            jnp.moveaxis(
                q.reshape(batch, seq // block, block, heads, hd), 1, 0
            ),
            jnp.arange(0, seq, block),
        ),
    )  # [blocks, b, block, heads, hd]
    out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, hd)
    if variant != "no_gate":
        out = out * jax.nn.sigmoid(gate)
    return out.reshape(batch, seq, -1) @ layer["w_out"]


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
    """The published router alone on ``x`` [..., d]: float32 softmax
    over all experts, the top 10, weights = the chosen probabilities
    over their sum (``norm_topk_prob``; + epsilon). Returns (experts
    [..., top_k] in ascending order, their weights in that order). With
    ``system``, the sets the system chose: a near-tied token's experts
    are the system's (``near_ties.settle``), and a third result, the
    ``Ties``."""
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
    shared: bool = True, variant: str = "", system=None,
):
    """The published routed FFN, this share of it: the router over all
    experts, the sum over the experts chosen AND held (``first_expert
    ..`` + the number of expert weights the layer has) of weight x
    gated FFN, and (``shared``) the shared expert on every token times
    ``sigmoid(x w_s)``. Returns (y, rows each of ALL experts was chosen
    for), and with ``system`` the router's ``Ties``. ``variant``: of
    ``ROUTER_FAULTS`` or ``ROUTED_FAULTS``."""
    import jax
    import jax.numpy as jnp

    first = sizes["first_expert"] if first_expert is None else first_expert
    total = sizes["router_width"]
    chosen, weights, *ties = reference_router(
        layer, x, sizes, variant if variant in ROUTER_FAULTS else "",
        system,
    )
    # (Checkpointed: a gradient holds one expert's float32
    # intermediates at a time, not those of all 32.)
    weighted = jax.checkpoint(
        lambda x, weight, w1, w3, w2: weight * _gated(x, w1, w3, w2)
    )
    y = jnp.zeros_like(x)
    for held in range(layer["w1"].shape[0]):
        mask = chosen == first + held  # [..., top_k]
        weight = jnp.where(mask, weights, 0.0).sum(-1, keepdims=True)
        y = y + weighted(
            x, weight, layer["w1"][held], layer["w3"][held],
            layer["w2"][held],
        )
    if shared:
        out = _gated(x, layer["s1"], layer["s3"], layer["s2"])
        if variant != "shared_ungated":
            out = out * jax.nn.sigmoid(x @ layer["sg"])
        y = y + out
    counts = jnp.sum(
        chosen[..., None] == jnp.arange(total),
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


def reference_mixer(kind: str, layer: dict, u, sizes: dict, variant=""):
    """The reference's gdn or attention mixer on the system's ``u``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        u = u.astype(jnp.float32)
        if kind == "gdn":
            return reference_gdn(layer, u, sizes, variant)
        return reference_attention(layer, u, sizes, variant)


def reference_mixer_vjp(kind: str, layer: dict, u, cotangent, sizes: dict):
    """Gradients of ``sum(y * cotangent)`` of a mixer with respect to
    (its weights, u), by ``jax.grad``."""
    import jax
    import jax.numpy as jnp

    def objective(layer, u):
        return jnp.sum(reference_mixer(kind, layer, u, sizes) * cotangent)

    return jax.grad(objective, argnums=(0, 1))(layer, u.astype(jnp.float32))


def reference_hidden(weights: dict, inputs, sizes: dict, variant: str = ""):
    """The final normed hidden states and the routed layers' expert
    counts ``[layers, router_width]``."""
    import jax.numpy as jnp

    eps = sizes["rms_norm_eps"]
    x = weights["embedding"][inputs].astype(jnp.float32)
    counts = []
    for layer in weights["layers"]:
        u = _rms_norm(x, layer["norm_op"], eps)
        if "gdn" in layer:
            x = x + reference_gdn(
                layer["gdn"], u, sizes,
                variant if variant in GDN_FAULTS else "",
            )
        else:
            x = x + reference_attention(
                layer["attention"], u, sizes,
                variant if variant in ATTENTION_FAULTS else "",
            )
        u = _rms_norm(x, layer["norm_ffn"], eps)
        y, chosen = reference_routed_ffn(
            layer, u, sizes,
            variant=variant if variant in ROUTER_FAULTS + ROUTED_FAULTS
            else "",
        )
        counts.append(chosen)
        x = x + y
    return _rms_norm(x, weights["norm_out"], eps), jnp.stack(counts)


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

    with jax.default_matmul_precision("highest"):
        hidden, counts = reference_hidden(weights, inputs, sizes, variant)
        logits = hidden @ weights["head"].T
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), targets[..., None], axis=-1
        )
        loss = -picked[..., 0] if per_token else -picked.mean()
        return loss, counts


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


def routed_grad_errors(got, want) -> dict:
    """The system's (parameter gradients, input gradient) of a routed
    layer against the reference's: worst expert's slice of a held
    expert's leaf (a shared expert's leaf as one slice), the router
    leaf and the shared expert's gate, the input."""
    import jax.numpy as jnp

    (got_w, got_x), (want_w, want_x) = got, want

    def err(name):
        g, w = _leaf(got_w, ROUTED_LEAVES[name]), want_w[name]
        return slice_error(g, w) if name[0] == "w" else slice_error(
            g[None], w[None]
        )

    gates = ("router", "sg")
    return {
        "expert_grad_err": jnp.max(
            jnp.stack([err(n) for n in ROUTED_LEAVES if n not in gates])
        ),
        "router_grad_err": jnp.maximum(err("router"), err("sg")),
        "input_grad_err": layer_error(got_x, want_x)[1],
    }


def mixer_grad_errors(kind: str, got, want) -> dict:
    """A mixer's (parameter gradients, input gradient) against the
    reference's: the worst leaf's |got - want| / |want|, the input as
    ``layer_error``'s rms."""
    import jax.numpy as jnp

    (got_w, got_x), (want_w, want_x) = got, want
    return {
        f"{kind}_param_grad_err": jnp.max(
            jnp.stack(
                [
                    slice_error(_leaf(got_w, path)[None], want_w[name][None])
                    for path, name in MIXER_LEAVES[kind].items()
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
    """Comparisons 5 and 6: every routed layer, one gdn mixer and the
    attention mixer, forward and backward, each alone on the system's
    own inputs. One program a layer kind, so that no two layers'
    float32 intermediates are alive together."""
    import jax

    weights = reference_weights(params, sizes)["layers"]
    routed = jax.jit(routed_check(built, sizes))
    found = [
        routed(
            weights[at], params[f"layer_{at}"]["moe"],
            load["inputs"][at], load["outputs"][at], load["experts"][at],
        )
        for at in range(sizes["num_hidden_layers"])
    ]
    worst = near_ties.worst_layer(found)
    for kind, at in checked_mixers(sizes).items():
        u, y = load[kind]
        errors = mixer_check(built, sizes, kind)(
            weights[at][kind], params[f"layer_{at}"][kind],
            u[:1], y[:1],
        )
        worst.update({k: float(v) for k, v in errors.items()})
    return worst


# The TPU compiler's default (``xla_allow_excess_precision``) keeps a
# value in float32 where the program rounds it to bfloat16 on the way
# to the next operation. More precision than stated is no fault, but a
# comparison layer by layer needs what a layer CONSUMED to be what the
# capture shows: the model's program of the comparisons is compiled as
# stated, as keye-vl-2.0-30b-a3b's and kimi-linear-48b-a3b's. The mean
# loss takes the trainer's own ``loss_fn`` under the default, as the
# step does.
AS_STATED = {"xla_allow_excess_precision": False}


def reference_check(built: dict, params, dataset: dict, sizes: dict) -> dict:
    """The system against the plain reference on the run's own weights
    and a sample of the seeded data, both computed on this device: the
    mean loss of the whole model, the head and every router token by
    token on the system's own inputs to them, the routed layers'
    per-expert row counts, and every routed layer, a gdn mixer and the
    attention mixer alone, forward and backward, on the system's own
    inputs (``layer_checks``)."""
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
        assignments = sample["inputs"].size * sizes["num_experts_per_tok"]
        set_mismatch, weight_err = zip(
            *(
                router_disagreement(
                    in_expert_order(
                        load["experts"][i], load["weights"][i]
                    ),
                    reference_router(layer, load["inputs"][i], sizes),
                )
                for i, layer in enumerate(weights["layers"])
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
