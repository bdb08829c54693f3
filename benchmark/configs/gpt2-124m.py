"""gpt2-124m: builder of the system under test, and its plain reference.

The system side goes through the program's own entry points
(``TransformerConfig`` / ``TransformerLM``, the Pallas flash kernel with
the example's block rule, ``ElasticTrainer`` with the example's recipe).
The reference side is written from the published description of GPT-2
small with the departures ``gpt2-124m.json`` lists, in plain float32
``jax.numpy``, and imports nothing from ``adaptdl_tpu``.
"""

from __future__ import annotations

import numpy as np

from benchmark import flops

# Two comparisons, because one cannot do both jobs (PERF.md, Findings
# PR 22).
#
# Whole model: |system loss - reference loss| / reference loss. The
# system computes the blocks in bfloat16 (8 mantissa bits, ~0.4% per
# rounding); the reference is float32 throughout at "highest" matmul
# precision. Averaged over 2 x 1024 tokens the bfloat16 noise of the
# blocks moved the mean loss by 0.9e-5 to 2.9e-5 relative on the chip,
# fresh and trained weights alike; 3e-4 is ten times the worst seen.
# Wrong mathematics — a missing 1/sqrt(d), an uncausal mask,
# non-rotated keys, erf instead of tanh gelu, an untied head — moves a
# ~10-nat loss by percents. It cannot tell a bfloat16 head from a
# float32 one: that rounding is unbiased and averages out of a mean,
# and token by token the blocks' own bfloat16 noise is as large.
REFERENCE_RTOL = 3e-4
# The head alone, token by token, on the hidden states the SYSTEM hands
# to it (taken from the same evaluation that gives the losses): the
# reference head multiplies the same bfloat16 operands — the hidden
# states, and the tied table rounded as the model rounds it — exactly,
# accumulates in float32 and keeps logits, softmax and loss in
# float32, which is what the configuration states. Against it the
# system's token losses may differ by accumulation order only. Logits
# rounded to bfloat16 move single token losses by 1e-2 nats, a
# bfloat16 softmax or loss by 1.2e-2 (benchmark/tests/head_precision.py
# on the chip, PERF.md Findings PR 22).
HEAD_TOKEN_LOSS_ATOL = 1e-3  # max |token loss - reference|, nats
REFERENCE_SEQUENCES = 2


def units_per_sample(sizes: dict) -> int:
    return int(sizes["n_positions"])


def train_flops_per_unit(sizes: dict) -> float:
    """Model FLOPs per trained token (benchmark/flops.py)."""
    return flops.lm_train_flops_per_token(
        n_layer=sizes["n_layer"],
        d_model=sizes["n_embd"],
        d_ff=sizes["n_inner"] or 4 * sizes["n_embd"],
        vocab_size=sizes["vocab_size"],
        seq_len=sizes["n_positions"],
    )


def make_dataset(sizes: dict, seed: int, samples: int) -> dict:
    """Packed token rows from the seed: documents of heavy-tailed
    length (lognormal, median ~400 tokens), each an arithmetic
    progression ``start + stride * position`` modulo the vocabulary
    with its own start and stride, packed back to back into rows of
    ``n_positions + 1`` tokens — learnable (the next token follows
    from the previous two) and made in bulk."""
    rng = np.random.default_rng(seed)
    vocab, row = sizes["vocab_size"], sizes["n_positions"] + 1
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


def build(sizes: dict, geometry: dict, seed: int) -> dict:
    """The system under test for one cell: model, weights made on the
    device in one jitted call from the seed, loss, trainer."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    from adaptdl_tpu.models import TransformerConfig
    from adaptdl_tpu.models.pipeline_lm import (
        dense_lm_checkpoint_transforms,
    )
    from adaptdl_tpu.models.transformer import TransformerLM
    from adaptdl_tpu.ops.flash_attention import flash_attention
    from adaptdl_tpu.scaling_rules import AdamScale
    from adaptdl_tpu.trainer import ElasticTrainer

    seq_len = sizes["n_positions"]
    block = min(128, seq_len)  # examples/transformer_lm.py's rule
    config = TransformerConfig(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["n_layer"],
        num_heads=sizes["n_head"],
        d_model=sizes["n_embd"],
        d_ff=sizes["n_inner"] or 4 * sizes["n_embd"],
        max_seq_len=seq_len,
        dtype=jnp.dtype(sizes.get("compute_dtype", "bfloat16")).type,
        remat=True,
        attention_fn=functools.partial(
            flash_attention, block_q=block, block_k=block
        ),
    )
    model = TransformerLM(config)
    # Parameter shapes do not depend on the attention function; init
    # through plain attention as init_transformer does.
    import dataclasses

    init_model = TransformerLM(
        dataclasses.replace(config, attention_fn=None)
    )
    dummy = jnp.zeros((1, seq_len), jnp.int32)
    params = jax.jit(
        lambda key: init_model.init(key, dummy, train=False)["params"]
    )(jax.random.key(seed))

    def token_losses(logits, targets):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        )

    def loss_fn(params, batch, rng):
        logits = model.apply(
            {"params": params}, batch["inputs"], train=True, rng=rng
        )
        return token_losses(logits, batch["targets"]).mean()

    def head_io(params, batch, rng):
        """What enters the system's head and what leaves it, from one
        evaluation: the final hidden states (the output of the last,
        top-level LayerNorm) and the loss of every token."""
        logits, captured = model.apply(
            {"params": params}, batch["inputs"], train=True, rng=rng,
            capture_intermediates=lambda module, _method: module.path
            == ("LayerNorm_0",),
            mutable=["intermediates"],
        )
        (hidden,) = captured["intermediates"]["LayerNorm_0"]["__call__"]
        return hidden, token_losses(logits, batch["targets"])

    recipe = sizes["recipe"]
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
        "checkpoint_transforms": dense_lm_checkpoint_transforms(
            config.num_layers
        ),
    }


# ---- the plain reference --------------------------------------------


def reference_weights(params, sizes: dict) -> dict:
    """The system's parameter tree in the reference's own layout."""
    layers = []
    for i in range(sizes["n_layer"]):
        block = params[f"layer_{i}"]
        qkv = block["attention"]["qkv"]["kernel"]  # [d, 3, heads, hd]
        layers.append(
            {
                "ln1": block["LayerNorm_0"]["scale"],
                "wq": qkv[:, 0],
                "wk": qkv[:, 1],
                "wv": qkv[:, 2],
                "wo": block["attention"]["out"]["kernel"],  # [d, d]
                "ln2": block["LayerNorm_1"]["scale"],
                "w_up": block["ff_up"]["kernel"],
                "w_down": block["ff_down"]["kernel"],
            }
        )
    return {
        "embedding": params["embed"]["embedding"],
        "layers": layers,
        "ln_f": params["LayerNorm_0"]["scale"],
    }


def reference_loss(
    weights: dict, inputs, targets, eps: float, per_token: bool = False
):
    """Mean (or, with ``per_token``, every token's) next-token
    cross-entropy of a pre-LN GPT-2 decoder: tied
    head, tanh-approximated gelu (gelu_new), scale-only LayerNorm,
    rotary positions on interleaved pairs, causal softmax attention.
    Float32, "highest" matmul precision, no kernel, no remat."""
    import jax
    import jax.numpy as jnp

    def layer_norm(x, scale):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + eps) * scale

    def rotary(x):  # [batch, seq, heads, head_dim]
        half = x.shape[-1] // 2
        inv_freq = 10000.0 ** (-jnp.arange(half) / half)
        angle = jnp.arange(x.shape[1])[:, None] * inv_freq[None, :]
        sin = jnp.sin(angle)[None, :, None, :]
        cos = jnp.cos(angle)[None, :, None, :]
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [even * cos - odd * sin, even * sin + odd * cos], axis=-1
        ).reshape(x.shape)

    def gelu_new(x):
        return 0.5 * x * (
            1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3))
        )

    with jax.default_matmul_precision("highest"):
        x = weights["embedding"][inputs].astype(jnp.float32)
        seq = inputs.shape[1]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        for layer in weights["layers"]:
            y = layer_norm(x, layer["ln1"])
            q = rotary(jnp.einsum("bsd,dhk->bshk", y, layer["wq"]))
            k = rotary(jnp.einsum("bsd,dhk->bshk", y, layer["wk"]))
            v = jnp.einsum("bsd,dhk->bshk", y, layer["wv"])
            scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(
                q.shape[-1]
            ).astype(jnp.float32)
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            attended = jnp.einsum(
                "bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v
            )
            x = x + attended.reshape(x.shape) @ layer["wo"]
            y = layer_norm(x, layer["ln2"])
            x = x + gelu_new(y @ layer["w_up"]) @ layer["w_down"]
        logits = layer_norm(x, weights["ln_f"]) @ weights["embedding"].T
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            log_probs, targets[..., None], axis=-1
        )
        return -picked[..., 0] if per_token else -picked.mean()


def reference_head(hidden, embedding, targets):
    """Tied head and next-token loss in float32 on the operands the
    system's head gets: the hidden states as handed over, the table
    rounded to their type. Returns (logits, loss of every token)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        # reduce_precision, not a cast there and back: the compiler may
        # drop such a pair (and did, in head_precision.py's variants).
        kind = jnp.finfo(hidden.dtype)
        table = jax.lax.reduce_precision(embedding, kind.nexp, kind.nmant)
        logits = hidden.astype(jnp.float32) @ table.T
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), targets[..., None], axis=-1
        )
        return logits, -picked[..., 0]


def reference_check(built: dict, params, dataset: dict, sizes: dict) -> dict:
    """The system against the plain reference on the run's own weights
    and a sample of the seeded data, both computed on this device: the
    mean loss of the whole model, and the head token by token."""
    import jax
    import jax.numpy as jnp

    sample = {
        k: v[:REFERENCE_SEQUENCES] for k, v in dataset.items()
    }
    hidden, token_losses = jax.jit(built["head_io"])(
        params, sample, jax.random.key(0)
    )

    # Everything is an argument: data closed over would be constants of
    # the program and make its compile-cache key follow the seed.
    def compare(weights, sample, hidden, token_losses):
        _, head_losses = reference_head(
            hidden, weights["embedding"], sample["targets"]
        )
        return {
            "system_loss": token_losses.mean(),
            "reference_loss": reference_loss(
                weights,
                sample["inputs"],
                sample["targets"],
                sizes["layer_norm_epsilon"],
            ),
            "head_token_loss_err": jnp.max(
                jnp.abs(token_losses - head_losses)
            ),
        }

    result = {
        k: float(v)
        for k, v in jax.jit(compare)(
            reference_weights(params, sizes), sample, hidden, token_losses
        ).items()
    }
    rel = abs(result["system_loss"] - result["reference_loss"]) / abs(
        result["reference_loss"]
    )
    result.update(
        rel_diff=rel,
        rtol=REFERENCE_RTOL,
        head_atol=HEAD_TOKEN_LOSS_ATOL,
        ok=bool(
            np.isfinite(result["system_loss"])
            and rel <= REFERENCE_RTOL
            and result["head_token_loss_err"] <= HEAD_TOKEN_LOSS_ATOL
        ),
    )
    return result
