"""Builder's tool: what the head comparison of ``configs/gpt2-124m.py``
reads on the chip at real size, and that it has teeth there.

    chiprun -- python benchmark/tests/head_precision.py

Builds the configuration as a cell does, runs its ``reference_check``,
then reads what the head comparison would read with one piece of the
head lowered to bfloat16 at a time (the logits; the softmax and loss),
or with the tied table left in float32. Also
prints how far single token losses of the whole model lie from the
whole float32 reference: the blocks' bfloat16 noise, which is why the
head is compared on the system's hidden states and not there.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(sizes_update=None, seed: int = 11) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark import manifest

    os.environ.setdefault("ADAPTDL_NUM_REPLICAS", "1")
    cell = manifest.load_cell("gpt2-124m-steady")
    config = manifest.load_module(cell.config_py)
    sizes = dict(cell.sizes, **(sizes_update or {}))
    geometry = {"atomic_bsz": 2, "accum_steps": 0, "global_batch": 2}
    built = config.build(sizes, geometry, seed)
    params = built["trainer"]._init_params
    dataset = config.make_dataset(sizes, seed, 8)
    out = {
        "device": jax.devices()[0].device_kind,
        "check": config.reference_check(built, params, dataset, sizes),
    }
    sample = {
        k: v[: config.REFERENCE_SEQUENCES] for k, v in dataset.items()
    }
    hidden, losses = jax.jit(built["head_io"])(
        params, sample, jax.random.key(0)
    )
    embedding, targets = params["embed"]["embedding"], sample["targets"]

    def variants(hidden, losses):
        import optax

        xent = optax.softmax_cross_entropy_with_integer_labels
        logits, ref = config.reference_head(hidden, embedding, targets)
        # An explicit rounding: a cast there and back inside one
        # program is dropped by the TPU compiler.
        rounded = jax.lax.reduce_precision(logits, 8, 7)

        def err(x):
            return jnp.max(jnp.abs(x.astype(jnp.float32) - ref))

        return {
            "as_is": err(losses),
            "bf16_logits": err(xent(rounded, targets)),
            "bf16_softmax_and_loss": err(
                xent(rounded.astype(jnp.bfloat16), targets)
            ),
            "float32_table": err(xent(jnp.matmul(
                hidden.astype(jnp.float32), embedding.T,
                precision="highest",
            ), targets)),
            "max_abs_logit": jnp.max(jnp.abs(logits)),
        }

    out["head_token_loss_err"] = jax.tree.map(
        float, jax.jit(variants)(hidden, losses)
    )
    out["hidden_dtype"] = str(hidden.dtype)
    # Single token losses, whole model against whole reference.
    weights = config.reference_weights(params, sizes)

    def whole(weights):
        with jax.default_matmul_precision("highest"):
            x = config.reference_loss(
                weights, sample["inputs"], sample["targets"],
                sizes["layer_norm_epsilon"], per_token=True,
            )
        return jnp.max(jnp.abs(x - losses)), jnp.mean(jnp.abs(x - losses))

    worst, mean = jax.jit(whole)(weights)
    out["whole_model_token_loss_diff"] = {
        "max": float(worst), "mean_abs": float(mean)
    }
    print(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
