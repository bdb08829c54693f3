"""Builder's tool, on the chip: the two readings behind each limit of
glm-4.7-flash's reference comparison (``configs/glm-4.7-flash.py``).

For each seed, at the published widths on one row of the timed length:
the SYSTEM against the float32 reference (first reading: what the
limits must admit — ``reference_check`` itself, as the cell runs it),
and the reference computed WITH A FAULT against itself (second
readings: what at least one limit must refuse), each on the layer's
own input as the system saw it: the latent-attention mixer with its
rotary's angles in bfloat16, without the rotary, with bfloat16 logits
and with a bfloat16 softmax statistic; a routed layer without its
scale; every router with bfloat16 scores; the prediction module alone
without its block's rotary, with its angles in bfloat16, without its
routed part's scale, and with a table's gradient cut off; the head with its logits and
softmax in bfloat16; the whole model with the module's loss left out,
and with the module's use of the embedding table, or of the output
table, sending no gradient back; the flash kernels alone on bfloat16
operands with bfloat16 logits and with a bfloat16 statistic (what
comparison 8 refuses where the mixer's own bfloat16 rounding hides
them). ``--first`` prints the first readings only, ``--controls`` the
second ones only (the cell's own runs print the first: ``compared.
reference``). The controls are compiled AS STATED
(``xla_allow_excess_precision`` false): under the TPU compiler's
default a rounding to bfloat16 and back is taken out of the program,
and a control reads 0.0 (PR 46). One seed a process on the chip.

    chiprun -- python benchmark/tests/glm_precision.py \
        [--first | --controls] [--tiny] seed ...

``--tiny``: a CPU rehearsal at a small size in bfloat16.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

TINY = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 24, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 32,
    "router_width": 16, "experts_held": 4, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "vocab_size": 512, "sequence_length": 256,
    "head_chunk_rows": 128,
}
MIXER_FAULTS = ("bf16_angles", "no_rotary", "bf16_logits", "bf16_stat")
MODULE_FAULTS = (
    "no_rotary", "bf16_angles", "no_scale", "embedding_one_use",
    "head_one_use",
)


def controls(config, built, params, sample, sizes, seed):
    """Yields one record a fault: its readings, and which limits
    refuse it."""
    import jax
    import jax.numpy as jnp

    as_stated = {"compiler_options": config.AS_STATED}
    limits = {
        "mla_token_err": config.LAYER_LIMITS["mla"][0],
        "mla_rms_err": config.LAYER_LIMITS["mla"][1],
        "mla_param_grad_err": config.MIXER_GRAD_LIMITS[0],
        "mla_input_grad_err": config.MIXER_GRAD_LIMITS[1],
        "routed_token_err": config.LAYER_LIMITS["routed"][0],
        "routed_rms_err": config.LAYER_LIMITS["routed"][1],
        "input_grad_err": config.INPUT_GRAD_RMS,
        "router_set_mismatch_share": config.ROUTER_SET_MISMATCH_SHARE,
        "router_weight_err": config.ROUTER_WEIGHT_ATOL,
        "head_token_loss_err": config.HEAD_TOKEN_LOSS_ATOL,
        "rel_diff": config.REFERENCE_RTOL,
        "mtp_rel_diff": config.REFERENCE_RTOL,
        "embedding_table_grad_err": config.TABLE_GRAD_RTOL["embedding"],
        "head_table_grad_err": config.TABLE_GRAD_RTOL["head"],
        "kernel_out_rms_err": config.KERNEL_RMS_LIMIT,
        "kernel_grad_rms_err": config.KERNEL_RMS_LIMIT,
        "kernel_row_scale_err": config.KERNEL_ROW_SCALE_LIMIT,
        "mtp_token_err": config.LAYER_LIMITS["mtp"][0],
        "mtp_rms_err": config.LAYER_LIMITS["mtp"][1],
        "mtp_alone_loss_rel": config.REFERENCE_RTOL,
        **{
            f"mtp_{name}_grad_err": limit
            for name, limit in config.MTP_GRAD_LIMITS.items()
        },
    }

    def refused(found):
        found = {
            k: float(v) for k, v in found.items() if "." not in k
        }  # (without the module's leaf-by-leaf list)
        found["refused_by"] = sorted(
            k for k, v in found.items() if v > limits[k]
        )
        return found

    hidden, _, load = jax.jit(built["head_io"])(
        params, sample, jax.random.key(0)
    )
    weights = config.reference_weights(params, sizes)

    def mixer_control(layer, u, variant):
        u32 = u.astype(jnp.float32)

        def vjp(variant):
            def objective(layer, u):
                return jnp.sum(
                    config.reference_mixer(layer, u, sizes, variant) * u32
                )

            return jax.grad(objective, argnums=(0, 1))(layer, u32)

        wrong = config.reference_mixer(layer, u, sizes, variant)
        right = config.reference_mixer(layer, u, sizes)
        token, rms = config.layer_error(wrong, right)
        (wrong_w, wrong_x), (right_w, right_x) = vjp(variant), vjp("")
        return {
            "mla_token_err": token, "mla_rms_err": rms,
            "mla_param_grad_err": jnp.max(jnp.stack([
                config.whole_error(wrong_w[k], right_w[k]) for k in right_w
            ])),
            "mla_input_grad_err": config.layer_error(wrong_x, right_x)[1],
        }

    at = config.checked_mixer(sizes)
    for variant in MIXER_FAULTS:
        yield {"mixer": "mla", "variant": variant, **refused(
            jax.jit(mixer_control, static_argnums=2, **as_stated)(
                weights["layers"][at]["mla"], load["mla"][0][:1], variant
            )
        )}

    def routed_control(layer, x, variant):
        x = x.astype(jnp.float32)

        def out(variant, x):
            with jax.default_matmul_precision("highest"):
                return config.reference_routed_ffn(
                    layer, x, sizes, variant=variant
                )[0]

        def d_x(variant):
            return jax.grad(lambda x: jnp.sum(out(variant, x) * x))(x)

        token, rms = config.layer_error(out(variant, x), out("", x))
        return {
            "routed_token_err": token, "routed_rms_err": rms,
            "input_grad_err": config.layer_error(d_x(variant), d_x(""))[1],
        }

    routed = [
        {k: layer[k] for k in (*config.ROUTED_LEAVES, "bias")}
        for layer in weights["layers"] + [weights["mtp"]["block"]]
        if "router" in layer
    ]
    for variant in config.ROUTED_FAULTS:
        yield {"variant": variant, **refused(
            jax.jit(routed_control, static_argnums=2, **as_stated)(
                routed[-1], load["inputs"][-1], variant
            )
        )}

    def router_control(layers, inputs):
        found = [
            config.router_disagreement(
                config.reference_router(layer, x, sizes, "bf16_scores"),
                config.reference_router(layer, x, sizes),
            )
            for layer, x in zip(layers, inputs)
        ]
        return {
            "router_set_mismatch_share": jnp.max(
                jnp.stack([f[0] for f in found])
            ),
            "router_weight_err": jnp.max(jnp.stack([f[1] for f in found])),
        }

    yield {"variant": "bf16_scores", "routers": len(routed), **refused(
        jax.jit(router_control, **as_stated)(routed, load["inputs"])
    )}

    def head_control(hidden, table, targets):
        return {"head_token_loss_err": jnp.max(jnp.abs(
            config.reference_head(hidden, table, targets, "bf16_loss")
            - config.reference_head(hidden, table, targets)
        ))}

    yield {"variant": "bf16_loss", **refused(
        jax.jit(head_control, **as_stated)(
            hidden[0], weights["head"], sample["targets"]
        )
    )}

    # The module alone with a fault of its block, or with a table's
    # use cut off, in the system's place.
    module = config.mtp_check(built, sizes)
    for variant in MODULE_FAULTS:
        yield {"module": "mtp", "variant": variant, **refused(module(
            weights, params, load["trunk"][:1], sample["targets"][:1],
            variant=variant,
        ))}

    def whole(variant):
        return jax.jit(
            lambda w, s: config.reference_table_grads(
                w, s["inputs"], s["targets"], sizes, variant
            )
        )(weights, sample)

    loss, parts, tables = whole("")
    for variant in config.LOSS_FAULTS + config.TABLE_FAULTS:
        wrong_loss, wrong_parts, wrong_tables = whole(variant)
        yield {"variant": variant, **refused({
            "rel_diff": abs(wrong_loss - loss) / abs(loss),
            "embedding_table_grad_err": config.whole_error(
                wrong_tables["embedding"], tables["embedding"]
            ),
            "head_table_grad_err": config.whole_error(
                wrong_tables["head"], tables["head"]
            ),
        })}
    for variant in config.KERNEL_FAULTS:
        yield {"kernel": "flash", "variant": variant, **refused(
            config.kernel_check(built, sizes, seed, variant)
        )}


def main(argv: list[str]) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import manifest

    first_only, tiny = "--first" in argv, "--tiny" in argv
    controls_only = "--controls" in argv
    seeds = [int(a) for a in argv if not a.startswith("--")]
    os.environ.setdefault("ADAPTDL_NUM_REPLICAS", "1")
    cell = manifest.load_cell("glm-4.7-flash-steady")
    config = manifest.load_module(cell.config_py)
    sizes, geometry = cell.sizes, cell.workload["geometry"]
    if tiny:
        sizes.update(TINY)
    print(f"device {jax.devices()[0].device_kind}", flush=True)
    for seed in seeds:
        built = config.build(sizes, geometry, seed)
        if controls_only:
            # The parameters alone, without the optimizer's state: a
            # control's program holds two references' gradients.
            state, params = None, built["trainer"]._init_params
        else:
            state = built["trainer"].init_state()
            params = jax.tree.map(
                lambda x: x.addressable_shards[0].data,
                built["trainer"].params_tree(state),
            )
        # The cell's own rows: a seed reads here what its run reads.
        dataset = config.make_dataset(
            sizes, seed, 4 if tiny else cell.workload["dataset_samples"]
        )
        if not controls_only:
            print(json.dumps({
                "seed": seed,
                "system": config.reference_check(
                    built, params, dataset, sizes
                ),
            }), flush=True)
        if not first_only:
            sample = {k: jnp.asarray(v[:1]) for k, v in dataset.items()}
            for record in controls(
                config, built, params, sample, sizes, seed
            ):
                print(json.dumps({"seed": seed, **record}), flush=True)
        del built, state, params  # one seed's state at a time


if __name__ == "__main__":
    main(sys.argv[1:])
