"""Builder's tool, on the chip: the two readings behind each limit of
smallthinker-21b-a3b's reference comparison
(``configs/smallthinker-21b-a3b.py``).

For each seed, at the published widths on one row of the timed length:
the SYSTEM against the float32 reference (first reading: what the
limits must admit — ``reference_check`` itself, as the cell runs it),
and the reference computed WITH A FAULT against itself (second
readings: what at least one limit must refuse), each on the layer's
own inputs as the system saw them: the sliding mixer with its band off
by one key at either edge, without rotary, with bfloat16 logits and
with a bfloat16 softmax statistic; the full mixer WITH rotary and with
bfloat16 logits; a routed layer with a ``silu`` gate and with its
router on the FFN's input; every router with bfloat16 logits; the band
alone, on float32 and on bfloat16 operands, with bfloat16 logits, with
a bfloat16 statistic and off by one key (what comparison 7 refuses
where the mixers' own bfloat16 rounding hides them); the whole model's
loss under the routed layers' and the rotary's faults. ``--first``
prints the first readings only, ``--controls`` the second ones only
(the cell's own runs print the first: ``compared.reference``). The controls are compiled AS STATED
(``xla_allow_excess_precision`` false): under the TPU compiler's
default a rounding to bfloat16 and back is taken out of the program,
and a control reads 0.0 (PR 46). One seed a process on the chip.

    chiprun -- python benchmark/tests/smallthinker_precision.py \\
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
    "hidden_size": 64, "moe_ffn_hidden_size": 32, "moe_intermediate_size": 32,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 32,
    "num_attention_heads_per_layer": [14] * 4,
    "sliding_window_size": 48, "sliding_window": 48,
    "router_width": 16, "experts_held": 4, "moe_num_primary_experts": 4,
    "moe_num_active_primary_experts": 3, "num_experts_per_tok": 3,
    "vocab_size": 512, "sequence_length": 256, "head_chunk_rows": 128,
}
# Which faults each checked mixer is read with.
MIXER_FAULTS = {
    "sliding": ("band_4097", "band_4095", "band_ahead", "rotary_swapped",
                "bf16_logits", "bf16_stat"),
    "full": ("rotary_swapped", "bf16_logits"),
}


def main(argv: list[str]) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import manifest

    first_only, tiny = "--first" in argv, "--tiny" in argv
    controls_only = "--controls" in argv
    seeds = [int(a) for a in argv if not a.startswith("--")]
    os.environ.setdefault("ADAPTDL_NUM_REPLICAS", "1")
    cell = manifest.load_cell("smallthinker-21b-a3b-steady")
    config = manifest.load_module(cell.config_py)
    sizes, geometry = cell.sizes, cell.workload["geometry"]
    if tiny:
        sizes.update(TINY)
    print(f"device {jax.devices()[0].device_kind}", flush=True)
    mixers = config.checked_mixers(sizes)

    def mixer_control(name, layer, u, variant):
        """``variant`` of the reference's mixer against the right one,
        read as the check reads the system's."""
        u32 = u.astype(jnp.float32)

        def vjp(variant):
            def objective(layer, u):
                return jnp.sum(
                    config.reference_mixer(name, layer, u, sizes, variant)
                    * u32
                )

            return jax.grad(objective, argnums=(0, 1))(layer, u32)

        wrong = config.reference_mixer(name, layer, u, sizes, variant)
        right = config.reference_mixer(name, layer, u, sizes)
        token, rms = config.layer_error(wrong, right)
        (wrong_w, wrong_x), (right_w, right_x) = vjp(variant), vjp("")
        found = {
            f"{name}_token_err": token, f"{name}_rms_err": rms,
            f"{name}_param_grad_err": jnp.max(jnp.stack([
                config.slice_error(wrong_w[k][None], right_w[k][None])
                for k in right_w
            ])),
            f"{name}_input_grad_err": config.layer_error(wrong_x, right_x)[1],
        }
        if name == "sliding":
            for where, tokens in config.token_ranges(sizes).items():
                found[f"sliding_token_err_{where}"] = config.layer_error(
                    wrong, right, tokens
                )[0]
        return found

    def routed_control(layer, x, h, variant):
        """A routed layer with ``variant`` against the right one,
        forward and every gradient, read as the check reads the
        system's."""
        x, h = x.astype(jnp.float32), h.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            wrong, _ = config.reference_routed_ffn(
                layer, x, h, sizes, variant=variant
            )
            right, _ = config.reference_routed_ffn(layer, x, h, sizes)
            got, want = (
                config.reference_routed_vjp(layer, x, h, x, sizes, v)
                for v in (variant, "")
            )
        token, rms = config.layer_error(wrong, right)
        (got_w, got_x, got_h), (want_w, want_x, want_h) = got, want
        return {
            "routed_token_err": token, "routed_rms_err": rms,
            "expert_grad_err": jnp.max(jnp.stack([
                config.slice_error(got_w[k], want_w[k])
                for k in ("w1", "w3", "w2")
            ])),
            "router_grad_err": config.slice_error(
                got_w["router"][None], want_w["router"][None]
            ),
            "input_grad_err": config.layer_error(got_x, want_x)[1],
            "routed_on_grad_err": config.layer_error(got_h, want_h)[1],
        }

    def router_control(layers, inputs):
        found = [
            config.router_disagreement(
                config.reference_router(layer, h, sizes, "bf16_logits"),
                config.reference_router(layer, h, sizes),
            )
            for layer, h in zip(layers, inputs)
        ]
        return {
            "router_set_mismatch_share": jnp.max(
                jnp.stack([f[0] for f in found])
            ),
            "router_weight_err": jnp.max(jnp.stack([f[1] for f in found])),
        }

    def loss_control(weights, sample, variant):
        """The whole model's mean loss with ``variant`` against the
        right one, read as comparison 1 reads the system's."""
        wrong, right = (
            config.reference_loss(
                weights, sample["inputs"], sample["targets"], sizes,
                variant=v,
            )[0]
            for v in (variant, "")
        )
        return {"rel_diff": jnp.abs(wrong - right) / jnp.abs(right)}

    limits = {
        "rel_diff": config.REFERENCE_RTOL,
        "kernel_bf16_out_rms_err": config.KERNEL_BF16_RMS_LIMIT,
        "kernel_bf16_grad_rms_err": config.KERNEL_BF16_RMS_LIMIT,
        "routed_token_err": config.LAYER_LIMITS["routed"][0],
        "routed_rms_err": config.LAYER_LIMITS["routed"][1],
        "expert_grad_err": config.EXPERT_GRAD_RTOL,
        "router_grad_err": config.ROUTER_GRAD_RTOL,
        "input_grad_err": config.INPUT_GRAD_RMS,
        "routed_on_grad_err": config.ROUTED_ON_GRAD_RMS,
        "router_set_mismatch_share": config.ROUTER_SET_MISMATCH_SHARE,
        "router_weight_err": config.ROUTER_WEIGHT_ATOL,
        "kernel_out_rms_err": config.KERNEL_RMS_LIMIT,
        "kernel_grad_rms_err": config.KERNEL_RMS_LIMIT,
    }
    for name in mixers:
        limits.update({
            f"{name}_token_err": config.LAYER_LIMITS[name][0],
            f"{name}_rms_err": config.LAYER_LIMITS[name][1],
            f"{name}_param_grad_err": config.MIXER_GRAD_LIMITS[name][0],
            f"{name}_input_grad_err": config.MIXER_GRAD_LIMITS[name][1],
        })
    for where, limit in config.SLIDING_RANGE_LIMITS.items():
        limits[f"sliding_token_err_{where}"] = limit

    def refused(found):
        found = {k: float(v) for k, v in found.items()}
        found["refused_by"] = sorted(
            k for k, v in found.items() if v > limits[k]
        )
        return found

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
            sample = {k: v[:1] for k, v in dataset.items()}
            _, _, load = jax.jit(built["head_io"])(
                params, sample, jax.random.key(0)
            )
            weights = config.reference_weights(params, sizes)["layers"]
            for name, at in mixers.items():
                u = load[name][0][:1]
                for variant in MIXER_FAULTS[name]:
                    print(json.dumps({
                        "seed": seed, "mixer": name, "variant": variant,
                        **refused(jax.jit(
                            mixer_control, static_argnums=(0, 3),
                            compiler_options=config.AS_STATED,
                        )(name, weights[at]["attention"], u, variant)),
                    }), flush=True)
            routed = [
                {k: layer[k] for k in config.ROUTED_LEAVES}
                for layer in weights
            ]
            for variant in config.ROUTED_FAULTS:
                print(json.dumps({
                    "seed": seed, "variant": variant,
                    **refused(jax.jit(
                        routed_control, static_argnums=3,
                        compiler_options=config.AS_STATED,
                    )(routed[-1], load["inputs"][-1], load["routed_on"][-1],
                      variant)),
                }), flush=True)
            print(json.dumps({
                "seed": seed, "variant": "bf16_logits",
                **refused(jax.jit(
                    router_control, compiler_options=config.AS_STATED
                )(routed, load["routed_on"])),
            }), flush=True)
            for variant in config.KERNEL_FAULTS:
                for dtype in ("float32", "bfloat16"):
                    print(json.dumps({
                        "seed": seed, "kernel": "band", "variant": variant,
                        "operands": dtype,
                        **refused(config.kernel_check(
                            built, sizes, seed, variant, dtype
                        )),
                    }), flush=True)
            whole = config.reference_weights(params, sizes)
            for variant in config.ROUTED_FAULTS + ("rotary_swapped",):
                print(json.dumps({
                    "seed": seed, "model": "loss", "variant": variant,
                    **refused(jax.jit(
                        loss_control, static_argnums=2,
                        compiler_options=config.AS_STATED,
                    )(whole, sample, variant)),
                }), flush=True)
        del built, state, params  # one seed's state at a time


if __name__ == "__main__":
    main(sys.argv[1:])
