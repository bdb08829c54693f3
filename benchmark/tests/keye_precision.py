"""Builder's tool, on the chip: the two readings behind each limit of
keye-vl-2.0-30b-a3b's reference comparison
(``configs/keye-vl-2.0-30b-a3b.py``).

For each seed, at the published widths on one row of the timed length:
the SYSTEM against the float32 reference (first reading: what the
limits must admit, every one of ``reference_check``'s), and the
reference computed in LOWER PRECISION than the configuration states,
on purpose, against itself, on the first layer and the system's own
inputs to it (second readings: what at least one limit must refuse, by
name) —

- ``bf16_index_scores``: the index scores from bfloat16 operands,
  rounded to and accumulated in bfloat16 (comparison 5);
- ``bf16_scores``: the router's logits in bfloat16 (comparison 3);
- ``bf16_attention_sums``: the attention's partial sums over 256 keys
  rounded to and added in bfloat16 (comparison 6);
- ``bf16_accumulate``: the same in the experts' products (comparison 4).

    chiprun -- python benchmark/tests/keye_precision.py [seed ...]

``--first``: second readings for the first seed only. ``--excess``:
for the first seed also what the whole model gives when its program is
compiled with ``xla_allow_excess_precision=false`` beside the default
(the head inside the model against the head alone on the captured
hidden states, and every router's sets against the reference on the
captured inputs): the compiler's default keeps a float32 value where
the program rounds the residual stream to bfloat16 between two fused
operations, and this shows how much of a reading that is.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)


def main(
    seeds: list[int], cell_name: str, shrink: dict | None,
    first_only: bool = False, excess: bool = False,
) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import manifest

    os.environ.setdefault("ADAPTDL_NUM_REPLICAS", "1")
    cell = manifest.load_cell(cell_name)
    config = manifest.load_module(cell.config_py)
    sizes, geometry = cell.sizes, cell.workload["geometry"]
    sizes.update(shrink or {})
    print(f"device {jax.devices()[0].device_kind}", flush=True)

    @jax.jit
    def same_operands(operands):
        """The operands the model's first sparse mixer handed its
        kernels, as the reference takes them, and the system's own
        sets."""
        _, member = built_now["selection"](operands)
        return config.as_reference_operands(operands, sizes), member

    @jax.jit
    def index_variant(operands):
        """The index scores rounded to and accumulated in bfloat16,
        and the sets they select, against the reference's own on the
        same operands."""
        with jax.default_matmul_precision("highest"):
            scores, member, _ = config.reference_selection(
                operands, sizes, "bf16_index_scores"
            )
            return config.selection_errors(operands, sizes, scores, member)

    @jax.jit
    def attention_variant(operands, member):
        cotangent = operands["q"].reshape(operands["q"].shape[0], -1)
        with jax.default_matmul_precision("highest"):
            errors = config.kernel_errors(
                config.reference_kernels_vjp(
                    operands, member, cotangent, sizes,
                    "bf16_attention_sums",
                ),
                config.reference_kernels_vjp(
                    operands, member, cotangent, sizes
                ),
            )
        # The variant's output is float32: it is what the kernel's
        # unrounded output (``out_dtype=float32``) is held to as well.
        return {
            **errors,
            "kernel_f32_token_err": errors["kernel_token_err"],
            "kernel_f32_rms_err": errors["kernel_rms_err"],
        }

    @jax.jit
    def routed_variant(layer, x):
        layer = config.operands_as_stated(layer, jnp.bfloat16)
        with jax.default_matmul_precision("highest"):
            want, _ = config.reference_routed_ffn(layer, x, sizes)
            got, _ = config.reference_routed_ffn(
                layer, x, sizes, variant="bf16_accumulate"
            )
            grads = config.reference_routed_vjp(layer, x, x, sizes)
            wrong = config.reference_routed_vjp(
                layer, x, x, sizes, "bf16_accumulate"
            )
        token, rms = config.layer_error(got, want)
        return {
            "routed_token_err": token, "routed_rms_err": rms,
            "expert_grad_err": jnp.max(
                jnp.stack(
                    [
                        config.slice_error(wrong[0][k], grads[0][k])
                        for k in ("w1", "w3", "w2")
                    ]
                )
            ),
            "router_grad_err": config.leaf_error(
                wrong[0]["router"], grads[0]["router"]
            ),
            "input_grad_err": config.layer_error(wrong[1], grads[1])[1],
        }

    @jax.jit
    def router_variant(layer, x):
        share, weight = config.router_disagreement(
            config.reference_router(layer, x, sizes, "bf16_scores"),
            config.reference_router(layer, x, sizes),
        )
        return {
            "router_set_mismatch_share": share, "router_weight_err": weight,
        }

    @jax.jit
    def in_model(params, targets, hidden, losses, load):
        """Of one evaluation of the whole model: the head inside it
        against the head alone, and every router's sets against the
        reference's on the captured inputs."""
        layers = config.reference_weights(params, sizes)["layers"]
        alone = built_now["head_losses"](
            {"lm_head": params["lm_head"]}, hidden, targets
        )
        mismatch = [
            config.router_disagreement(
                config.in_expert_order(
                    load["experts"][i], load["weights"][i]
                ),
                config.reference_router(
                    layer,
                    load["inputs"][i].reshape(-1, sizes["hidden_size"]),
                    sizes,
                ),
            )[0]
            for i, layer in enumerate(layers)
        ]
        return {
            "head_in_model_gap": jnp.max(jnp.abs(losses - alone)),
            "router_set_mismatch_share": jnp.max(jnp.stack(mismatch)),
        }

    def excess_precision(params, sample) -> None:
        key = jax.random.key(0)
        for name, options in (
            ("default", None),
            ("xla_allow_excess_precision=false",
             {"xla_allow_excess_precision": False}),
        ):
            run = jax.jit(built_now["head_io"]).lower(
                params, sample, key
            ).compile(compiler_options=options)
            hidden, losses, load = run(params, sample, key)
            found = in_model(params, sample["targets"], hidden, losses, load)
            print(
                f"whole model compiled with {name}: "
                + json.dumps({k: float(v) for k, v in found.items()}),
                flush=True,
            )

    def refused(readings: dict) -> list[str]:
        return [
            name for name, value in readings.items()
            if value > config.limit_of(name)
        ]

    built_now = {}
    for seed in seeds:
        built = config.build(sizes, geometry, seed)
        built_now.clear()
        built_now.update(built)
        trainer = built["trainer"]
        state = trainer.init_state()
        params = jax.tree.map(
            lambda x: x.addressable_shards[0].data,
            trainer.params_tree(state),
        )
        dataset = config.make_dataset(sizes, seed, 4)
        first = config.reference_check(built, params, dataset, sizes)
        judged = config.verdict(first)
        print(
            f"seed {seed} first readings ok={first['ok']}: "
            + json.dumps({k: v[0] for k, v in judged.items()}),
            flush=True,
        )
        print(
            f"seed {seed} reading over limit: "
            + json.dumps(
                {
                    k: round(v[0] / v[1], 4)
                    for k, v in judged.items() if v[1]
                }
            ),
            flush=True,
        )
        sample = {k: v[:1] for k, v in dataset.items()}
        if excess and seed == seeds[0]:
            excess_precision(params, sample)
        if first_only and seed != seeds[0]:
            del built, trainer, state, params
            continue
        _, _, load = config.as_stated(built["head_io"])(
            params, sample, jax.random.key(0)
        )
        layer = config.reference_weights(params, sizes)["layers"][0]
        ffn_x = load["inputs"][0][0].astype(jnp.float32)
        operands, member = same_operands(load["operands"][0])
        # The routed layer's float32 intermediates of 16 experts and
        # their gradients on a whole row do not fit beside the train
        # state: its second reading is of the row's first quarter.
        second = {
            "bf16_index_scores": lambda: index_variant(operands),
            "bf16_scores": lambda: router_variant(layer, ffn_x),
            "bf16_attention_sums": lambda: attention_variant(
                operands, member
            ),
            "bf16_accumulate": lambda: routed_variant(
                layer, ffn_x[: max(ffn_x.shape[0] // 4, 1)]
            ),
        }
        for variant, read in second.items():
            readings = {k: float(v) for k, v in read().items()}
            names = refused({k: v for k, v in readings.items()
                             if k in config.LIMITS})
            print(
                f"seed {seed} second readings {variant}: "
                f"{json.dumps(readings)} REFUSED BY {names or 'NOTHING'}",
                flush=True,
            )
        del built, trainer, state, params, load, operands, member


if __name__ == "__main__":
    argv = sys.argv[1:]
    flags = {a for a in argv if a.startswith("--")}
    argv = [a for a in argv if a not in flags]
    shrink = None
    if "--tiny" in flags:  # a CPU rehearsal of this script
        from benchmark.tests.test_keye_cell import TINY as shrink
    main(
        [int(a) for a in argv] or [2147483000],
        "keye-vl-2.0-30b-a3b-steady", shrink,
        first_only="--first" in flags, excess="--excess" in flags,
    )
