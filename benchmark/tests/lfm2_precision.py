"""Builder's tool, on the chip: the two readings behind each limit of
lfm2-8b-a1b's reference comparison (``configs/lfm2-8b-a1b.py``).

For each seed, at the published widths on rows of the timed length:
the SYSTEM against the float32 reference (first reading: what the
limits must admit), and the reference computed WRONG on purpose
against itself (second readings: what at least one limit must
refuse) — bfloat16 router scores, a softmax router, a bfloat16 head;
and, layer by layer on the system's own inputs, the planted faults of
``ROUTED_FAULTS`` (forward and gradients, first routed layer),
``CONV_FAULTS`` and ``ATTENTION_FAULTS``.
Weights that include the bias cannot be told apart on the cell's own
weights (the bias is zero there): ``tests/test_routed_lm.py`` holds
that with a seeded non-zero bias. The first seed also reads what the
device holds before and after the trainer lets go of its initial
parameters.

    chiprun -- python benchmark/tests/lfm2_precision.py [seed ...]
"""

from __future__ import annotations

import functools
import json
import os
import sys

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)


def main(seeds: list[int]) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import manifest

    os.environ.setdefault("ADAPTDL_NUM_REPLICAS", "1")
    cell = manifest.load_cell("lfm2-8b-a1b-steady")
    config = manifest.load_module(cell.config_py)
    sizes, geometry = cell.sizes, cell.workload["geometry"]
    print(f"device {jax.devices()[0].device_kind}", flush=True)

    def reference(variant):
        def run(weights, sample):
            losses, counts = config.reference_loss(
                weights, sample["inputs"], sample["targets"], sizes,
                per_token=True, variant=variant,
            )
            return losses, counts

        return jax.jit(run)

    variants = {
        v: reference(v) for v in ("", "bf16_scores", "softmax", "bf16_head")
    }

    def head_variant(hidden, embedding, targets):
        """The system's head with its logits rounded to bfloat16."""
        logits = (
            hidden.astype(jnp.bfloat16) @ embedding.T.astype(jnp.bfloat16)
        )
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
            targets[..., None], axis=-1,
        )
        return -picked[..., 0]

    def in_use():
        stats = jax.devices()[0].memory_stats() or {}  # None off the chip
        return stats.get("bytes_in_use", 0)

    def fault_readings(built, params, load):
        """Each planted fault against the right reference, read as
        ``layer_checks`` reads the system; the routed ones on the
        first routed layer, gradients on the first row."""
        weights = config.reference_weights(params, sizes)["layers"]
        at = sizes["num_dense_layers"]
        row = sizes["sequence_length"]
        x = load["inputs"][0].astype(jnp.float32)

        @functools.partial(jax.jit, static_argnames="fault")
        def routed(layer, x, fault):
            def run(variant):
                with jax.default_matmul_precision("highest"):
                    return (
                        config.reference_routed_ffn(
                            layer, x, sizes, variant=variant
                        )[0],
                        config.reference_routed_vjp(
                            layer, x[:row], x[:row], sizes, variant
                        ),
                    )

            (want, want_grads), (got, (got_w, got_x)) = run(""), run(fault)
            token, rms = config.layer_error(got, want)
            # routed_grad_errors takes the first side under the
            # system's names for the leaves.
            renamed = {
                "w_gate": got_w["w1"], "w_up": got_w["w3"],
                "w_down": got_w["w2"], "router": got_w["router"],
            }
            return {
                "token_err": token, "rms_err": rms,
                **config.routed_grad_errors((renamed, got_x), want_grads),
            }

        @functools.partial(jax.jit, static_argnames=("kind", "fault"))
        def mixer(layer, u, kind, fault):
            return config.layer_error(
                config.mixer_reference(kind, layer, u, sizes, fault),
                config.mixer_reference(kind, layer, u, sizes),
            )

        out = {}
        for fault in config.ROUTED_FAULTS:
            out[f"routed.{fault}"] = {
                k: float(v) for k, v in routed(weights[at], x, fault).items()
            }
        kinds = sizes["layer_types"]
        for kind, name, faults in (
            ("conv", "conv", config.CONV_FAULTS),
            ("attention", "full_attention", config.ATTENTION_FAULTS),
        ):
            for fault in faults:
                token, rms = mixer(
                    weights[kinds.index(name)], load[kind][0], kind, fault
                )
                out[f"{kind}.{fault}"] = {
                    "token_err": float(token), "rms_err": float(rms)
                }
        return out

    for number, seed in enumerate(seeds):
        before = in_use()
        built = config.build(sizes, geometry, seed)
        params = built["trainer"]._init_params
        if number == 0:
            # What the device holds with the caller's initial
            # parameters alive, with the fresh state beside them, and
            # once the caller lets go (the trainer already has).
            held = {"built": in_use() - before}
            state = built["trainer"].init_state()
            jax.block_until_ready(state)
            held["state_and_initial"] = in_use() - before
            del params
            held["state_alone"] = in_use() - before
            print(json.dumps({"bytes_in_use": held}), flush=True)
            params = jax.tree.map(
                lambda x: x.addressable_shards[0].data,
                built["trainer"].params_tree(state),
            )
            del state
        dataset = config.make_dataset(sizes, seed, 8)
        sample = {
            k: v[: config.REFERENCE_SEQUENCES] for k, v in dataset.items()
        }
        weights = config.reference_weights(params, sizes)
        hidden, system_losses, load = jax.jit(built["head_io"])(
            params, sample, jax.random.key(0)
        )
        ref_losses, ref_counts = variants[""](weights, sample)
        _, head_losses = jax.jit(config.reference_head)(
            hidden, weights["embedding"], sample["targets"]
        )
        out = {
            "seed": seed,
            "system": {
                "rel_loss": float(
                    abs(system_losses.mean() - ref_losses.mean())
                    / ref_losses.mean()
                ),
                "head_token_loss_err": float(
                    jnp.max(jnp.abs(system_losses - head_losses))
                ),
                "routing_l1_share": float(
                    config.routing_l1_share(
                        load["held_rows"], ref_counts, sizes
                    )
                ),
                "dropped": int(load["dropped"].sum()),
                "held_rows": load["held_rows"].tolist(),
            },
            "bf16_logits_head_token_loss_err": float(
                jnp.max(
                    jnp.abs(
                        jax.jit(head_variant)(
                            hidden, weights["embedding"], sample["targets"]
                        )
                        - head_losses
                    )
                )
            ),
        }
        routers = [l for l in weights["layers"] if "router" in l]

        def router_readings(variant):
            """Worst layer's (set mismatch share, weight error) of the
            system's router (variant None) or a wrong reference router
            against the reference router, on the system's inputs."""
            def one(layer, i):
                want = config.reference_router(layer, load["inputs"][i], sizes)
                got = (
                    config.in_expert_order(
                        load["experts"][i], load["weights"][i]
                    )
                    if variant is None
                    else config.reference_router(
                        layer, load["inputs"][i], sizes, variant
                    )
                )
                return config.router_disagreement(got, want)

            pairs = [
                jax.jit(one, static_argnums=1)(layer, i)
                for i, layer in enumerate(routers)
            ]
            return {
                "set_mismatch_share": max(float(p[0]) for p in pairs),
                "weight_err": max(float(p[1]) for p in pairs),
            }

        out["router_alone"] = {
            "system": router_readings(None),
            "bf16_scores": router_readings("bf16_scores"),
            "softmax": router_readings("softmax"),
        }
        first, held = sizes["first_expert"], sizes["experts_held"]
        for name in ("bf16_scores", "softmax", "bf16_head"):
            losses, counts = variants[name](weights, sample)
            out[name] = {
                "rel_loss": float(
                    abs(losses.mean() - ref_losses.mean())
                    / ref_losses.mean()
                ),
                "token_loss_err": float(
                    jnp.max(jnp.abs(losses - ref_losses))
                ),
                "routing_l1_share": float(
                    config.routing_l1_share(
                        counts[:, first:first + held], ref_counts, sizes
                    )
                ),
            }
        out["layers"] = {
            "system": config.layer_checks(built, params, load, sizes),
            **fault_readings(built, params, load),
        }
        print(json.dumps(out), flush=True)
        del built, params, weights, load, hidden


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:]] or [3000100011, 3000100012])
