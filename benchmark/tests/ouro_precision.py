"""Builder's tool, on the chip: the two readings behind each limit of
ouro-2.6b's reference comparison (``configs/ouro-2.6b.py``).

For each seed, at the published widths on one row of the timed length:
the SYSTEM against the float32 reference (first reading: what the
limits must admit — ``reference_check`` itself, as the cell runs it),
and the reference computed WRONG on purpose against itself (second
readings: what at least one limit must refuse), each control of
``VARIANTS`` read as the check reads the system: the loss, every
exit's per-token cross-entropy and state, layer_0 alone on the
embedding, and the gradient of every leaf on the row's first
``GRADIENT_TOKENS`` tokens. ``--first`` prints the first readings
only (about a third of the time a seed).

    chiprun -- python benchmark/tests/ouro_precision.py [--first] [--tiny] seed ...

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
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 96, "vocab_size": 512,
    "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
    "sequence_length": 256, "head_chunk_columns": 128,
}


def main(argv: list[str]) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import manifest

    first_only, tiny = "--first" in argv, "--tiny" in argv
    seeds = [int(a) for a in argv if not a.startswith("--")]
    os.environ.setdefault("ADAPTDL_NUM_REPLICAS", "1")
    cell = manifest.load_cell("ouro-2.6b-steady")
    config = manifest.load_module(cell.config_py)
    sizes, geometry = cell.sizes, cell.workload["geometry"]
    if tiny:
        sizes.update(TINY)
        config.GRADIENT_TOKENS, config.ATTENTION_QUERY_BLOCK = 64, 64
    print(f"device {jax.devices()[0].device_kind}", flush=True)

    block, head = config.reference_pieces(sizes)
    pieces = dict(
        block=jax.checkpoint(block, static_argnums=(2,)),
        head=jax.checkpoint(head, static_argnums=(3,)),
    )

    def control(
        weights, sample, short, right, right_grads, gate_terms, variant
    ):
        """``variant`` of the reference against the right one."""
        loss, wrong = config.reference_loss(
            weights, sample["inputs"], sample["targets"], sizes,
            variant=variant, block=block, head=head, details=True,
        )
        states = [
            config.layer_error(wrong["z"][t], right[1]["z"][t])
            for t in range(len(wrong["z"]))
        ]
        embedded = weights["embedding"][sample["inputs"]]
        layer = weights["layers"][0]
        alone = config.layer_error(
            block(layer, embedded, variant), block(layer, embedded, "")
        )
        grads, _ = config.reference_gradient(
            weights, short["inputs"], short["targets"], sizes,
            variant=variant, **pieces,
        )
        out = {
            "rel_diff": abs(float(loss) - float(right[0])) / float(right[0]),
            # The head alone, on the RIGHT reference's states.
            "head_token_loss_err": max(
                float(jnp.max(jnp.abs(
                    head(right[1]["z"][t], weights["head"],
                         sample["targets"], variant)
                    - right[1]["xent"][t]
                )))
                for t in range(len(wrong["z"]))
            ),
            "state_token_err": max(float(s[0]) for s in states),
            "state_rms_err": max(float(s[1]) for s in states),
            "block_pass1_token_err": float(alone[0]),
            "block_pass1_rms_err": float(alone[1]),
            **{
                k: float(v)
                for k, v in jax.jit(config.grad_errors)(
                    grads, right_grads, gate_terms
                ).items()
            },
        }
        out["refused_by"] = sorted(
            k for k, v in config.limits().items() if k in out and out[k] > v
        )
        return out

    for seed in seeds:
        built = config.build(sizes, geometry, seed)
        state = built["trainer"].init_state()
        params = jax.tree.map(
            lambda x: x.addressable_shards[0].data,
            built["trainer"].params_tree(state),
        )
        # The cell's own rows: a seed reads here what its run reads.
        dataset = config.make_dataset(
            sizes, seed, 4 if tiny else cell.workload["dataset_samples"]
        )
        out = {
            "seed": seed,
            "system": config.reference_check(built, params, dataset, sizes),
        }
        print(json.dumps(out), flush=True)
        if first_only:
            del built, state, params  # one seed's state at a time
            continue
        sample = {k: jnp.asarray(v[:1]) for k, v in dataset.items()}
        short = {
            k: v[:, : config.GRADIENT_TOKENS] for k, v in sample.items()
        }
        weights = config.reference_weights(params, sizes)
        right = config.reference_loss(
            weights, sample["inputs"], sample["targets"], sizes,
            block=block, head=head, details=True,
        )
        right_grads, gate_terms = config.reference_gradient(
            weights, short["inputs"], short["targets"], sizes, **pieces
        )
        for variant in config.VARIANTS:
            print(
                json.dumps(
                    {
                        "seed": seed, "control": variant,
                        **control(
                            weights, sample, short, right, right_grads,
                            gate_terms, variant,
                        ),
                    }
                ),
                flush=True,
            )
        del built, state, params, weights, right, right_grads


if __name__ == "__main__":
    main(sys.argv[1:])
