"""The plain reference against the system's loss at tiny widths on the
CPU, in float32 (where they must agree to rounding) — and proof that
the comparison has teeth: a reference with a piece of the mathematics
changed, or a head with a piece lowered to bfloat16, lands outside the
tolerances the chip run uses."""

import jax
import numpy as np
import pytest

from benchmark import manifest

from rehearse import TINY

CASES = [("gpt2-124m-steady", 1e-5)]


def _built(cell_name, monkeypatch):
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    cell = manifest.load_cell(cell_name)
    sizes = dict(cell.sizes, **TINY[cell.config_name])
    config = manifest.load_module(cell.config_py)
    geometry = {"atomic_bsz": 2, "accum_steps": 0, "global_batch": 2}
    built = config.build(sizes, geometry, seed=3)
    dataset = config.make_dataset(sizes, seed=3, samples=64)
    params = built["trainer"]._init_params
    return config, built, params, dataset, sizes


@pytest.mark.parametrize("cell_name, rtol", CASES)
def test_reference_agrees_with_system(cell_name, rtol, monkeypatch):
    config, built, params, dataset, sizes = _built(cell_name, monkeypatch)
    result = config.reference_check(built, params, dataset, sizes)
    assert result["ok"]
    assert result["rel_diff"] <= rtol, result
    assert result["head_token_loss_err"] <= 1e-5, result


def test_gpt2_comparison_has_teeth(monkeypatch):
    """Untying the head, dropping the rotation or the causal mask moves
    the loss beyond REFERENCE_RTOL; bf16 blocks stay inside it.

    The bf16 blocks' rounding averages out of a MEAN loss with the
    number of tokens: on the first 2 rows (64 tokens, what this test
    held to the limit until PR 39) seeds 3-7 read 4.1e-4, 1.9e-4,
    3.5e-6, 2.0e-4, 1.8e-4 of the float32 loss, the noise itself
    around the limit of 3e-4; on all 64 rows (2048 tokens) 2.3e-5,
    3.6e-5, 3.3e-5, 1.6e-5, 5.6e-5 (this CPU, tiny widths; the chip's
    check reads 0.05e-5-3.5e-5 on 2048 tokens at real widths). So the
    bf16 case takes every row of the dataset; the limit is the
    configuration's, untouched."""
    config, built, params, dataset, sizes = _built(
        "gpt2-124m-steady", monkeypatch
    )
    sample = {k: v[:2] for k, v in dataset.items()}
    system = float(built["loss_fn"](params, sample, jax.random.key(0)))
    weights = config.reference_weights(params, sizes)
    eps = sizes["layer_norm_epsilon"]

    def off_by(broken):
        ref = float(
            config.reference_loss(
                broken, sample["inputs"], sample["targets"], eps
            )
        )
        return abs(system - ref) / abs(ref)

    assert off_by(weights) <= 1e-5
    scaled = dict(weights, ln_f=weights["ln_f"] * 1.5)
    assert off_by(scaled) > config.REFERENCE_RTOL
    swapped = dict(weights)
    swapped["layers"] = [
        dict(layer, wq=layer["wk"], wk=layer["wq"])
        for layer in weights["layers"]
    ]
    assert off_by(swapped) > 1e-4
    # bf16 blocks with a float32 head stay inside the tolerance.
    sizes16 = dict(sizes, compute_dtype="bfloat16")
    built16 = config.build(
        sizes16, {"atomic_bsz": 2, "accum_steps": 0, "global_batch": 2}, 3
    )
    bf16 = float(built16["loss_fn"](params, dataset, jax.random.key(0)))
    ref = float(
        config.reference_loss(
            weights, dataset["inputs"], dataset["targets"], eps
        )
    )
    assert len(dataset["inputs"]) == 64
    assert 0 < abs(bf16 - ref) / ref <= config.REFERENCE_RTOL / 3


def test_gpt2_head_comparison_has_teeth(monkeypatch):
    """The model's code rounds the logits to bfloat16 before casting
    them (``embed.attend`` under a bfloat16 dtype). The CPU backend
    keeps that rounding — the TPU compiler skips it where the logits
    feed the loss (PERF.md, Findings PR 22) — so here a bfloat16 model
    IS a model with bfloat16 logits: the whole-model mean stays inside
    its tolerance and cannot see it, the head comparison fails."""
    config, _, params, dataset, sizes = _built(
        "gpt2-124m-steady", monkeypatch
    )
    sizes16 = dict(sizes, compute_dtype="bfloat16")
    built16 = config.build(
        sizes16, {"atomic_bsz": 2, "accum_steps": 0, "global_batch": 2}, 3
    )
    result = config.reference_check(built16, params, dataset, sizes16)
    assert result["rel_diff"] <= config.REFERENCE_RTOL
    assert result["head_token_loss_err"] > 3 * config.HEAD_TOKEN_LOSS_ATOL
    assert not result["ok"]


def test_datasets_are_seeded_and_learnable_shapes():
    for cell_name, _ in CASES:
        cell = manifest.load_cell(cell_name)
        sizes = dict(cell.sizes, **TINY[cell.config_name])
        config = manifest.load_module(cell.config_py)
        a = config.make_dataset(sizes, seed=5, samples=32)
        b = config.make_dataset(sizes, seed=5, samples=32)
        c = config.make_dataset(sizes, seed=6, samples=32)
        for key in a:
            assert np.array_equal(a[key], b[key])
            assert len(a[key]) == 32
        assert any(not np.array_equal(a[k], c[k]) for k in a)
    tokens = manifest.load_module(
        manifest.load_cell("gpt2-124m-steady").config_py
    ).make_dataset(
        dict(TINY["gpt2-124m"], vocab_size=211, n_positions=32), 1, 16
    )
    assert tokens["inputs"].shape == (16, 32)
    assert np.array_equal(tokens["inputs"][:, 1:], tokens["targets"][:, :-1])
    assert tokens["inputs"].max() < 211
