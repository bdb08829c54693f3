"""The FLOP function against GPT-2 124M numbers computed by hand."""

import pytest

from benchmark import flops, manifest

GPT2 = dict(
    n_layer=12, d_model=768, d_ff=3072, vocab_size=50257, seq_len=1024
)


def test_forward_parts_by_hand():
    parts = flops.lm_forward_flops_per_token(**GPT2)
    # QKV + output: 4 * 768^2 multiply-accumulates, 2 FLOPs each.
    assert parts["projections"] == 12 * 2 * 4 * 768 * 768 == 56_623_104
    # up + down: 2 * 768 * 3072 MACs.
    assert parts["ffn"] == 12 * 2 * 2 * 768 * 3072 == 113_246_208
    # QK^T and PV over 1024 keys, half of them masked.
    assert parts["attention"] == 12 * 2 * 2 * 1024 * 768 // 2 == 18_874_368
    assert parts["head"] == 2 * 768 * 50257 == 77_194_752


def test_train_flops_per_token_by_hand():
    # 3 x (56.6 + 113.2 + 18.9 + 77.2) MFLOP = 0.798 GFLOP per token.
    assert flops.lm_train_flops_per_token(**GPT2) == 3 * 265_938_432
    full = flops.lm_train_flops_per_token(**GPT2, causal=False)
    assert full - 3 * 265_938_432 == 3 * 18_874_368


def test_mfu_percent_by_hand():
    # 50 k tokens/s on one v5e: 50e3 * 797.8e6 / 197e12 = 20.25 %.
    got = flops.mfu_percent(797_815_296, 50_000, 1, 197e12)
    assert got == pytest.approx(20.2491, abs=1e-3)
    assert flops.mfu_percent(797_815_296, 200_000, 4, 197e12) == (
        pytest.approx(got)
    )


def test_config_module_uses_the_published_sizes():
    cell = manifest.load_cell("gpt2-124m-steady")
    config = manifest.load_module(cell.config_py)
    assert config.train_flops_per_unit(cell.sizes) == 797_815_296
    assert config.units_per_sample(cell.sizes) == 1024
