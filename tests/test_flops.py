"""MFU accounting sanity (adaptdl_tpu/flops.py).

The reference has no utilization reporting to mirror; these tests pin
the arithmetic of the matmul-only convention so chip_smoke.py's MFU
line is trustworthy.
"""

import pytest

from adaptdl_tpu.flops import (
    FlopsBreakdown,
    device_peak_flops,
    mfu,
    transformer_train_flops,
)
from adaptdl_tpu.models import TransformerConfig


def test_dense_transformer_flops_match_hand_count():
    cfg = TransformerConfig(
        vocab_size=1000,
        num_layers=2,
        num_heads=4,
        d_model=64,
        d_ff=256,
        max_seq_len=128,
    )
    fl = transformer_train_flops(cfg, batch_size=4, seq_len=128)
    tokens = 4 * 128
    proj = 2 * 4 * 64 * 64
    ffn = 2 * 2 * 64 * 256
    head = 2 * 64 * 1000
    fwd_matmul = tokens * (2 * proj + 2 * ffn + head)
    assert fl.matmul == pytest.approx(3 * fwd_matmul)
    # causal: half the [S, S] rectangle, QK^T + PV, per layer
    fwd_attn = tokens * 2 * 2 * (2 * 128 * 64) / 2
    assert fl.attention == pytest.approx(3 * fwd_attn)
    assert fl.total == fl.matmul + fl.attention


def test_moe_blocks_cost_topk_experts():
    base = dict(
        vocab_size=1000, num_layers=4, num_heads=4,
        d_model=64, d_ff=256, max_seq_len=64,
    )
    dense = transformer_train_flops(
        TransformerConfig(**base), 2, 64
    )
    moe = transformer_train_flops(
        TransformerConfig(
            **base, moe_every_n=2, moe_num_experts=8, moe_top_k=2
        ),
        2,
        64,
    )
    # 2 of 4 layers swap a dense FFN for 2 expert FFNs + a router.
    tokens = 2 * 64
    ffn = 2 * 2 * 64 * 256
    router = 2 * 64 * 8
    expected_extra = 3 * tokens * 2 * (ffn + router)
    assert moe.total - dense.total == pytest.approx(expected_extra)


@pytest.mark.parametrize("passes", [1, 4])
def test_a_looped_model_counts_blocks_and_head_once_a_pass(passes):
    """``loop_passes`` applications of every block and one head an
    exit; a SwiGLU FFN is three matmuls."""
    base = dict(
        vocab_size=1000, num_layers=3, num_heads=4, d_model=64,
        d_ff=256, max_seq_len=128, ffn="swiglu",
    )
    once = transformer_train_flops(TransformerConfig(**base), 2, 128)
    tokens = 2 * 128
    per_token = 3 * (2 * 4 * 64 * 64 + 2 * 3 * 64 * 256) + 2 * 64 * 1000
    assert once.matmul == pytest.approx(3 * tokens * per_token)
    looped = transformer_train_flops(
        TransformerConfig(**base, loop_passes=passes), 2, 128
    )
    assert looped.matmul == pytest.approx(passes * once.matmul)
    assert looped.attention == pytest.approx(passes * once.attention)


def test_mfu_uses_peak_and_devices():
    value = mfu(
        flops_per_step=100e12, step_time_s=1.0,
        num_devices=2, peak_flops=100e12,
    )
    assert value == pytest.approx(0.5)
    assert mfu(1e12, 0.1, peak_flops=None, device=FakeCpu()) is None


class FakeCpu:
    platform = "cpu"
    device_kind = "cpu"


class FakeV5e:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_device_peak_table():
    assert device_peak_flops(FakeV5e()) == pytest.approx(197e12)
    assert device_peak_flops(FakeCpu()) is None


def test_kda_mla_and_shared_expert_are_counted_as_the_benchmark_counts():
    """``transformer_train_flops`` of the kimi-linear-48b-a3b
    configuration is the sum of ``benchmark/configs/
    kimi-linear-48b-a3b.py:forward_flops_per_token`` a token, three
    times (forward + backward)."""
    import json
    import os

    from benchmark import manifest

    base = os.path.join(
        manifest.ROOT, "benchmark", "configs", "kimi-linear-48b-a3b"
    )
    with open(base + ".json") as f:
        sizes = json.load(f)
    config = manifest.load_module(base + ".py")
    parts = config.forward_flops_per_token(sizes)
    seq = sizes["sequence_length"]
    got = transformer_train_flops(config.model_config(sizes), 2, seq)
    assert got.total == pytest.approx(
        3.0 * 2 * seq * sum(parts.values()), rel=1e-9
    )
    assert got.attention == pytest.approx(
        3.0 * 2 * seq * (parts["mla_attention"] + parts["kda_mixing"]),
        rel=1e-9,
    )
    assert got.total / (2 * seq) == pytest.approx(
        config.train_flops_per_unit(sizes), rel=1e-9
    )
