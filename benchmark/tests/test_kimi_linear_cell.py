"""The kimi-linear-48b-a3b configuration and its cell (PR 46): the
manifest loads it, its job driver runs end to end on a shrunk copy on
the CPU, its FLOP count is the issue's arithmetic, and the three new
readers read hand-made traces — and nothing where there is nothing to
read."""

import argparse
import json
import os

import pytest

from benchmark import kda, manifest
from benchmark.xplane import DevicePlane, Event, Trace

ROOT = manifest.ROOT
CELL = "kimi-linear-48b-a3b-steady"
TINY = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "linear_attn_config": {
        "full_attn_layers": [4], "head_dim": 8, "kda_layers": [1, 2, 3, 5],
        "num_heads": 2, "short_conv_kernel_size": 4,
    },
    "kv_lora_rank": 12, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "router_width": 16, "experts_held": 4,
    "num_experts": 4, "num_experts_per_token": 2, "num_experts_per_tok": 2,
    "vocab_size": 211, "sequence_length": 64, "kda_gate_rank": 8,
    "kda_chunk": 64, "head_chunk_rows": 32, "compute_dtype": "float32",
}
CALL = (
    '%{name} = (bf16[32,16384,128]{{2,1,0}}, f32[32,256,128,128]{{3,2,1,0}}) '
    'custom-call(bf16[32,16384,128]{{2,1,0}} %qp), '
    'custom_call_target="tpu_custom_call"'
)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _record():
    cell = manifest.load_cell(CELL)
    return {
        "peak_table": PEAK, "sizes": cell.sizes,
        "geometry": cell.workload["geometry"],
    }


def _reader(name):
    return manifest.load_module(manifest.reader_path(ROOT, name))


def test_manifest_loads_the_cell():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "kimi-linear-48b-a3b"
    names = {m["name"] for m in cell.per_layer}
    assert {
        "kda_ms", "kda_roofline", "kda_fwd_runs_per_layer", "flash_fwd_ms",
        "moe_gmm_ms", "moe_load_max_over_mean", "mfu", "peak_hbm_gib",
    } <= names
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "setup_s"
    }
    sizes = cell.sizes
    # Every published width, the router's width, and the stated cuts.
    assert (
        sizes["hidden_size"], sizes["intermediate_size"],
        sizes["moe_intermediate_size"], sizes["num_attention_heads"],
        sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
        sizes["qk_rope_head_dim"], sizes["v_head_dim"],
        sizes["num_experts_per_token"], sizes["num_shared_experts"],
        sizes["routed_scaling_factor"], sizes["rms_norm_eps"],
    ) == (2304, 9216, 1024, 32, 512, 128, 64, 128, 8, 1, 2.446, 1e-5)
    linear = sizes["linear_attn_config"]
    assert (
        linear["head_dim"], linear["num_heads"],
        linear["short_conv_kernel_size"],
    ) == (128, 32, 4)
    assert sizes["router_width"] == sizes["published"]["num_experts"] == 256
    assert sizes["experts_held"] == sizes["num_experts"] == 8
    assert sizes["vocab_size"] * 8 == sizes["published"]["vocab_size"]
    config = manifest.load_module(cell.config_py)
    assert config.layer_kinds(sizes) == sizes["layer_types"] == [
        "kda", "kda", "kda", "mla", "kda"
    ]
    published = sizes["published"]["linear_attn_config"]
    assert set(linear["kda_layers"]) <= set(published["kda_layers"])
    assert set(linear["full_attn_layers"]) <= set(published["full_attn_layers"])
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["kimi-linear-48b-a3b"]
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"])
    assert entry["source"] == sizes["source"]


def test_flops_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    parts = config.forward_flops_per_token(cell.sizes)
    assert parts["kda_projections"] == pytest.approx(315.7e6, rel=2e-3)
    assert parts["kda_mixing"] == pytest.approx(23.1e6, rel=5e-3)
    assert parts["mla_attention"] == pytest.approx(167.8e6, rel=2e-3)
    assert parts["mla_projections"] == pytest.approx(58.2e6, rel=2e-3)
    assert parts["dense_ffn"] == pytest.approx(127.4e6, rel=2e-3)
    assert parts["shared_experts"] == pytest.approx(56.6e6, rel=2e-3)
    assert parts["routed_experts"] == pytest.approx(14.16e6, rel=2e-3)
    assert parts["head"] == pytest.approx(94.4e6, rel=2e-3)
    assert sum(parts.values()) == pytest.approx(862e6, rel=5e-3)
    assert config.units_per_sample(cell.sizes) == 16384
    # One layer's chunked rule: its own four products and the state's.
    assert kda.forward_flops_per_token(32, 128, 128, 64) == 2 * 32 * (
        4 * 64 * 128 + 3 * 128 * 128 + 64 * 128
    )


def test_parameters_are_the_issues_count(monkeypatch):
    """602.4 M parameters at the published widths, from shapes alone."""
    import jax
    import jax.numpy as jnp

    from adaptdl_tpu.models import TransformerLM

    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    model = TransformerLM(config.model_config(cell.sizes))
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 128), jnp.int32), train=False
        )
    )["params"]
    count = sum(x.size for x in jax.tree.leaves(shapes))
    assert count == pytest.approx(602.4e6, rel=1e-3)
    kda_layer = sum(x.size for x in jax.tree.leaves(shapes["layer_1"]["kda"]))
    mla_layer = sum(x.size for x in jax.tree.leaves(shapes["layer_3"]["mla"]))
    assert kda_layer == pytest.approx(39.5e6, rel=2e-3)
    assert mla_layer == pytest.approx(29.1e6, rel=2e-3)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_cpu(trace, tmp_path, monkeypatch):
    """The steady job driver on a shrunk copy of the cell: correct
    (every reference comparison included), nothing failed, the line
    has the cell's metrics; on the CPU the kernels are interpreted, so
    the device-trace readers find no Mosaic call and leave their
    metrics out, while the program counter reads."""
    from benchmark import run

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cell = manifest.load_cell(CELL)
    cell.platform = "cpu"
    cell.sizes.update(TINY)
    # (A tiny model shows a falling loss in a two-second window only at
    # a larger rate than the cell's 2e-5, which is its users'.)
    cell.sizes["recipe"] = {**cell.sizes["recipe"], "learning_rate": 3e-4}
    cell.workload["dataset_samples"] = 64
    cell.workload["job"].update(
        warm_steps=3, trace_after_steps=2, trace_slice_s=0.5
    )
    args = argparse.Namespace(
        workload=CELL, seed=2**31 + 12345, seconds=2.0, trace=trace
    )
    line = run.run_cell(cell, args)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    group = cell.per_layer if trace else cell.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in group}
    if trace:
        for name in ("kda_ms", "kda_roofline", "kda_fwd_runs_per_layer",
                     "flash_fwd_ms", "moe_gmm_ms"):
            assert name not in line["metrics"]
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    else:
        assert set(line["metrics"]) == {m["name"] for m in group}
    reference = line["compared"]["reference"]
    assert reference["shared_rows_missing"] == 0
    assert reference["kda_rms_err"] < 1e-5 and reference["mla_rms_err"] < 1e-5


def _trace(ops):
    """Two executions of one step program over ``ops`` (ns)."""
    modules = [
        Event("jit_step", 0, 50_000_000),
        Event("jit_step", 50_000_000, 100_000_000),
    ]
    return Trace([DevicePlane(0, ops, modules)], [], {})


def _calls(fwd_ns, bwd_ns, fwd=16, bwd=8):
    """A step's worth of the state kernels' calls, twice, back to
    back: 4 kda layers x 2 micro-batches (all heads a call), the
    forward ``fwd / bwd`` times each."""
    ops, at = [], 0
    for _step in range(2):
        for n in range(fwd + bwd):
            name, ns = (
                (f"kda_fwd.{n}", fwd_ns) if n < fwd else (f"kda_bwd.{n}", bwd_ns)
            )
            ops.append(Event(CALL.format(name=name), at, at + ns))
            at += ns
    ops.append(
        Event("%fusion.9 = bf16[16384,2304]{1,0} fusion(%x)", at, at + 5000)
    )
    return ops


def test_kda_ms_and_runs_per_layer():
    record = _record()
    trace = _trace(_calls(100_000, 300_000))
    assert _reader("kda_ms").read(trace, {}, record) == pytest.approx(
        16 * 0.1 + 8 * 0.3
    )
    assert _reader("kda_fwd_runs_per_layer").read(
        trace, {}, record
    ) == pytest.approx(2.0)
    once = _trace(_calls(100_000, 300_000, fwd=8))
    assert _reader("kda_fwd_runs_per_layer").read(
        once, {}, record
    ) == pytest.approx(1.0)
    # Eight groups of heads a layer, the forward twice a group.
    grouped = _trace(_calls(10_000, 30_000, fwd=128, bwd=64))
    assert _reader("kda_fwd_runs_per_layer").read(
        grouped, {}, record
    ) == pytest.approx(2.0)
    pattern = _reader("kda_ms").PATTERN
    assert pattern.search(CALL.format(name="kda_bwd.3"))
    assert pattern.search(CALL.format(name="transpose_jvp_kda_fwd__.4"))
    assert not pattern.search(CALL.format(name="flash_bwd.3"))
    assert not pattern.search(CALL.format(name="attention.7"))
    assert not pattern.search(CALL.format(name="moe_gmm.7"))


def test_roofline_counts_one_forward_a_backward_and_cannot_pass_100():
    record = _record()
    shape = kda.layer_shape(record)
    assert shape == dict(
        batch=1, heads=32, seq_len=16384, dk=128, dv=128, chunk=64
    )
    fwd_s = kda.least_seconds(shape, False, PEAK)
    bwd_s = kda.least_seconds(shape, True, PEAK)
    # Memory-bound both ways at these widths: the state a chunk.
    assert kda.kernel_flops(backward=False, **shape) / 197e12 < fwd_s
    assert kda.kernel_flops(backward=True, **shape) / 197e12 < bwd_s
    assert fwd_s == pytest.approx(
        kda.kernel_bytes(backward=False, **shape) / 819e9
    )
    reader = _reader("kda_roofline")
    at_bound = _trace(
        _calls(round(fwd_s * 1e9), round(bwd_s * 1e9), fwd=8)
    )
    assert reader.read(at_bound, {}, record) == pytest.approx(100.0, rel=1e-3)
    # The forward run a second time earns nothing.
    twice = _trace(_calls(round(fwd_s * 1e9), round(bwd_s * 1e9), fwd=16))
    assert reader.read(twice, {}, record) == pytest.approx(
        100.0 * (fwd_s + bwd_s) / (2 * fwd_s + bwd_s), rel=1e-3
    )
    slow = _trace(
        _calls(round(2 * fwd_s * 1e9), round(2 * bwd_s * 1e9), fwd=8)
    )
    assert reader.read(slow, {}, record) == pytest.approx(50.0, rel=1e-3)
    # The same time in eight calls a layer is the same share.
    grouped = _trace(
        _calls(round(fwd_s * 1e9 / 8), round(bwd_s * 1e9 / 8), fwd=64, bwd=64)
    )
    assert reader.read(grouped, {}, record) == pytest.approx(100.0, rel=2e-3)


def test_readers_return_none_not_zero_when_nothing_matches():
    record = _record()
    other = _trace(
        [Event("%fusion.9 = bf16[16384,2304]{1,0} fusion(%x)", 0, 1000)]
    )
    for name in ("kda_ms", "kda_roofline", "kda_fwd_runs_per_layer"):
        assert _reader(name).read(None, {}, record) is None
        assert _reader(name).read(other, {}, record) is None
    # Another configuration's record: no such layer to count by.
    ran = _trace(_calls(100_000, 300_000))
    gpt2 = {
        "peak_table": PEAK, "sizes": {"n_head": 12},
        "geometry": {"atomic_bsz": 16, "accum_steps": 1},
    }
    assert _reader("kda_roofline").read(ran, {}, gpt2) is None
    only_forward = _trace(_calls(100_000, 300_000, bwd=0))
    assert _reader("kda_fwd_runs_per_layer").read(
        only_forward, {}, record
    ) is None
