"""The qwen3-next-80b-a3b configuration and its cell (PR 49): the
manifest loads it, its job driver runs end to end on a shrunk copy on
the CPU, its FLOP count is the issue's arithmetic, the accepted
readers of the state kernels read its sizes, and the two new readers
of the chunks' own work read hand-made traces — and nothing where
there is nothing to read."""

import argparse
import json
import os

import pytest

from benchmark import delta_chunk, kda, manifest
from benchmark.xplane import DevicePlane, Event, Trace

ROOT = manifest.ROOT
CELL = "qwen3-next-80b-a3b-steady"
CONFIG = "qwen3-next-80b-a3b"
TINY = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_attn_config": {
        "num_heads": 4, "head_dim": 8, "kda_layers": [1, 2, 3],
    },
    "router_width": 16, "experts_held": 4, "num_experts": 4,
    "num_experts_per_tok": 3, "vocab_size": 211, "sequence_length": 64,
    "kda_chunk": 64, "head_chunk_rows": 32, "compute_dtype": "float32",
}
CALL = (
    '%{name} = (bf16[4,256,64,128]{{3,2,1,0}}, bf16[4,256,64,64]{{3,2,1,0}}) '
    'custom-call(bf16[4,256,64,128]{{3,2,1,0}} %q), '
    'custom_call_target="tpu_custom_call"'
)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("delta_chunk_ms", "delta_chunk_roofline")


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
    assert cell.chips == 1 and cell.config_name == CONFIG
    names = {m["name"] for m in cell.per_layer}
    assert {
        "kda_ms", "kda_roofline", "kda_fwd_runs_per_layer", "flash_fwd_ms",
        "moe_gmm_ms", "moe_load_max_over_mean", "mfu", "peak_hbm_gib",
        "restart_span_s", "state_init_s", "trace_lower_s", *NEW,
    } <= names
    assert not {"flash_bwd_ms", "calibrate_s"} & names
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "setup_s"
    }
    sizes = cell.sizes
    # Every published width, the router's width, and the stated cuts.
    assert (
        sizes["hidden_size"], sizes["intermediate_size"],
        sizes["moe_intermediate_size"],
        sizes["shared_expert_intermediate_size"],
        sizes["num_attention_heads"], sizes["num_key_value_heads"],
        sizes["head_dim"], sizes["partial_rotary_factor"],
        sizes["linear_num_key_heads"], sizes["linear_num_value_heads"],
        sizes["linear_key_head_dim"], sizes["linear_value_head_dim"],
        sizes["linear_conv_kernel_dim"], sizes["num_experts_per_tok"],
        sizes["rms_norm_eps"], sizes["rope_theta"],
        sizes["full_attention_interval"],
    ) == (2048, 5120, 512, 512, 16, 2, 256, 0.25, 16, 32, 128, 128, 4, 10,
          1e-6, 10000000, 4)
    assert sizes["router_width"] == sizes["published"]["num_experts"] == 512
    assert sizes["experts_held"] == sizes["num_experts"] == 32
    assert sizes["vocab_size"] * 8 == sizes["published"]["vocab_size"]
    assert sizes["published"]["num_hidden_layers"] == 48
    config = manifest.load_module(cell.config_py)
    assert config.layer_kinds(sizes) == sizes["layer_types"] == [
        "gdn", "gdn", "gdn", "full_attention"
    ]
    assert config.rotary_dims(sizes) == 64
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"]) == sorted(
        sizes["cuts"]
    ) == sorted(sizes["published"])
    assert entry["source"] == sizes["source"]
    # The two new readers read this cell alone.
    for metric in bench["per_layer"]:
        if metric["name"] in NEW:
            assert metric["workloads"] == [CELL]


def test_the_file_holds_the_catalogs_config():
    """Every number of the catalog entry's ``config`` under the same
    key, but the three the file lists as reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        entries = [json.loads(line) for line in f if line.strip()]
    (entry,) = [
        e for e in entries if e["name"] == "Qwen3-Next-80B-A3B-Instruct"
    ]
    sizes = manifest.load_cell(CELL).sizes
    assert sizes["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in sizes["reduced"]:
            assert sizes["published"][key] == value
        else:
            assert sizes[key] == value, key


def test_flops_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    parts = config.forward_flops_per_token(cell.sizes)
    assert parts["gdn_projections"] == pytest.approx(3 * 67.4e6, rel=2e-3)
    assert parts["gdn_mixing"] == pytest.approx(3 * 5.77e6, rel=2e-3)
    assert parts["attention_projections"] == pytest.approx(54.5e6, rel=2e-3)
    assert parts["attention"] == pytest.approx(134.2e6, rel=2e-3)
    assert parts["router"] == pytest.approx(4 * 2.1e6, rel=2e-3)
    assert parts["shared_expert"] == pytest.approx(4 * 6.3e6, rel=2e-3)
    assert parts["routed_experts"] == pytest.approx(4 * 3.93e6, rel=2e-3)
    assert parts["head"] == pytest.approx(77.8e6, rel=2e-3)
    assert sum(parts.values()) == pytest.approx(534.5e6, rel=2e-3)
    assert config.train_flops_per_unit(cell.sizes) == pytest.approx(
        1.60e9, rel=5e-3
    )
    assert config.units_per_sample(cell.sizes) == 16384


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
        workload=CELL, seed=2**31 + 4949, seconds=2.0, trace=trace
    )
    line = run.run_cell(cell, args)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    group = cell.per_layer if trace else cell.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in group}
    if trace:
        for name in ("kda_ms", "kda_roofline", "kda_fwd_runs_per_layer",
                     "flash_fwd_ms", "moe_gmm_ms", *NEW):
            assert name not in line["metrics"]
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    else:
        assert set(line["metrics"]) == {m["name"] for m in group}
    reference = line["compared"]["reference"]
    assert reference["shared_rows_missing"] == 0
    assert reference["gdn_rms_err"] < 1e-5
    assert reference["attention_rms_err"] < 1e-5


def _trace(ops):
    """Two executions of one step program over ``ops`` (ns)."""
    modules = [
        Event("jit_step", 0, 50_000_000),
        Event("jit_step", 50_000_000, 100_000_000),
    ]
    return Trace([DevicePlane(0, ops, modules)], [], {})


def _calls(fwd_ns, bwd_ns, fwd=6, bwd=6):
    """A step's worth of the chunk kernels' calls, twice, back to
    back: 3 gdn layers x 2 micro-batches (all heads a call), and a
    state kernel's call and a fusion that are nobody's."""
    ops, at = [], 0
    for _step in range(2):
        for n in range(fwd + bwd):
            name, ns = (
                (f"delta_chunk_fwd.{n}", fwd_ns) if n < fwd
                else (f"delta_chunk_bwd.{n}", bwd_ns)
            )
            ops.append(Event(CALL.format(name=name), at, at + ns))
            at += ns
        ops.append(Event(CALL.format(name="kda_fwd.1"), at, at + 7000))
        at += 7000
    ops.append(
        Event("%fusion.9 = bf16[16384,2048]{1,0} fusion(%x)", at, at + 5000)
    )
    return ops


def test_delta_chunk_ms_reads_the_named_calls_alone():
    record = _record()
    trace = _trace(_calls(100_000, 300_000))
    assert _reader("delta_chunk_ms").read(trace, {}, record) == pytest.approx(
        6 * 0.1 + 6 * 0.3
    )
    # The state kernels' reader does not read them, nor they it.
    assert _reader("kda_ms").read(trace, {}, record) == pytest.approx(0.007)
    pattern = _reader("delta_chunk_ms").PATTERN
    assert pattern.search(CALL.format(name="delta_chunk_bwd.3"))
    assert pattern.search(
        CALL.format(name="transpose_jvp_delta_chunk_fwd__.4")
    )
    for other in ("kda_fwd.3", "flash_bwd.3", "attention.7", "moe_gmm.7"):
        assert not pattern.search(CALL.format(name=other))


def test_the_accepted_state_readers_read_this_cells_sizes():
    record = _record()
    assert kda.layer_shape(record) == dict(
        batch=1, heads=32, seq_len=16384, dk=128, dv=128, chunk=64
    )
    assert delta_chunk.layer_shape(record) == dict(
        batch=1, heads=32, seq_len=16384, dk=128, dv=128, chunk=64,
        key_heads=16,
    )
    assert delta_chunk.layer_passes(record) == 3 * 2
    # kimi's record: every head has its own q and k.
    kimi = manifest.load_cell("kimi-linear-48b-a3b-steady")
    shape = delta_chunk.layer_shape(
        {"sizes": kimi.sizes, "geometry": kimi.workload["geometry"]}
    )
    assert shape["key_heads"] == shape["heads"] == 32


def test_roofline_prices_one_decay_a_head_and_cannot_pass_100():
    record = _record()
    shape = delta_chunk.layer_shape(record)
    # The yardstick: four products a chunk, the backward at twice.
    tokens = 16384 * 32
    assert delta_chunk.flops(shape, False) == 2.0 * tokens * (
        3 * 64 * 128 + 64 * 128
    )
    assert delta_chunk.flops(shape, True) == 2 * delta_chunk.flops(
        shape, False
    )
    operands = (2 * 16 * 128 + 32 * 128) * 2 + 32 * 8
    results = 32 * (3 * 128 + 128 + 64) * 2
    assert delta_chunk.bytes_moved(shape, False) == 16384 * (
        operands + results
    )
    assert delta_chunk.bytes_moved(shape, True) == 16384 * (
        2 * operands + results
    )
    fwd_s = delta_chunk.least_seconds(shape, False, PEAK)
    bwd_s = delta_chunk.least_seconds(shape, True, PEAK)
    # Memory-bound both ways at these widths.
    assert delta_chunk.flops(shape, False) / 197e12 < fwd_s
    assert delta_chunk.flops(shape, True) / 197e12 < bwd_s
    reader = _reader("delta_chunk_roofline")
    at_bound = _trace(_calls(round(fwd_s * 1e9), round(bwd_s * 1e9)))
    assert reader.read(at_bound, {}, record) == pytest.approx(100.0, rel=1e-3)
    # A forward formed again in a group's backward earns nothing.
    twice = _trace(
        _calls(round(fwd_s * 1e9), round(bwd_s * 1e9), fwd=12)
    )
    assert reader.read(twice, {}, record) == pytest.approx(
        100.0 * (fwd_s + bwd_s) / (2 * fwd_s + bwd_s), rel=1e-3
    )
    slow = _trace(_calls(round(4 * fwd_s * 1e9), round(4 * bwd_s * 1e9)))
    assert reader.read(slow, {}, record) == pytest.approx(25.0, rel=1e-3)
    # The same time in eight calls a layer is the same share.
    grouped = _trace(
        _calls(round(fwd_s * 1e9 / 8), round(bwd_s * 1e9 / 8), fwd=48, bwd=48)
    )
    assert reader.read(grouped, {}, record) == pytest.approx(100.0, rel=2e-3)


def test_new_readers_return_none_not_zero_when_nothing_matches():
    record = _record()
    other = _trace([
        Event("%fusion.9 = bf16[16384,2048]{1,0} fusion(%x)", 0, 1000),
        Event(CALL.format(name="kda_fwd.1"), 1000, 2000),
    ])
    for name in NEW:
        assert _reader(name).read(None, {}, record) is None
        assert _reader(name).read(other, {}, record) is None
    # Another configuration's record: no such layer to count by.
    ran = _trace(_calls(100_000, 300_000))
    gpt2 = {
        "peak_table": PEAK, "sizes": {"n_head": 12},
        "geometry": {"atomic_bsz": 16, "accum_steps": 1},
    }
    assert _reader("delta_chunk_roofline").read(ran, {}, gpt2) is None
    assert _reader("delta_chunk_ms").read(ran, {}, gpt2) == pytest.approx(2.4)
