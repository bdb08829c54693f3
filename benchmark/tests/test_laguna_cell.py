"""The laguna-xs.2 configuration and its cell (PR 54): the manifest
loads it, its file holds the catalog's config, its job driver runs end
to end on a shrunk copy on the CPU, its FLOP count is the issue's
arithmetic with the sliding layers counted over their band, and the
three new readers read hand-made traces and events — and nothing where
there is nothing to read."""

import argparse
import json
import os

import pytest

from benchmark import manifest, window_attention
from benchmark.xplane import DevicePlane, Event, Trace

ROOT = manifest.ROOT
CELL = "laguna-xs.2-steady"
CONFIG = "laguna-xs.2"
TINY = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "sliding_window": 24,
    "router_width": 16, "experts_held": 4, "num_experts": 4,
    "num_experts_per_tok": 3, "vocab_size": 211, "sequence_length": 64,
    "head_chunk_rows": 32, "compute_dtype": "float32",
}
CALL = (
    '%{name} = (bf16[64,128,16384]{{2,1,0}}, f32[64,1,16384]{{2,1,0}}) '
    'custom-call(bf16[64,128,16384]{{2,1,0}} %q), '
    'custom_call_target="tpu_custom_call"'
)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = (
    "window_attn_ms", "window_attn_roofline",
    "window_keys_visited_over_window",
)


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
        "flash_fwd_ms", "moe_gmm_ms", "moe_gmm_roofline",
        "moe_load_max_over_mean", "mfu", "peak_hbm_gib", "step_device_ms",
        "device_idle_share", "restart_span_s", "state_init_s",
        "trace_lower_s", *NEW,
    } <= names
    assert not {"flash_bwd_ms", "flash_bwd_roofline", "calibrate_s"} & names
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "setup_s"
    }
    assert cell.workload["geometry"] == {
        "atomic_bsz": 1, "accum_steps": 1, "global_batch": 2
    }
    sizes = cell.sizes
    # Every published width, the router's width, and the stated cuts.
    assert (
        sizes["hidden_size"], sizes["intermediate_size"],
        sizes["moe_intermediate_size"],
        sizes["shared_expert_intermediate_size"],
        sizes["num_attention_heads"], sizes["num_key_value_heads"],
        sizes["head_dim"], sizes["sliding_window"],
        sizes["num_experts_per_tok"], sizes["rms_norm_eps"],
        sizes["moe_routed_scaling_factor"],
    ) == (2048, 8192, 512, 512, 48, 8, 128, 512, 8, 1e-6, 2.5)
    assert sizes["router_width"] == sizes["published"]["num_experts"] == 256
    assert sizes["experts_held"] == sizes["num_experts"] == 16
    assert sizes["vocab_size"] * 8 == sizes["published"]["vocab_size"]
    assert sizes["published"]["num_hidden_layers"] == 40
    config = manifest.load_module(cell.config_py)
    assert config.layer_kinds(sizes) == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention",
    ]
    assert sizes["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert config.routed_layers(sizes) == [1, 2, 3, 4]
    assert config.rotary_lanes(sizes, "full_attention") == 64
    assert config.rotary_lanes(sizes, "sliding_attention") == 128
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"]) == sorted(
        k for k in sizes["published"] if k != "parameters"
    )
    assert {"num_hidden_layers", "num_experts", "vocab_size"} == set(
        sizes["cuts"]
    )
    assert entry["source"] == sizes["source"]
    for key in ("deployment", "assumed", "departures", "recipe"):
        assert sizes[key]
    # The three new readers read this cell (and whatever later cell
    # has a window to read), and the cell is there: what the case
    # means, not how many cells came after it.
    for metric in bench["per_layer"]:
        if metric["name"] in NEW:
            assert CELL in metric["workloads"]
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_file_holds_the_catalogs_config():
    """Every key of the catalog entry's ``config`` under the same key,
    unchanged but those the file lists as reduced; a per-layer list is
    cut to the kept layers, which are the published first five."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        entries = [json.loads(line) for line in f if line.strip()]
    (entry,) = [e for e in entries if e["name"] == "Laguna-XS.2"]
    sizes = manifest.load_cell(CELL).sizes
    assert sizes["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key not in sizes["reduced"]:
            assert sizes[key] == value, key
        elif isinstance(value, list):
            assert sizes[key] == value[: sizes["num_hidden_layers"]], key
        else:
            assert sizes["published"][key] == value


def test_flops_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    parts = config.forward_flops_per_token(cell.sizes)
    # The two full layers' kernels, and the three sliding layers' BAND:
    # 504.0 keys a query where every causal pair would be 8192.
    assert parts["full_attention"] == pytest.approx(402.7e6, rel=1e-3)
    assert parts["sliding_attention"] == pytest.approx(49.5e6, rel=2e-3)
    assert window_attention.band_pairs(16384, 512) / 16384 == pytest.approx(
        504.0, abs=0.02
    )
    assert 3 * 2 * 2 * 128 * 64 * 8192 == pytest.approx(805.3e6, rel=1e-3)
    assert parts["head"] == pytest.approx(2 * 2048 * 12544)
    assert parts["dense_ffn"] == pytest.approx(2 * 3 * 2048 * 8192)
    assert parts["routed_experts"] == pytest.approx(
        4 * 0.5 * 2 * 3 * 2048 * 512
    )
    assert sum(parts.values()) == pytest.approx(991e6, rel=5e-3)
    assert config.train_flops_per_unit(cell.sizes) == 3 * sum(parts.values())
    assert config.units_per_sample(cell.sizes) == 16384
    # The program's own count agrees (sliding layers over their band).
    from adaptdl_tpu.flops import transformer_train_flops

    own = transformer_train_flops(config.model_config(cell.sizes), 1, 16384)
    assert own.total / 16384 == pytest.approx(
        config.train_flops_per_unit(cell.sizes), rel=2e-3
    )


def test_band_counts_against_brute_force():
    def brute(seq, window):
        return sum(min(i + 1, window) for i in range(seq))

    for seq, window in ((64, 8), (64, 64), (64, 100), (100, 1), (96, 33)):
        assert window_attention.band_pairs(seq, window) == brute(seq, window)
    shape = {"batch": 2, "heads": 8, "kv_heads": 2, "head_dim": 16,
             "seq_len": 96, "window": 33}
    assert window_attention.flops(shape, False) == (
        4 * 16 * brute(96, 33) * 2 * 8
    )
    assert window_attention.flops(shape, True) == 2.5 * (
        window_attention.flops(shape, False)
    )
    # q and o of the query heads, k and v of the kv heads, one lse.
    tokens = 2 * 96
    assert window_attention.bytes_moved(shape, False) == (
        tokens * 16 * 2 * (2 * 8 + 2 * 2) + 4 * tokens * 8
    )
    assert window_attention.bytes_moved(shape, True) == (
        tokens * 16 * 2 * (4 * 8 + 4 * 2) + 4 * tokens * 8
    )


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_cpu(trace, tmp_path, monkeypatch):
    """The steady job driver on a shrunk copy of the cell: correct
    (every reference comparison included), nothing failed, the line
    has the cell's metrics; on the CPU the kernels are interpreted, so
    the device-trace readers find no Mosaic call and leave their
    metrics out, while the program counters read."""
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
        workload=CELL, seed=2**31 + 5454, seconds=2.0, trace=trace
    )
    line = run.run_cell(cell, args)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    group = cell.per_layer if trace else cell.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in group}
    if trace:
        for name in ("flash_fwd_ms", "moe_gmm_ms", "window_attn_ms",
                     "window_attn_roofline"):
            assert name not in line["metrics"]
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
        # One tile holds the tiny row whole: 64 x 64 logits a row for
        # sum_i min(i + 1, 24) pairs.
        assert line["metrics"]["window_keys_visited_over_window"][
            "value"
        ] == pytest.approx(64 * 64 / window_attention.band_pairs(64, 24))
    else:
        assert set(line["metrics"]) == {m["name"] for m in group}
    reference = line["compared"]["reference"]
    assert reference["shared_rows_missing"] == 0
    assert reference["sliding_rms_err"] < 1e-5
    assert reference["full_rms_err"] < 1e-5
    assert reference["kernel_out_rms_err"] < 1e-5


def _trace(ops):
    """Two executions of one step program over ``ops`` (ns)."""
    modules = [
        Event("jit_step", 0, 50_000_000),
        Event("jit_step", 50_000_000, 100_000_000),
    ]
    return Trace([DevicePlane(0, ops, modules)], [], {})


def _calls(fwd_ns, bwd_ns, fwd=6, bwd=6):
    """A step's worth of the band kernels' calls, twice, back to back:
    3 sliding layers x 2 micro-batches, and a full layer's pair of
    calls and a fusion that are nobody's."""
    ops, at = [], 0
    for _step in range(2):
        for n in range(fwd + bwd):
            name, ns = (
                (f"window_attn_fwd.{n}", fwd_ns) if n < fwd
                else (f"window_attn_bwd.{n}", bwd_ns)
            )
            ops.append(Event(CALL.format(name=name), at, at + ns))
            at += ns
        for name in ("attention.3", "flash_bwd.1"):
            ops.append(Event(CALL.format(name=name), at, at + 7000))
            at += 7000
    ops.append(
        Event("%fusion.9 = bf16[16384,2048]{1,0} fusion(%x)", at, at + 5000)
    )
    return ops


def test_window_attn_ms_reads_the_named_calls_alone():
    record = _record()
    trace = _trace(_calls(100_000, 300_000))
    assert _reader("window_attn_ms").read(trace, {}, record) == pytest.approx(
        6 * 0.1 + 6 * 0.3
    )
    # The full layers' readers do not read them, nor they the full
    # layers' kernels.
    assert _reader("flash_fwd_ms").read(trace, {}, record) == pytest.approx(
        0.007
    )
    assert _reader("flash_bwd_ms").read(trace, {}, record) == pytest.approx(
        0.007
    )
    pattern = _reader("window_attn_ms").PATTERN
    assert pattern.search(CALL.format(name="window_attn_bwd.3"))
    assert pattern.search(
        CALL.format(name="transpose_jvp_window_attn_fwd__.4")
    )
    for other in ("attention.7", "flash_bwd.3", "kda_fwd.3", "moe_gmm.7"):
        assert not pattern.search(CALL.format(name=other))


def test_roofline_prices_the_band_and_cannot_pass_100():
    record = _record()
    shape = window_attention.layer_shape(record)
    assert shape == dict(
        batch=1, heads=64, kv_heads=8, head_dim=128, seq_len=16384,
        window=512,
    )
    assert window_attention.layer_passes(record) == 3 * 2
    pairs = window_attention.band_pairs(16384, 512)
    assert window_attention.flops(shape, False) == 4.0 * 128 * pairs * 64
    fwd_s = window_attention.least_seconds(shape, False, PEAK)
    bwd_s = window_attention.least_seconds(shape, True, PEAK)
    # Compute-bound both ways at these widths: 504 keys a query.
    assert fwd_s == window_attention.flops(shape, False) / 197e12
    assert bwd_s == 2.5 * fwd_s
    reader = _reader("window_attn_roofline")
    at_bound = _trace(_calls(round(fwd_s * 1e9), round(bwd_s * 1e9)))
    assert reader.read(at_bound, {}, record) == pytest.approx(100.0, rel=1e-3)
    # A kernel that multiplies 1.5 times the band's pairs at the peak.
    blocks = _trace(
        _calls(round(1.5 * fwd_s * 1e9), round(1.5 * bwd_s * 1e9))
    )
    assert reader.read(blocks, {}, record) == pytest.approx(
        100.0 / 1.5, rel=1e-3
    )
    # The same time in four calls a layer (runs of heads): the same.
    runs = _trace(
        _calls(round(fwd_s * 1e9 / 4), round(bwd_s * 1e9 / 4), fwd=24, bwd=24)
    )
    assert reader.read(runs, {}, record) == pytest.approx(100.0, rel=2e-3)


def test_keys_visited_over_window_reads_the_programs_events():
    record = _record()
    reader = _reader("window_keys_visited_over_window")
    pairs = window_attention.band_pairs(16384, 512)
    ours = {"seq_len": 16384, "window": 512, "batch_heads": 16,
            "keys_visited": 1.5 * pairs, "keys_in_window": pairs}
    snapshot = [
        {"name": "window.keys", "attrs": ours},
        {"name": "window.keys", "attrs": {**ours, "batch_heads": 48}},
        # Another shape's (a test's, a check's at another size): left out.
        {"name": "window.keys", "attrs": {**ours, "seq_len": 64,
                                          "keys_visited": 9 * pairs}},
        {"name": "flash.schedule", "attrs": {"seq_len": 16384}},
    ]
    events = window_attention.keys_events(snapshot, record)
    assert len(events) == 2
    assert reader.read(None, {}, record, events) == pytest.approx(1.5)
    # A walk over every causal pair.
    causal = [{**ours, "keys_visited": 16384 * 16385 / 2}]
    assert reader.read(None, {}, record, causal) == pytest.approx(
        16.26, abs=0.01
    )
    assert reader.read(None, {}, record, []) is None


def test_new_readers_return_none_not_zero_when_nothing_matches():
    record = _record()
    other = _trace([
        Event("%fusion.9 = bf16[16384,2048]{1,0} fusion(%x)", 0, 1000),
        Event(CALL.format(name="attention.1"), 1000, 2000),
    ])
    for name in NEW[:2]:
        assert _reader(name).read(None, {}, record) is None
        assert _reader(name).read(other, {}, record) is None
    # Another configuration's record: no such layer to count by.
    ran = _trace(_calls(100_000, 300_000))
    gpt2 = {
        "peak_table": PEAK, "sizes": {"n_head": 12},
        "geometry": {"atomic_bsz": 16, "accum_steps": 1},
    }
    assert _reader("window_attn_roofline").read(ran, {}, gpt2) is None
    assert _reader("window_attn_ms").read(ran, {}, gpt2) == pytest.approx(2.4)
    assert _reader(NEW[2]).read(ran, {}, gpt2, []) is None
