"""The smallthinker-21b-a3b configuration and its cell (PR 60): the
manifest loads it, its file holds the catalog's config and restates it
under the accepted readers' names, the model's own leaves add up to the
file's written-out sum, its FLOP count is the issue's arithmetic and
the program's own, its job driver runs end to end on a shrunk copy on
the CPU, the accepted ``window_attn_*`` / ``moe_gmm_*`` readers find
their shapes in the new sizes, and the new reader reads a recorded
snapshot — and nothing where there is nothing to read."""

import argparse
import json
import os

import pytest

from benchmark import grouped_matmul, manifest, window_attention
from benchmark.xplane import DevicePlane, Event, Trace

ROOT = manifest.ROOT
CELL = "smallthinker-21b-a3b-steady"
CONFIG = "smallthinker-21b-a3b"
TINY = {
    "hidden_size": 32, "moe_ffn_hidden_size": 16, "moe_intermediate_size": 16,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 8,
    "num_attention_heads_per_layer": [14] * 4,
    "sliding_window_size": 24, "sliding_window": 24,
    "router_width": 16, "experts_held": 4, "moe_num_primary_experts": 4,
    "moe_num_active_primary_experts": 3, "num_experts_per_tok": 3,
    "vocab_size": 211, "sequence_length": 64, "head_chunk_rows": 32,
    "compute_dtype": "float32",
}
CALL = (
    '%{name} = (bf16[28,128,16384]{{2,1,0}}, f32[28,1,16384]{{2,1,0}}) '
    'custom-call(bf16[28,128,16384]{{2,1,0}} %q), '
    'custom_call_target="tpu_custom_call"'
)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# The accepted per-layer metrics whose lists gained the cell, and the
# one this PR adds.
APPENDED = (
    "flash_fwd_ms", "moe_gmm_ms", "moe_gmm_roofline",
    "moe_load_max_over_mean", "window_attn_ms", "window_attn_roofline",
    "window_keys_visited_over_window", "restart_span_s", "state_init_s",
    "trace_lower_s",
)
NEW = "expert_hidden_zero_share"


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
        "mfu", "peak_hbm_gib", "step_device_ms", "device_idle_share",
        "compiles_in_window", *APPENDED, NEW,
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
        sizes["hidden_size"], sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim"],
        sizes["moe_ffn_hidden_size"], sizes["moe_num_active_primary_experts"],
        sizes["sliding_window_size"], sizes["rope_theta"],
        sizes["rms_norm_eps"], sizes["max_position_embeddings"],
        sizes["tie_word_embeddings"],
    ) == (2560, 28, 4, 128, 768, 6, 4096, 1500000, 1e-6, 16384, False)
    published = sizes["published"]
    assert sizes["router_width"] == published["moe_num_primary_experts"] == 64
    assert sizes["experts_held"] == sizes["moe_num_primary_experts"] == 8
    assert sizes["vocab_size"] * 8 == published["vocab_size"]
    assert published["num_hidden_layers"] == 52
    assert sizes["rope_layout"] == sizes["sliding_window_layout"] == [
        0, 1, 1, 1
    ]
    # The accepted readers' names restate the published keys.
    assert set(sizes["derived"]) == {
        "layer_types", "num_attention_heads_per_layer", "sliding_window",
        "sequence_length", "num_experts_per_tok", "moe_intermediate_size",
        "router_width", "experts_held",
    }
    config = manifest.load_module(cell.config_py)
    assert config.layer_kinds(sizes) == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention",
    ]
    cfg = config.model_config(sizes)
    assert (cfg.experts_routed_on, cfg.experts_activation,
            cfg.experts_router, cfg.experts_pieces_from) == (
        "block_input", "relu", "softmax", 2.5
    )
    full = cfg.attention_kind("full_attention")
    sliding = cfg.attention_kind("sliding_attention")
    assert (full.rope, full.window, sliding.rope, sliding.window,
            sliding.rope_theta) == (False, None, True, 4096, 1.5e6)
    assert sizes["recipe"]["precondition"] == "adam"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"]) == sorted(
        k for k in published if k != "parameters"
    )
    assert {"num_hidden_layers", "moe_num_primary_experts",
            "vocab_size"} == set(sizes["cuts"])
    assert entry["source"] == sizes["source"]
    for key in ("deployment", "assumed", "departures", "recipe"):
        assert sizes[key]
    for said in ("router_input", "activation", "biases", "qk_norm"):
        assert sizes["assumed"][said]
    # The cell is in every list it was appended to and reads the new
    # metric. (Nothing here pins the NUMBER of cells or which is last:
    # the next PR appends too, and an accepted test cannot be edited.)
    for metric in bench["per_layer"]:
        if metric["name"] in APPENDED + (NEW,):
            assert CELL in metric["workloads"], metric["name"]
    assert len(bench["workloads"]) >= 11 and len(bench["configs"]) >= 9


def test_the_file_holds_the_catalogs_config():
    """Every key of the catalog entry's ``config`` under the same key,
    unchanged but those the file lists as reduced; a per-layer list is
    cut to the kept layers, which are the published first four."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        entries = [json.loads(line) for line in f if line.strip()]
    (entry,) = [
        e for e in entries if e["name"] == "SmallThinker-21BA3B-Instruct"
    ]
    sizes = manifest.load_cell(CELL).sizes
    assert sizes["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key not in sizes["reduced"]:
            assert sizes[key] == value, key
        elif isinstance(value, list):
            assert sizes[key] == value[: sizes["num_hidden_layers"]], key
        else:
            assert sizes["published"][key] == value


def test_the_models_leaves_are_the_files_sum():
    """The model's own parameter tree at the published widths (shapes
    only) against the JSON's written-out arithmetic: 370 547 200."""
    import math

    import jax
    import jax.numpy as jnp

    from adaptdl_tpu.models.transformer import TransformerLM

    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    model = TransformerLM(config.model_config(cell.sizes))
    shapes = jax.eval_shape(
        lambda key: model.init(
            key, jnp.zeros((1, 128), jnp.int32), train=False
        )["params"],
        jax.random.key(0),
    )
    count = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes))
    mixer = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560
    layer = mixer + 2560 * 64 + 8 * 3 * 2560 * 768 + 2 * 2560
    assert (mixer, layer) == (20_971_520, 68_326_400)
    assert count == 4 * layer + 2 * 18_992 * 2560 + 2560 == 370_547_200
    assert "370 547 200" in cell.sizes["parameters"]["total"]
    assert "68 326 400" in cell.sizes["parameters"]["per_layer"]


def test_flops_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    parts = config.forward_flops_per_token(cell.sizes)
    assert parts["attention_projections"] == pytest.approx(167.8e6, rel=1e-3)
    assert parts["router"] == pytest.approx(1.31e6, rel=1e-2)
    assert parts["routed_experts"] == pytest.approx(
        4 * 0.75 * 2 * 3 * 2560 * 768
    )
    assert parts["full_attention"] == pytest.approx(117.4e6, rel=1e-3)
    # The three bands: 3584 keys a query on average where every causal
    # pair would be 8192.
    assert parts["sliding_attention"] == pytest.approx(154.1e6, rel=1e-3)
    assert window_attention.band_pairs(16384, 4096) == 58_722_304
    assert 16384 * 16385 // 2 == 134_225_920
    assert parts["head"] == pytest.approx(2 * 2560 * 18992)
    assert sum(parts.values()) == pytest.approx(573.3e6, rel=1e-3)
    assert config.train_flops_per_unit(cell.sizes) == 3 * sum(parts.values())
    assert config.units_per_sample(cell.sizes) == 16384
    # The program's own count agrees (router, bands, no dense FFN).
    from adaptdl_tpu.flops import transformer_train_flops

    own = transformer_train_flops(config.model_config(cell.sizes), 1, 16384)
    assert own.total / 16384 == pytest.approx(
        config.train_flops_per_unit(cell.sizes), rel=1e-6
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
        workload=CELL, seed=2**31 + 6060, seconds=2.0, trace=trace
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
        assert 20 < line["metrics"][NEW]["value"] < 80
        assert line["metrics"]["window_keys_visited_over_window"][
            "value"
        ] == pytest.approx(64 * 64 / window_attention.band_pairs(64, 24))
    else:
        assert set(line["metrics"]) == {m["name"] for m in group}
    reference = line["compared"]["reference"]
    assert reference["rows_dropped"] == 0
    assert reference["sliding_rms_err"] < 1e-5
    assert reference["full_rms_err"] < 1e-5
    assert reference["routed_on_grad_err"] < 1e-4
    assert reference["kernel_out_rms_err"] < 1e-5
    assert 0.2 < reference["hidden_zero_share"] < 0.8


def _trace(ops):
    """Two executions of one step program over ``ops`` (ns)."""
    modules = [
        Event("jit_step", 0, 50_000_000),
        Event("jit_step", 50_000_000, 100_000_000),
    ]
    return Trace([DevicePlane(0, ops, modules)], [], {})


def _band_calls(fwd_ns, bwd_ns):
    """A step's worth of the band kernels' calls, twice, back to back:
    3 sliding layers x 2 micro-batches, ONE call of the layer's 28
    heads on their 4 kv heads forward and one backward."""
    ops, at = [], 0
    for _step in range(2):
        for n in range(12):
            name, ns = (
                (f"window_attn_fwd.{n}", fwd_ns) if n < 6
                else (f"window_attn_bwd.{n}", bwd_ns)
            )
            ops.append(Event(CALL.format(name=name), at, at + ns))
            at += ns
    return ops


def test_the_window_readers_find_their_shapes_in_the_new_sizes():
    """``window_attn_roofline`` prices this cell's band from the
    derived keys: 28 heads on 4, 16 384 keys, a window of 4096; three
    sliding layers x two micro-batches; compute-bound."""
    record = _record()
    shape = window_attention.layer_shape(record)
    assert shape == dict(
        batch=1, heads=28, kv_heads=4, head_dim=128, seq_len=16384,
        window=4096,
    )
    assert window_attention.layer_passes(record) == 3 * 2
    fwd_s = window_attention.least_seconds(shape, False, PEAK)
    bwd_s = window_attention.least_seconds(shape, True, PEAK)
    assert fwd_s == 4.0 * 128 * 58_722_304 * 28 / 197e12
    assert bwd_s == 2.5 * fwd_s
    reader = _reader("window_attn_roofline")
    at_bound = _trace(_band_calls(round(fwd_s * 1e9), round(bwd_s * 1e9)))
    assert reader.read(at_bound, {}, record) == pytest.approx(100.0, rel=1e-3)
    assert _reader("window_attn_ms").read(
        at_bound, {}, record
    ) == pytest.approx(6e3 * (fwd_s + bwd_s), rel=1e-3)
    # The schedule's own count of what the pair multiplies: 952 blocks
    # of 256 x 256 a row and head, every one of them in the band.
    pairs = window_attention.band_pairs(16384, 4096)
    event = {"seq_len": 16384, "window": 4096, "batch_heads": 28,
             "keys_visited": 952 * 256 * 256, "keys_in_window": pairs}
    events = window_attention.keys_events(
        [{"name": "window.keys", "attrs": event},
         {"name": "window.keys", "attrs": {**event, "window": 512}}],
        record,
    )
    assert len(events) == 1
    assert _reader("window_keys_visited_over_window").read(
        None, {}, record, events
    ) == pytest.approx(1.0625, abs=1e-3)


def _load_event(rows_an_expert=3072, zero_share=0.5):
    """One whole step's ``moe.load``: four routed layers, eight held
    experts, two micro-batches of 16 384 x 6 assignments."""
    held = [[rows_an_expert] * 8 for _ in range(4)]
    return {
        "held_rows": held,
        "left_out": [2 * 16384 * 6 - 8 * rows_an_expert] * 4,
        "hidden_zero": [int(zero_share * 8 * rows_an_expert * 768)] * 4,
    }


def test_the_grouped_products_reader_finds_its_shapes_in_the_new_sizes():
    record = _record()
    snapshot = [
        {"name": "moe.load", "attrs": _load_event()},
        # A warm-up step of one micro-batch: not a whole step.
        {"name": "moe.load", "attrs": {
            **_load_event(), "left_out": [16384 * 6 - 8 * 3072] * 4,
        }},
    ]
    events = grouped_matmul.load_events(snapshot, record)
    assert len(events) == 1
    # 4 layers x 2 micro-batches x (3 + 3 recomputed + 3 input-gradient
    # ``moe_gmm`` + 3 ``moe_tgmm``), each over a micro-batch's 12 288
    # rows at widths 2560 x 768, at its least time.
    gmm_s = grouped_matmul.least_seconds(12288, 8, 2560, 768, 1, 0, PEAK)
    tgmm_s = grouped_matmul.least_seconds(12288, 8, 2560, 768, 0, 1, PEAK)
    call = (
        '%{name} = bf16[12288,768]{{1,0}} custom-call(bf16[12288,2560]{{1,0}} '
        '%x), custom_call_target="tpu_custom_call"'
    )
    ops, at = [], 0
    for _step in range(2):
        for n in range(4 * 2 * 12):
            name, s = (
                (f"moe_gmm.{n}", gmm_s) if n % 4 else (f"moe_tgmm.{n}", tgmm_s)
            )
            ops.append(Event(call.format(name=name), at, at + round(s * 1e9)))
            at += round(s * 1e9)
    trace = _trace(ops)
    assert _reader("moe_gmm_roofline").read(
        trace, {}, record, events
    ) == pytest.approx(100.0, rel=1e-3)
    assert _reader("moe_load_max_over_mean").read(
        None, {}, record, events
    ) == pytest.approx(1.0)


def test_hidden_zero_share_reads_a_recorded_snapshot():
    record = _record()
    reader = _reader(NEW)
    events = grouped_matmul.load_events(
        [{"name": "moe.load", "attrs": _load_event(zero_share=0.5)},
         {"name": "moe.load", "attrs": _load_event(zero_share=0.25)}],
        record,
    )
    assert reader.read(None, {}, record, events) == pytest.approx(37.5)
    # A program that gates with silu journals no such counter (nor does
    # a parent commit): nothing is read, and nothing raises.
    silu = _load_event()
    del silu["hidden_zero"]
    assert reader.read(None, {}, record, [silu]) is None
    assert reader.read(None, {}, record, []) is None
    assert reader.read(None, {}, {"sizes": {}, "geometry": {}}, []) is None
