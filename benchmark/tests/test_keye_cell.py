"""The keye-vl-2.0-30b-a3b configuration and its cell (PR 34): the
manifest loads it, its job driver runs end to end on a shrunk copy on
the CPU, its FLOP count is the issue's arithmetic, ``benchmark/
sparse_attention.py`` counts by hand at one small shape, and the five
new readers read hand-made traces and journals — and nothing where
there is nothing to read."""

import argparse
import json
import os

import pytest

from benchmark import manifest, sparse_attention
from benchmark.xplane import DevicePlane, Event, Trace

ROOT = manifest.ROOT
CELL = "keye-vl-2.0-30b-a3b-steady"
TINY = {
    "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "router_width": 8,
    "experts_held": 2, "num_experts": 2, "num_local_experts": 2,
    "num_experts_per_tok": 2, "vocab_size": 211, "sequence_length": 64,
    "num_hidden_layers": 2, "compute_dtype": "float32",
    "sa_config": {
        "indexer_head_dim": 16, "indexer_num_heads": 3,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 16,
    },
}
CALL = (
    '%{name} = (bf16[1,32,128,16384]{{3,2,1,0}}, f32[1,32,1,16384]'
    '{{3,2,1,0}}) custom-call(bf16[1,32,16384,128]{{3,2,1,0}} %q), '
    'custom_call_target="tpu_custom_call"'
)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RECORD = {
    "peak_table": PEAK,
    "sizes": {
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "sequence_length": 16384,
        "sa_config": {
            "indexer_num_heads": 16, "indexer_head_dim": 64, "topk": 2048,
        },
    },
    "geometry": {"atomic_bsz": 1, "accum_steps": 1, "global_batch": 2},
}
ROW = 2048 * 2049 // 2 + 14336 * 2048  # pairs selected in one row


def _reader(name):
    return manifest.load_module(manifest.reader_path(ROOT, name))


def test_manifest_loads_the_cell():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "keye-vl-2.0-30b-a3b"
    names = {m["name"] for m in cell.per_layer}
    assert {
        "sparse_attn_ms", "sparse_attn_roofline", "indexer_ms",
        "indexer_roofline", "sparse_keys_visited_over_selected", "mfu",
        "step_device_ms", "device_idle_share", "peak_hbm_gib",
    } <= names
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "setup_s"
    }
    sizes = cell.sizes
    # Published widths, the router's width, and the stated cuts.
    assert (
        sizes["hidden_size"], sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim"],
        sizes["moe_intermediate_size"], sizes["router_width"],
        sizes["num_experts_per_tok"], sizes["rms_norm_eps"],
        sizes["rope_theta"], sizes["tie_word_embeddings"],
    ) == (2048, 32, 4, 128, 768, 128, 8, 1e-6, 10000000, False)
    assert sizes["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048,
    }
    assert sizes["experts_held"] == sizes["num_experts"] == 16
    assert sizes["vocab_size"] * 8 == sizes["published"]["vocab_size"]
    assert sizes["num_hidden_layers"] >= 4
    assert sizes["sequence_length"] == 16384
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["keye-vl-2.0-30b-a3b"]
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"])
    geometry = cell.workload["geometry"]
    assert (geometry["atomic_bsz"], geometry["global_batch"]) == (1, 2)


def test_flops_count_selected_pairs_not_causal_pairs():
    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    sizes = cell.sizes
    layers = sizes["num_hidden_layers"]
    parts = config.forward_flops_per_token(sizes)
    assert config.selected_pairs_per_row(16384, 2048) == ROW == 31_458_304
    # 23.4% of the 134.2 M causal pairs of a row.
    assert ROW / sparse_attention.causal_pairs(16384) == pytest.approx(
        0.2344, rel=1e-3
    )
    assert parts["attention_scores"] == pytest.approx(
        layers * 4 * 32 * 128 * ROW / 16384
    )
    assert parts["index_scores"] == pytest.approx(
        layers * 2 * 16 * 64 * 16385 / 2
    )
    # One expected held choice of the eight: 16 / 128 x 8.
    assert parts["routed_experts"] == pytest.approx(
        layers * 1.0 * 6 * 2048 * 768
    )
    assert parts["attention_projections"] == pytest.approx(
        layers * 2 * 2048 * (4096 + 1024 + 4096)
    )
    assert parts["head"] == pytest.approx(2 * 2048 * 18992)
    assert config.train_flops_per_unit(sizes) == pytest.approx(
        3 * sum(parts.values())
    )
    assert config.units_per_sample(sizes) == 16384


def test_counts_by_hand_at_a_small_shape():
    """4 queries, topk 2: pairs 1 + 2 + 2 + 2; 2 heads of 8."""
    assert sparse_attention.selected_pairs(4, 2) == 7
    assert sparse_attention.causal_pairs(4) == 10
    assert sparse_attention.selected_pairs(3, 8) == 6  # all earlier keys
    assert sparse_attention.attention_flops(7, 2, 8, 2) == 2 * 2 * 2 * 8 * 7
    assert sparse_attention.attention_flops(7, 2, 8, 5) == 2 * 5 * 2 * 8 * 7
    # q + out for 2 heads, k + v for 1: 6 rows of 8 x 2 bytes, + 2 lse.
    assert sparse_attention.attention_forward_bytes(4, 2, 1, 8) == 4 * (
        6 * 8 * 2 + 8
    )
    assert sparse_attention.attention_backward_bytes(4, 2, 1, 8) == 4 * (
        12 * 8 * 2 + 8
    )
    assert sparse_attention.index_flops(10, 3, 4) == 2 * 3 * 4 * 10
    assert sparse_attention.index_bytes(4, 3, 4) == 4 * (
        4 * 4 * 2 + 4 * 3 + 12
    )


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_cpu(trace, tmp_path, monkeypatch):
    """The steady job driver on a shrunk copy of the cell: correct
    (the seven reference comparisons included), nothing failed, the
    line has the cell's metrics; on the CPU the kernels are
    interpreted, so the four device-trace readers find no Mosaic call
    and leave their metrics out, while the program counter reads."""
    from benchmark import run

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cell = manifest.load_cell(CELL)
    cell.platform = "cpu"
    cell.sizes.update(TINY)
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
    assert line["device"]["platform"] == "cpu"
    group = cell.per_layer if trace else cell.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in group}
    if trace:
        for name in ("sparse_attn_ms", "sparse_attn_roofline",
                     "indexer_ms", "indexer_roofline"):
            assert name not in line["metrics"]
        # One tile covers the tiny row: 64 x 64 pairs multiplied for
        # 16 x 17 / 2 + 48 x 16 selected.
        assert line["metrics"]["sparse_keys_visited_over_selected"][
            "value"
        ] == pytest.approx(64 * 64 / (136 + 768))
    else:
        assert set(line["metrics"]) == {m["name"] for m in group}


def _trace(ops):
    """Two executions of one step program over ``ops`` (ns)."""
    modules = [
        Event("jit_step", 0, 3_000_000_000),
        Event("jit_step", 3_000_000_000, 6_000_000_000),
    ]
    return Trace([DevicePlane(0, ops, modules)], [], {})


KERNELS = (
    "sparse_attn_fwd", "sparse_attn_kl", "sparse_attn_bwd_q",
    "sparse_attn_bwd_kv",
)


def _calls(attn_ns, index_ns, layers=4, micro=2):
    """A step's worth of kernel calls, twice, back to back."""
    ops, at = [], 0
    for step in range(2):
        for n in range(layers * micro):
            for kernel, ns in [("sparse_index_select", index_ns)] + [
                (k, attn_ns) for k in KERNELS
            ]:
                ops.append(
                    Event(CALL.format(name=f"{kernel}.{n}"), at, at + ns)
                )
                at += ns
    ops.append(
        Event("%fusion.9 = bf16[16384,2048]{1,0} fusion(%x)", at, at + 5000)
    )
    return ops


def _select(layers=4, rows=2, visited_per_row=138_412_032):
    return {
        "queries": [rows * 16384] * layers,
        "keys_selected": [rows * ROW] * layers,
        "keys_visited": [rows * visited_per_row] * layers,
        "tied_queries": [0] * layers,
    }


def test_ms_readers_sum_their_own_kernels_per_step():
    trace = _trace(_calls(1_000_000, 250_000))
    assert _reader("sparse_attn_ms").read(trace, {}, RECORD) == (
        pytest.approx(8 * 4 * 1.0)
    )
    assert _reader("indexer_ms").read(trace, {}, RECORD) == pytest.approx(
        8 * 0.25
    )
    attn = _reader("sparse_attn_ms").PATTERN
    index = _reader("indexer_ms").PATTERN
    for kernel in KERNELS:
        assert attn.search(CALL.format(name=kernel + ".3"))
        assert not index.search(CALL.format(name=kernel + ".3"))
    assert attn.search(CALL.format(name="transpose_jvp_sparse_attn_bwd_q__.4"))
    assert index.search(CALL.format(name="sparse_index_select.7"))
    for other in ("sparse_index_select.7", "flash_bwd.3", "moe_gmm.2"):
        assert not attn.search(CALL.format(name=other))
    assert not index.search(CALL.format(name="moe_tgmm.3"))


def test_rooflines_count_the_models_work_and_cannot_pass_100():
    events = [_select()]
    # FLOP-bound: forward 2 and backward 5 matmuls of the SELECTED pairs.
    forward = 2 * 2 * 32 * 128 * ROW / 197e12
    assert forward == pytest.approx(2.616e-3, rel=1e-3)
    assert sparse_attention.attention_forward_bytes(
        16384, 32, 4, 128
    ) / 819e9 < forward
    least = 8 * 3.5 * forward  # 4 layers x 2 rows, forward + backward
    reader = _reader("sparse_attn_roofline")
    # Four kernels a layer and row, together exactly the bound: 100%.
    at_bound = _trace(_calls(round(3.5 * forward / 4 * 1e9), 1000))
    assert reader.read(at_bound, {}, RECORD, events) == pytest.approx(
        100.0, rel=1e-3
    )
    # A kernel that multiplies every causal pair at the same rate
    # takes 4.27 x as long and earns the same: 23.4%.
    dense = _trace(_calls(round(3.5 * forward / 4 * 4.2667 * 1e9), 1000))
    assert reader.read(dense, {}, RECORD, events) == pytest.approx(
        23.44, rel=2e-3
    )
    assert least == pytest.approx(0.07325, rel=1e-3)
    index = _reader("indexer_roofline")
    scores = 2 * 16 * 64 * sparse_attention.causal_pairs(16384) / 197e12
    assert scores == pytest.approx(1.395e-3, rel=1e-3)
    exact = _trace(_calls(1000, round(scores * 1e9)))
    assert index.read(exact, {}, RECORD, events) == pytest.approx(
        100.0, rel=1e-3
    )
    slow = _trace(_calls(1000, round(10 * scores * 1e9)))
    assert index.read(slow, {}, RECORD, events) == pytest.approx(
        10.0, rel=1e-3
    )


def test_visited_over_selected_reads_the_counters():
    reader = _reader("sparse_keys_visited_over_selected")
    assert reader.read(None, {}, {}, [_select()]) == pytest.approx(
        138_412_032 / ROW
    )
    exact = _select(visited_per_row=ROW)
    assert reader.read(None, {}, {}, [exact, exact]) == pytest.approx(1.0)
    assert reader.read(None, {}, {}, [_select(), exact]) == pytest.approx(
        (138_412_032 / ROW + 1.0) / 2
    )


def test_readers_return_none_not_zero_when_nothing_matches():
    other = _trace(
        [Event("%fusion.9 = bf16[16384,2048]{1,0} fusion(%x)", 0, 1000)]
    )
    events = [_select()]
    for name in ("sparse_attn_ms", "sparse_attn_roofline", "indexer_ms",
                 "indexer_roofline"):
        assert _reader(name).read(None, {}, RECORD) is None
        assert _reader(name).read(other, {}, RECORD) is None
    for name in ("sparse_attn_roofline", "indexer_roofline"):
        assert _reader(name).read(other, {}, RECORD, events) is None
        # The kernels ran but the program journalled no selection (a
        # parent commit).
        ran = _trace(_calls(100_000, 100_000))
        assert _reader(name).read(ran, {}, RECORD, []) is None
    assert _reader("sparse_keys_visited_over_selected").read(
        None, {}, {}, []
    ) is None
    assert sparse_attention.select_events(
        [{"name": "moe.schedule", "attrs": {}}], RECORD
    ) == []


def test_only_whole_steps_of_the_geometry_are_read():
    """A warm-up step before the loader adopts the pinned accumulation
    journals one micro-batch's queries: it is not a step of the cell."""
    whole, half = _select(), _select(rows=1)
    snapshot = [
        {"name": "sparse.select", "attrs": half},
        {"name": "sparse.schedule", "attrs": {}},
        {"name": "sparse.select", "attrs": whole},
    ]
    assert sparse_attention.select_events(snapshot, RECORD) == [whole]
    assert sparse_attention.select_events(snapshot, {}) == []
