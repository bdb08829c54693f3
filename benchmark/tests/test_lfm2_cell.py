"""The lfm2-8b-a1b configuration and its cell (PR 30): the manifest
loads it, its job driver runs end to end on a shrunk copy on the CPU,
its FLOP count is the issue's arithmetic, and the three new readers
read hand-made traces and journals — and nothing where there is
nothing to read."""

import argparse
import json
import os

import pytest

from benchmark import grouped_matmul, manifest
from benchmark.xplane import DevicePlane, Event, Trace

ROOT = manifest.ROOT
CELL = "lfm2-8b-a1b-steady"
TINY = {
    "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 8, "experts_held": 2,
    "num_experts_per_tok": 2, "vocab_size": 211, "sequence_length": 32,
    "compute_dtype": "float32",
}
CALL = (
    '%{name} = bf16[69632,1792]{{1,0}} custom-call(s32[136]{{0}} %te, '
    'bf16[69632,2048]{{1,0}} %x), custom_call_target="tpu_custom_call"'
)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RECORD = {
    "peak_table": PEAK,
    "sizes": {
        "hidden_size": 2048, "moe_intermediate_size": 1792,
        "sequence_length": 8192, "num_experts_per_tok": 4,
    },
    "geometry": {"atomic_bsz": 2, "accum_steps": 1, "global_batch": 4},
}


def _reader(name):
    return manifest.load_module(manifest.reader_path(ROOT, name))


def test_manifest_loads_the_cell():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "lfm2-8b-a1b"
    names = {m["name"] for m in cell.per_layer}
    assert {
        "moe_gmm_ms", "moe_gmm_roofline", "moe_load_max_over_mean",
        "mfu", "step_device_ms", "device_idle_share", "peak_hbm_gib",
    } <= names
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "setup_s"
    }
    sizes = cell.sizes
    # Published widths, the router's width, and the stated cuts.
    assert (
        sizes["hidden_size"], sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["intermediate_size"],
        sizes["moe_intermediate_size"], sizes["num_experts"],
        sizes["num_experts_per_tok"], sizes["conv_L_cache"],
        sizes["norm_eps"], sizes["rope_theta"],
    ) == (2048, 32, 8, 7168, 1792, 32, 4, 3, 1e-5, 1000000)
    assert sizes["experts_held"] == 8 and sizes["vocab_size"] == 16384
    assert sizes["layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv"
    ]
    assert sizes["published"]["layer_types"][1:6] == sizes["layer_types"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["lfm2-8b-a1b"]
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"])


def test_flops_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    parts = config.forward_flops_per_token(cell.sizes)
    assert sum(parts.values()) == pytest.approx(432.5e6, rel=2e-3)
    assert parts["routed_experts"] == pytest.approx(88.08e6, rel=1e-3)
    assert config.train_flops_per_unit(cell.sizes) == pytest.approx(
        1.2976e9, rel=1e-3
    )
    assert config.units_per_sample(cell.sizes) == 8192


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_cpu(trace, tmp_path, monkeypatch):
    """The steady job driver on a shrunk copy of the cell: correct
    (the six reference comparisons included), nothing failed, the
    line has the cell's metrics; on the CPU the kernels are
    interpreted, so the two device-trace readers find no Mosaic call
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
        assert "moe_gmm_ms" not in line["metrics"]
        assert "moe_gmm_roofline" not in line["metrics"]
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    else:
        assert set(line["metrics"]) == {m["name"] for m in group}


def _trace(ops):
    """Two executions of one step program over ``ops`` (ns)."""
    modules = [
        Event("jit_step", 0, 50_000_000),
        Event("jit_step", 50_000_000, 100_000_000),
    ]
    return Trace([DevicePlane(0, ops, modules)], [], {})


def _calls(duration_ns, gmm=72, tgmm=24):
    """A step's worth of grouped-product calls, twice, back to back."""
    ops, at = [], 0
    for step in range(2):
        for n in range(gmm + tgmm):
            name = ("moe_gmm" if n < gmm else "moe_tgmm") + f".{n}"
            ops.append(Event(CALL.format(name=name), at, at + duration_ns))
            at += duration_ns
    ops.append(
        Event("%fusion.9 = bf16[16384,2048]{1,0} fusion(%x)", at, at + 5000)
    )
    return ops


def test_gmm_ms_sums_both_kernels_per_step():
    trace = _trace(_calls(100_000))
    assert _reader("moe_gmm_ms").read(trace, {}, RECORD) == pytest.approx(
        96 * 0.1
    )
    pattern = _reader("moe_gmm_ms").PATTERN
    assert pattern.search(CALL.format(name="moe_tgmm.3"))
    assert pattern.search(CALL.format(name="transpose_jvp_moe_gmm__.4"))
    assert not pattern.search(CALL.format(name="flash_bwd.3"))
    assert not pattern.search(CALL.format(name="attention.7"))
    roofline = _reader("moe_gmm_roofline")
    assert not roofline.GMM.search(CALL.format(name="moe_tgmm.3"))
    assert not roofline.TGMM.search(CALL.format(name="moe_gmm.3"))


def _load(rows_per_expert, layers=4):
    return {
        "held_rows": [list(rows_per_expert) for _ in range(layers)],
        "left_out": [0] * layers,
        "dropped": [0] * layers,
    }


def test_roofline_counts_routed_rows_and_cannot_pass_100():
    # 8 experts x 4096 rows a STEP and layer = 2048 a micro-batch.
    events = [_load([4096] * 8)]
    flops_s = grouped_matmul.product_flops(16384, 2048, 1792) / 197e12
    assert flops_s == pytest.approx(0.6104e-3, rel=1e-3)
    # FLOP-bound at these widths: bytes take a third of that.
    assert grouped_matmul.gmm_bytes(16384, 8, 2048, 1792) / 819e9 < flops_s
    assert grouped_matmul.tgmm_bytes(16384, 8, 2048, 1792) / 819e9 < flops_s
    reader = _reader("moe_gmm_roofline")
    # Calls exactly as long as the FLOP bound: 100%.
    at_bound = _trace(_calls(round(flops_s * 1e9)))
    assert reader.read(at_bound, {}, RECORD, events) == pytest.approx(
        100.0, rel=1e-3
    )
    # Twice as long: 50%. Half the rows in the same time: 25%.
    slow = _trace(_calls(round(2 * flops_s * 1e9)))
    assert reader.read(slow, {}, RECORD, events) == pytest.approx(
        50.0, rel=1e-3
    )
    assert reader.read(slow, {}, RECORD, [_load([2048] * 8)]) == (
        pytest.approx(25.0, rel=1e-3)
    )
    # Imbalance does not change the rows' sum, nor the bound.
    skewed = [_load([16384, 8192, 4096, 2048, 1024, 512, 256, 256])]
    assert reader.read(slow, {}, RECORD, skewed) == pytest.approx(
        50.0, rel=1e-3
    )


def test_load_reader_takes_the_worst_layer():
    reader = _reader("moe_load_max_over_mean")
    balanced = _load([100] * 8)
    assert reader.read(None, {}, {}, [balanced]) == pytest.approx(1.0)
    skew = _load([100] * 8)
    skew["held_rows"][2] = [450, 50, 50, 50, 50, 50, 50, 50]
    assert reader.read(None, {}, {}, [skew]) == pytest.approx(4.5)
    assert reader.read(None, {}, {}, [skew, balanced]) == pytest.approx(2.75)


def test_readers_return_none_not_zero_when_nothing_matches():
    other = _trace(
        [Event("%fusion.9 = bf16[16384,2048]{1,0} fusion(%x)", 0, 1000)]
    )
    events = [_load([4096] * 8)]
    for name in ("moe_gmm_ms", "moe_gmm_roofline"):
        assert _reader(name).read(None, {}, RECORD) is None
    assert _reader("moe_gmm_ms").read(other, {}, RECORD) is None
    assert _reader("moe_gmm_roofline").read(other, {}, RECORD, events) is None
    # The kernels ran but the program journalled no load (a parent).
    ran = _trace(_calls(100_000))
    assert _reader("moe_gmm_roofline").read(ran, {}, RECORD, []) is None
    assert _reader("moe_load_max_over_mean").read(None, {}, {}, []) is None
    # In this process nothing journalled moe.load: the readers' own
    # look into the program's trace buffer finds nothing either.
    assert grouped_matmul.load_events(
        [{"name": "flash.schedule", "attrs": {}}], RECORD
    ) == []


def test_only_whole_steps_of_the_geometry_are_read():
    """A warm-up step before the loader adopts the pinned accumulation
    journals one micro-batch's rows: it is not a step of the cell."""
    whole = _load([4096] * 8)
    whole["left_out"] = [4 * 8192 * 4 - 8 * 4096] * 4
    half = _load([2048] * 8)
    half["left_out"] = [2 * 8192 * 4 - 8 * 2048] * 4
    snapshot = [
        {"name": "moe.load", "attrs": half},
        {"name": "moe.schedule", "attrs": {}},
        {"name": "moe.load", "attrs": whole},
    ]
    assert grouped_matmul.load_events(snapshot, RECORD) == [whole]
    assert grouped_matmul.load_events(snapshot, {}) == []
