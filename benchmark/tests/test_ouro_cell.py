"""The ouro-2.6b configuration and its cell (PR 42): the manifest
loads it, its job driver runs end to end on a shrunk copy on the CPU,
its FLOP count counts every block and the head once a pass, and the
two new readers read hand-made traces, the recorded v5e trace and a
journal — and nothing where there is nothing to read."""

import argparse
import json
import os

import pytest

from benchmark import manifest, xplane
from benchmark.xplane import DevicePlane, Event, Trace

ROOT = manifest.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ouro-2.6b-steady"
TINY = {
    "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 48, "vocab_size": 211,
    "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
    "sequence_length": 32, "head_chunk_columns": 64,
    "compute_dtype": "float32",
}
CALL = (
    '%{name} = (bf16[16,128,8192]{{2,1,0}}, f32[16,8,8192]{{2,1,0}}) '
    'custom-call(bf16[16,128,8192]{{2,1,0}} %q), '
    'custom_call_target="tpu_custom_call"'
)
RECORD = {
    "sizes": {"num_hidden_layers": 5, "total_ut_steps": 4},
    "geometry": {"atomic_bsz": 1, "accum_steps": 1, "global_batch": 2},
}


def _reader(name):
    return manifest.load_module(manifest.reader_path(ROOT, name))


def test_manifest_loads_the_cell():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "ouro-2.6b"
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "setup_s"
    }
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # The two new readers, and every accepted reader that lists no
    # cells (it has to be reported wherever its end-to-end metric is).
    everywhere = {
        m["name"] for m in bench["per_layer"] if "workloads" not in m
    }
    # ... and the three set-up spans that every cell lists
    # (``test_setup_readers.py`` holds that).
    assert {m["name"] for m in cell.per_layer} == everywhere | {
        "loop_attention_runs_per_layer", "loop_expected_exit",
        "restart_span_s", "state_init_s", "trace_lower_s",
    }
    sizes = cell.sizes
    # Every published width, the whole vocabulary, the stated cut.
    assert (
        sizes["hidden_size"], sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim"],
        sizes["intermediate_size"], sizes["vocab_size"],
        sizes["rms_norm_eps"], sizes["rope_theta"],
        sizes["total_ut_steps"], sizes["tie_word_embeddings"],
        sizes["max_position_embeddings"], sizes["early_exit_threshold"],
    ) == (2048, 16, 16, 128, 5632, 49152, 1e-6, 1000000, 4, False, 65536, 1)
    assert 4 <= sizes["num_hidden_layers"] <= 6
    assert sizes["layer_types"] == (
        ["full_attention"] * sizes["num_hidden_layers"]
    )
    assert sizes["published"]["num_hidden_layers"] == 48
    # The aliases the accepted flash roofline readers take a call's
    # shape from.
    assert sizes["n_embd"] // sizes["n_head"] == sizes["head_dim"]
    assert sizes["n_positions"] == sizes["sequence_length"] == 8192
    entry = {c["name"]: c for c in bench["configs"]}["ouro-2.6b"]
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"]) == [
        "layer_types", "num_hidden_layers"
    ]
    assert cell.workload["geometry"] == {
        "atomic_bsz": 1, "accum_steps": 1, "global_batch": 2
    }
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_flops_count_every_block_and_the_head_once_a_pass():
    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    sizes = dict(cell.sizes, num_hidden_layers=6)
    parts = config.forward_flops_per_token(sizes)
    # One application: 2 x (4 x 2048^2 + 3 x 2048 x 5632) + the causal
    # half of 2 x 2 x 8192 x 2048 = 136.3 MFLOP; 24 of them, 4 heads.
    application = (
        parts["attention_projections"] + parts["attention_scores"]
        + parts["ffn"]
    ) / 24
    assert application == pytest.approx(136.3e6, rel=1e-3)
    assert parts["attention_scores"] / 24 == pytest.approx(33.55e6, rel=1e-3)
    assert parts["head"] == 4 * 2 * 2048 * 49152
    assert sum(parts.values()) == pytest.approx(4.08e9, rel=2e-3)
    assert config.train_flops_per_unit(sizes) == pytest.approx(
        12.2e9, rel=3e-3
    )
    # One pass of the same sizes: a quarter of the blocks and the head.
    once = config.forward_flops_per_token(dict(sizes, total_ut_steps=1))
    assert sum(parts.values()) == pytest.approx(4 * sum(once.values()))
    # As the cell runs it.
    held = cell.sizes["num_hidden_layers"]
    assert config.train_flops_per_unit(cell.sizes) == pytest.approx(
        3 * (4 * held * application + parts["head"])
    )
    assert config.units_per_sample(cell.sizes) == 8192


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_cpu(trace, tmp_path, monkeypatch):
    """The steady job driver on a shrunk copy of the cell: correct
    (the reference comparisons (a) - (e) included), nothing failed,
    the line has the cell's metrics; on the CPU the flash kernel is
    interpreted, so the device-trace reader finds no Mosaic call and
    leaves its metric out, while the program counter reads."""
    from benchmark import run

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cell = manifest.load_cell(CELL)
    cell.platform = "cpu"
    cell.sizes.update(TINY)
    config = manifest.load_module(cell.config_py)
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
    reference = line["compared"]["reference"]
    assert reference["ok"] is True
    assert reference["gradient_tokens"] == config.GRADIENT_TOKENS
    assert reference["block_grad_err"] < 1e-4
    group = cell.per_layer if trace else cell.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in group}
    if trace:
        assert "loop_attention_runs_per_layer" not in line["metrics"]
        assert 1.0 < line["metrics"]["loop_expected_exit"]["value"] < 4.0
    else:
        assert set(line["metrics"]) == {m["name"] for m in group}


def _trace(ops):
    """Two executions of one step program over ``ops`` (ns)."""
    modules = [
        Event("jit_step", 0, 50_000_000),
        Event("jit_step", 50_000_000, 100_000_000),
    ]
    return Trace([DevicePlane(0, ops, modules)], [], {})


def _calls(forward, backward=40):
    """Two steps' worth of kernel calls back to back: ``forward`` of
    the forward kernel a step and ``backward`` of the backward's."""
    ops, at = [], 0
    for _step in range(2):
        for n in range(forward + backward):
            name = (
                "attention" if n < forward else "flash_bwd"
            ) + f".{n % 5}"
            ops.append(Event(CALL.format(name=name), at, at + 1000))
            at += 1000
    return ops


def test_attention_runs_per_layer_counts_the_forward_kernel():
    reader = _reader("loop_attention_runs_per_layer")
    # 5 layers x 2 micro-batches x 4 passes = 40 calls a step.
    assert reader.read(_trace(_calls(40)), {}, RECORD) == pytest.approx(4.0)
    # A remat'd application that re-ran its kernel in the backward.
    assert reader.read(_trace(_calls(80)), {}, RECORD) == pytest.approx(8.0)
    # A pass went missing.
    assert reader.read(_trace(_calls(30)), {}, RECORD) == pytest.approx(3.0)
    assert reader.PATTERN.pattern == _reader("flash_fwd_ms").PATTERN.pattern
    assert not reader.PATTERN.search(CALL.format(name="flash_bwd.3"))


def test_expected_exit_reads_whole_steps_of_the_journal():
    reader = _reader("loop_expected_exit")
    snapshot = [
        # The calibration program's single micro-batch: not a step.
        {"name": "loop.exit",
         "attrs": {"expected_exit": 1.5, "micro_batches": 1}},
        {"name": "loop.schedule", "attrs": {"passes": 4}},
        {"name": "loop.exit",
         "attrs": {"expected_exit": 4.2, "micro_batches": 2}},
        {"name": "loop.exit",
         "attrs": {"expected_exit": 5.0, "micro_batches": 2}},
    ]
    events = reader.exit_events(snapshot, RECORD)
    assert [e["expected_exit"] for e in events] == [4.2, 5.0]
    assert reader.read(None, {}, RECORD, events) == pytest.approx(2.3)


def test_readers_return_none_not_zero_when_nothing_matches():
    attention, exits = (
        _reader("loop_attention_runs_per_layer"),
        _reader("loop_expected_exit"),
    )
    other = _trace(
        [Event("%fusion.9 = bf16[8192,2048]{1,0} fusion(%x)", 0, 1000)]
    )
    assert attention.read(None, {}, RECORD) is None
    assert attention.read(other, {}, RECORD) is None
    # The recorded v5e trace (a data-parallel matmul, PR 22) has a step
    # program and no attention kernel.
    recorded = xplane.load(
        os.path.join(HERE, "data", "recorded_v5e.xplane.pb")
    )
    assert recorded.step_program() is not None
    assert attention.read(recorded, {}, RECORD) is None
    # A program that journalled no whole step (a parent commit).
    assert exits.read(None, {}, RECORD, []) is None
    assert exits.exit_events(
        [{"name": "moe.load", "attrs": {"micro_batches": 2}}], RECORD
    ) == []
