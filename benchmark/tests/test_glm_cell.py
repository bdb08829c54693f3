"""The glm-4.7-flash configuration and its cell (PR 56): the manifest
loads it, its file holds the catalog's config, its job driver runs end
to end on a shrunk copy on the CPU (every reference comparison
included), its FLOP count is the issue's arithmetic with the prediction
module and the second head pass counted, the two new readers read
hand-made events — and nothing where there is nothing to read — and
each precision witness differs from the reference it stands beside."""

import argparse
import functools
import json
import os

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
CELL = "glm-4.7-flash-steady"
CONFIG = "glm-4.7-flash"
TINY = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 12, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 4, "v_head_dim": 16,
    "router_width": 16, "experts_held": 4, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "vocab_size": 211, "sequence_length": 64,
    "head_chunk_rows": 32, "compute_dtype": "float32",
}
NEW = ("mtp_loss_share", "head_rows_per_token")


def _record():
    cell = manifest.load_cell(CELL)
    return {
        "sizes": cell.sizes, "geometry": cell.workload["geometry"],
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
    # The seventeen that laguna-xs.2-steady lists from tokens_per_s to
    # moe_load_max_over_mean, and the two new ones.
    laguna = manifest.load_json(
        manifest.bench_path(ROOT, "workloads", "laguna-xs.2-steady.json")
    )["metrics"]
    upto = laguna.index("moe_load_max_over_mean") + 1
    assert cell.workload["metrics"] == laguna[:upto] + list(NEW)
    assert upto == 17
    sizes = cell.sizes
    # Every published width, the router's width, and the stated cuts.
    assert (
        sizes["hidden_size"], sizes["intermediate_size"],
        sizes["moe_intermediate_size"], sizes["num_attention_heads"],
        sizes["q_lora_rank"], sizes["kv_lora_rank"],
        sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
        sizes["v_head_dim"], sizes["num_experts_per_tok"],
        sizes["n_shared_experts"], sizes["routed_scaling_factor"],
        sizes["first_k_dense_replace"], sizes["num_nextn_predict_layers"],
        sizes["rope_theta"], sizes["rms_norm_eps"],
        sizes["tie_word_embeddings"],
    ) == (
        2048, 10240, 1536, 20, 768, 512, 192, 64, 256, 4, 1, 1.8, 1, 1,
        1000000, 1e-5, False,
    )
    assert sizes["router_width"] == sizes["published"]["n_routed_experts"] == 64
    assert sizes["experts_held"] == sizes["n_routed_experts"] == 8
    assert sizes["vocab_size"] * 8 == sizes["published"]["vocab_size"]
    assert sizes["published"]["num_hidden_layers"] == 47
    assert sizes["num_hidden_layers"] == 4
    assert sizes["sequence_length"] == 16384
    assert sizes["mtp_loss_weight"] == 0.1
    config = manifest.load_module(cell.config_py)
    assert config.routed_layers(sizes) == [1, 2, 3]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == sizes["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"
    ]
    assert set(sizes["published"]) == set(sizes["cuts"]) == set(
        sizes["reduced"]
    )
    assert entry["source"] == sizes["source"]
    for key in ("deployment", "assumed", "departures", "recipe",
                "parameters"):
        assert sizes[key]
    # Both sums of the cut, and every assumed value the issue lists.
    assert "599.7 M" in sizes["parameters"]["total"]
    assert "706.5 M" in sizes["parameters"]["total"]
    assert "706.5 M" in sizes["cuts"]["num_hidden_layers"]
    assert "8 chips" in sizes["deployment"]
    assert {
        "mtp_loss_weight", "mtp_concatenation", "mtp_trunk_state",
        "rotary", "expert_bias", "expert_weight_eps", "initialisers",
        "compute_dtype", "remat", "head_chunk_rows", "learning_rate",
    } <= set(sizes["assumed"])
    assert sizes["recipe"]["learning_rate"] == 2e-5
    assert sizes["recipe"]["precondition"] is None
    # The two new readers read this cell alone, one after the other,
    # and the cell is there (later PRs append cells and readers).
    for metric in bench["per_layer"]:
        if metric["name"] in NEW:
            assert metric["workloads"] == [CELL]
    assert CELL in [w["name"] for w in bench["workloads"]]
    readers = [m["name"] for m in bench["per_layer"]]
    at = readers.index(NEW[0])
    assert readers[at:at + len(NEW)] == list(NEW)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_file_holds_the_catalogs_config():
    """Every key of the catalog entry's ``config`` under the same key,
    unchanged but those the file lists as reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        entries = [json.loads(line) for line in f if line.strip()]
    (entry,) = [e for e in entries if e["name"] == "GLM-4.7-Flash"]
    sizes = manifest.load_cell(CELL).sizes
    assert sizes["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key not in sizes["reduced"]:
            assert sizes[key] == value, key
        else:
            assert sizes["published"][key] == value


def test_flops_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    parts = config.forward_flops_per_token(cell.sizes)
    # Five blocks' kernels at 16 384 keys: 5 x 167.8.
    assert parts["mla_attention"] == pytest.approx(5 * 167.77e6, rel=1e-4)
    assert parts["mla_projections"] == pytest.approx(
        5 * 2 * 21.758e6, rel=1e-4
    )
    assert parts["mtp_projection"] == pytest.approx(16.78e6, rel=1e-3)
    assert parts["head"] == pytest.approx(2 * 79.3e6, rel=1e-4)
    assert parts["dense_ffn"] == pytest.approx(2 * 3 * 2048 * 10240)
    assert parts["routed_experts"] == pytest.approx(4 * 9.437e6, rel=1e-4)
    assert parts["shared_experts"] == pytest.approx(4 * 18.874e6, rel=1e-4)
    assert sum(parts.values()) == pytest.approx(1472e6, rel=1e-3)
    assert config.train_flops_per_unit(cell.sizes) == 3 * sum(parts.values())
    assert config.units_per_sample(cell.sizes) == 16384
    # The program's own count agrees, the module and its head pass in.
    from adaptdl_tpu.flops import transformer_train_flops

    own = transformer_train_flops(config.model_config(cell.sizes), 1, 16384)
    assert own.total / 16384 == pytest.approx(
        config.train_flops_per_unit(cell.sizes), rel=1e-9
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
        workload=CELL, seed=2**31 + 5656, seconds=2.0, trace=trace
    )
    line = run.run_cell(cell, args)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    group = cell.per_layer if trace else cell.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in group}
    if trace:
        for name in ("flash_fwd_ms", "moe_gmm_ms", "moe_gmm_roofline"):
            assert name not in line["metrics"]
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
        assert line["metrics"]["head_rows_per_token"]["value"] == 2.0
        # Both losses near ln(211): 0.1 / 1.1.
        assert line["metrics"]["mtp_loss_share"]["value"] == pytest.approx(
            100 / 11, rel=0.1
        )
    else:
        assert set(line["metrics"]) == {m["name"] for m in group}
    reference = line["compared"]["reference"]
    assert reference["routers"] == 4
    assert reference["shared_rows_missing"] == 0
    for name in ("mla_rms_err", "mtp_rms_err", "routed_rms_err",
                 "embedding_table_grad_err", "head_table_grad_err",
                 "mtp_embedding_grad_err", "mtp_head_grad_err",
                 "mtp_trunk_grad_err", "mtp_leaf_grad_err",
                 "kernel_out_rms_err", "kernel_row_scale_err"):
        assert reference[name] < 1e-5, name
    # Each table's second use is in the whole model's gradient.
    for table in ("embedding", "head"):
        assert reference[f"{table}_second_use"] == pytest.approx(1.0, abs=1e-4)


def test_mtp_loss_share_reads_whole_steps_alone():
    record = _record()
    reader = _reader("mtp_loss_share")
    events = [
        {"name": "mtp.schedule", "attrs": {"loss_weight": 0.1}},
        # Two micro-batches summed: the cell's whole steps.
        {"name": "mtp.loss", "attrs": {
            "main": 2 * 9.8, "mtp": 2 * 9.9, "micro_batches": 2}},
        {"name": "mtp.loss", "attrs": {
            "main": 2 * 5.0, "mtp": 2 * 10.0, "micro_batches": 2}},
        # The calibration program's single micro-batch: left out.
        {"name": "mtp.loss", "attrs": {
            "main": 1.0, "mtp": 90.0, "micro_batches": 1}},
        {"name": "moe.load", "attrs": {"held_rows": [[1]]}},
    ]
    want = 100 * (0.99 / (9.8 + 0.99) + 1.0 / (5.0 + 1.0)) / 2
    assert reader.read(None, {}, record, events) == pytest.approx(want)
    # A module whose loss vanished reads 0, not nothing.
    gone = [events[0], {"name": "mtp.loss", "attrs": {
        "main": 19.6, "mtp": 0.0, "micro_batches": 2}}]
    assert reader.read(None, {}, record, gone) == 0.0
    assert reader.read(None, {}, record, []) is None
    assert reader.read(None, {}, record, events[1:]) is None  # no weight


def test_head_rows_per_token_reads_the_schedule_at_the_cells_shape():
    record = _record()
    reader = _reader("head_rows_per_token")
    ours = {"tokens": 16384, "head_rows": 32768, "rows": 16384,
            "head_calls": 1, "loss_weight": 0.1}
    events = [
        {"name": "mtp.schedule", "attrs": ours},
        {"name": "mtp.schedule", "attrs": ours},
        # Another shape's (a check's at another size): left out.
        {"name": "mtp.schedule", "attrs": {
            **ours, "tokens": 64, "head_rows": 64}},
        {"name": "mla.schedule", "attrs": {"seq_len": 16384}},
    ]
    assert reader.read(None, {}, record, events) == 2.0
    dropped = [{"name": "mtp.schedule", "attrs": {**ours, "head_rows": 16384}}]
    assert reader.read(None, {}, record, dropped) == 1.0
    assert reader.read(None, {}, record, []) is None
    assert reader.read(None, {}, {"geometry": {}, "sizes": {}}, events) is None


def test_a_program_without_the_events_reads_nothing():
    """A parent commit journals neither event: both readers, asked for
    THIS process's events (none of the cell's were journalled here),
    leave their metric out and do not raise."""
    record = _record()
    for name in NEW:
        assert _reader(name).read(None, {}, record) is None


# ---- the precision witnesses, on the CPU -----------------------------------


@functools.cache
def _witness_case():
    import jax
    import jax.numpy as jnp

    os.environ.setdefault("ADAPTDL_NUM_REPLICAS", "1")
    cell = manifest.load_cell(CELL)
    config = manifest.load_module(cell.config_py)
    sizes = {**cell.sizes, **TINY, "num_hidden_layers": 2,
             "sequence_length": 32}
    built = config.build(
        sizes, {"global_batch": 4, "atomic_bsz": 2, "accum_steps": 1}, 3
    )
    params = built["trainer"]._init_params
    data = config.make_dataset(sizes, 5, 2)
    sample = {k: jnp.asarray(v[:1]) for k, v in data.items()}
    weights = config.reference_weights(params, sizes)
    right = jax.jit(
        lambda w, s: config.reference_table_grads(
            w, s["inputs"], s["targets"], sizes
        )
    )(weights, sample)
    return config, sizes, built, params, sample, weights, right


@pytest.mark.parametrize("table", ["embedding", "head"])
def test_a_table_that_received_one_streams_gradient_fails(table):
    """Each shared table has two uses. The system's gradient of it is
    the reference's; a reference in which the module's use sends
    nothing back is refused by that table's limit."""
    import jax

    config, sizes, built, params, sample, weights, right = _witness_case()
    _, _, got = jax.jit(built["table_grads"])(
        params, sample, jax.random.key(0)
    )
    assert float(config.whole_error(got[table], right[2][table])) < 1e-4
    _, _, one_use = jax.jit(
        lambda w, s: config.reference_table_grads(
            w, s["inputs"], s["targets"], sizes, variant=f"{table}_one_use"
        )
    )(weights, sample)
    assert float(
        config.whole_error(one_use[table], right[2][table])
    ) > config.TABLE_GRAD_RTOL[table]
    other = "head" if table == "embedding" else "embedding"
    if table == "head":  # the lookup's gradient does not pass the head
        assert float(
            config.whole_error(one_use[other], right[2][other])
        ) < 1e-6


def test_dropping_the_modules_loss_is_refused_by_the_sum():
    import jax

    config, sizes, _, _, sample, weights, right = _witness_case()
    without = jax.jit(
        lambda w, s: config.reference_loss(
            w, s["inputs"], s["targets"], sizes, variant="no_mtp_loss"
        )[0]
    )(weights, sample)
    rel = abs(float(without) - float(right[0])) / float(right[0])
    assert rel > 100 * config.REFERENCE_RTOL


@pytest.mark.parametrize(
    "variant", ["bf16_angles", "no_rotary", "bf16_logits", "bf16_stat"]
)
def test_a_lower_precision_mixer_differs(variant):
    """What ``glm_precision.py`` reads on the chip is not a no-op."""
    import jax

    config, sizes, _, params, _, weights, _ = _witness_case()
    layer = weights["layers"][1]["mla"]
    u = jax.random.normal(jax.random.key(4), (1, 32, 32))
    sizes = {**sizes, "rope_theta": 100.0}
    want = config.reference_mixer(layer, u, sizes)
    low = config.reference_mixer(layer, u, sizes, variant)
    assert float(config.layer_error(low, want)[1]) > 1e-4


def test_a_lower_precision_router_and_head_differ():
    import jax
    import jax.numpy as jnp

    config, sizes, _, _, sample, weights, _ = _witness_case()
    layer = weights["mtp"]["block"]
    x = 4.0 * jax.random.normal(jax.random.key(5), (2048, 32))
    mismatch, _ = config.router_disagreement(
        config.reference_router(layer, x, sizes, "bf16_scores"),
        config.reference_router(layer, x, sizes),
    )
    assert float(mismatch) > config.ROUTER_SET_MISMATCH_SHARE
    unscaled = config.reference_router(layer, x, sizes, "no_scale")[1]
    scaled = config.reference_router(layer, x, sizes)[1]
    assert float(jnp.abs(scaled / unscaled - 1.8).max()) < 1e-5
    hidden = jax.random.normal(jax.random.key(6), (1, 32, 32)).astype(
        jnp.bfloat16
    )
    head = 4.0 * weights["head"]
    low = config.reference_head(hidden, head, sample["targets"], "bf16_loss")
    want = config.reference_head(hidden, head, sample["targets"])
    assert float(jnp.abs(low - want).max()) > config.HEAD_TOKEN_LOSS_ATOL
