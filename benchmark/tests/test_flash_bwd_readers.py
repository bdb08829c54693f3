"""The readers of the flash attention BACKWARD kernel (PR 26):
``flash_bwd_ms`` and ``flash_bwd_roofline`` on hand-made traces, their
FLOP and byte functions, and both through the harness on the CPU.

Since PR 39 the three ``gpt2-124m`` cells list both. On the CPU the
kernel is interpreted, so there is no Mosaic call to find: the readers
must return nothing and the line must leave the two out, which is
also what they do on a parent without the kernel.
"""

import argparse
import json
import os

import pytest

from benchmark import attention_backward, flops, manifest
from benchmark.xplane import DevicePlane, Event, Trace

ROOT = manifest.ROOT
NAMES = ["flash_bwd_ms", "flash_bwd_roofline"]
CALL = (
    '%{name} = ({shape}) custom-call(bf16[192,1024,64]{{2,1,0}} %p), '
    'custom_call_target="tpu_custom_call"'
)
FWD = CALL.format(name="attention.7", shape="bf16[192,1024,64]{2,1,0}")
BWD = CALL.format(name="flash_bwd.3", shape="bf16[1,192,1024,64]{3,2,1,0}")
RECORD = {
    "peak_table": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "sizes": {"n_head": 12, "n_positions": 1024, "n_embd": 768},
    "geometry": {"atomic_bsz": 16},
}


def _reader(name):
    return manifest.load_module(manifest.reader_path(ROOT, name))


def _trace(ops):
    """Two executions of one step program over ``ops`` (ns)."""
    modules = [Event("jit_step", 0, 5_000_000), Event("jit_step", 5_000_000, 10_000_000)]
    return Trace([DevicePlane(0, ops, modules)], [], {})


def test_backward_readers_read_only_the_backward_kernel():
    ms = 1_000_000  # ns
    ops = [
        Event(FWD, 0, 400_000),
        Event(BWD, 1 * ms, 2 * ms),
        Event("%fusion.9 = f32[16,12,1024,64]{3,2,1,0} fusion(%x)", 2 * ms, 3 * ms),
        # As the kernel is named where no scope surrounds the call.
        Event(
            BWD.replace("flash_bwd.3", "transpose_jvp_flash_bwd__.4"),
            6 * ms, 8 * ms,
        ),
        Event(FWD.replace("attention.7", "attention.8"), 8 * ms, 8 * ms + 400_000),
    ]
    trace = _trace(ops)
    # 3 ms of backward kernel over two executions of the step.
    assert _reader("flash_bwd_ms").read(trace, {}, RECORD) == pytest.approx(1.5)
    # Mean call 1.5 ms against the FLOP bound of 0.327 ms.
    least = attention_backward.attention_backward_flops(192, 1024, 64) / 197e12
    assert least == pytest.approx(0.327e-3, rel=1e-3)
    assert _reader("flash_bwd_roofline").read(
        trace, {}, RECORD
    ) == pytest.approx(100 * least / 1.5e-3)
    # The forward's readers still read the forward alone: 0.8 ms over
    # two executions, whatever the backward kernel takes.
    assert _reader("flash_fwd_ms").read(trace, {}, RECORD) == pytest.approx(0.4)
    for name in ("flash_fwd_ms", "flash_fwd_roofline"):
        assert not _reader(name).PATTERN.search(BWD)
    for name in NAMES:
        assert not _reader(name).PATTERN.search(FWD)


@pytest.mark.parametrize("name", NAMES)
def test_backward_readers_find_nothing_without_the_kernel(name):
    """No trace (an untraced run), no peak row, or a program whose
    backward is not this kernel (the parent's scan): nothing, and no
    exception."""
    read = _reader(name).read
    assert read(None, {}, RECORD) is None
    parent = _trace([
        Event(FWD, 0, 400_000),
        Event("%fusion.9 = f32[16,12,1024,64]{3,2,1,0} fusion(%x)", 500_000, 900_000),
    ])
    assert read(parent, {}, RECORD) is None
    assert read(_trace([]), {}, {**RECORD, "peak_table": None}) is None


@pytest.mark.parametrize("name", NAMES)
def test_backward_readers_declare_a_device_trace_of_the_kernels(name):
    reader = _reader(name)
    assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
        "kernels", "device_trace", "tokens_per_s"
    )
    assert reader.UNIT == {"flash_bwd_ms": "ms", "flash_bwd_roofline": "%"}[name]


@pytest.mark.parametrize("causal", [True, False])
def test_backward_operations_and_bytes(causal):
    shape = dict(batch_heads=192, seq_len=1024, head_dim=64)
    # Five matmuls to the forward's two.
    assert attention_backward.attention_backward_flops(
        **shape, causal=causal
    ) == 2.5 * flops.attention_forward_flops(**shape, causal=causal)
    # q, k, v, o, do in and dq, dk, dv out in bf16, lse in float32.
    assert attention_backward.attention_backward_bytes(**shape) == (
        8 * 192 * 1024 * 64 * 2 + 192 * 1024 * 4
    )
    assert attention_backward.attention_backward_bytes(
        **shape, itemsize=4
    ) == 8 * 192 * 1024 * 64 * 4 + 192 * 1024 * 4


GPT2_CELLS = ["gpt2-124m-steady", "gpt2-124m-rescale", "gpt2-124m-dp4"]


def test_the_cells_that_list_them():
    """The three ``gpt2-124m`` cells report both; ``lfm2-8b-a1b-steady``
    runs the kernel too (through GQA, at 8192 keys) and lists the
    time, not the roofline share, whose reader takes ``n_head`` and
    ``n_positions`` from sizes that configuration does not have."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {
            m["name"]: m["workloads"]
            for m in json.load(f)["per_layer"] if m["name"] in NAMES
        }
    assert listed["flash_bwd_roofline"] == GPT2_CELLS
    assert listed["flash_bwd_ms"] == GPT2_CELLS + ["lfm2-8b-a1b-steady"]
    for name in NAMES:
        for cell_name in listed[name]:
            cell = manifest.load_cell(cell_name)
            assert name in {m["name"] for m in cell.per_layer}


def test_traced_rehearsal_leaves_the_backward_metrics_out_on_the_cpu(
    tmp_path, monkeypatch
):
    """The steady job at a tiny size on the CPU: the traced line is ``correct`` and carries neither (no
    device trace, no Mosaic call), as on a parent without the
    kernel. A number from a CPU run is never a device metric."""
    import rehearse

    from benchmark import run

    work = tmp_path / "tmp"
    work.mkdir()
    monkeypatch.setenv("TMPDIR", str(work))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
    )
    cell = manifest.load_cell("gpt2-124m-steady")
    assert set(NAMES) <= {m["name"] for m in cell.per_layer}
    rehearse.shrink(cell)
    args = argparse.Namespace(
        workload="gpt2-124m-steady", seed=2600000001, seconds=2.0, trace=1
    )
    line = run.run_cell(cell, args, root=ROOT)
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert not set(NAMES) & set(line["metrics"])
    assert "flash_fwd_ms" not in line["metrics"]  # for the same reason
