"""The chip path, guarded without a chip.

1. Whole STEPS compiled by the TPU's own compiler for a DESCRIBED (not
   attached) v5e (``conftest.py``: ``v5e``, ``chip_compile``): the routed
   cells' steps, smallthinker-21b-a3b's step under the chip's memory
   limit, the reduce-overlap options and the dp=4 step that
   reduces under its last backward, the one-chip step without a tail. A
   compile that passes is not a chip run and says nothing about results
   or speed. (The kernels alone: ``tests/test_chip_compile_kernels.py``;
   the layers around them: ``tests/test_chip_compile_layers.py``.)
2. ``chip_smoke.py``'s two incarnations end to end on the CPU at a tiny
   size: wrong paths, arguments and control flow in the smoke are found
   here, not on the chip.
"""

import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
)

# ``import adaptdl_tpu.ops.flash_attention as m`` yields the FUNCTION
# (the package re-exports it under the module's name).
flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "cell", ["lfm2-8b-a1b-steady", "keye-vl-2.0-30b-a3b-steady"]
)
def test_routed_cells_steps_are_the_programs_before_the_ladder(
    v5e, chip_compile, monkeypatch, tmp_path, cell
):
    """Both routed cells at real size, on a described v5e that says
    what a 16 GB chip says (``bytes_limit`` 15.75 GiB) under a job
    with a checkpoint path: two copies of their state and gradient
    pass the limit, so the step DONATES, and a job that donates for
    want of memory has no bytes for the ladder — the program the
    trainer lowers is, text for text, the one lowered with no budget
    mechanism and no rung named at all (their traffic bypasses the
    ladder by the rule's own decision, not by their names)."""
    from adaptdl_tpu import trace
    from adaptdl_tpu import trainer as trainer_mod
    from adaptdl_tpu.models import transformer
    from tools import compile_step_v5e as rehearsal

    gmm = importlib.import_module("adaptdl_tpu.ops.grouped_matmul")
    monkeypatch.setattr(gmm, "_use_interpret", lambda: False)
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))

    def lowered(limit):
        rehearsal.inject_limit(limit, monkeypatch.setattr)
        lower, facts = rehearsal.step_program(
            cell, bytes_limit=rehearsal.BYTES_LIMIT, topo=v5e
        )
        assert facts["donated"]
        # (Without the serial numbers that lowering appends to the
        # names of private functions.)
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", lower().as_text())

    def no_mechanism():
        for mod in (flash_mod, transformer):
            monkeypatch.setattr(
                mod, "checkpoint_name",
                lambda x, name, keep=mod.checkpoint_name: x if name in (
                    flash_mod.SAVED_QKV, transformer.SAVED_QKV,
                    transformer.SAVED_MIXED, transformer.SAVED_FF_UP,
                ) else keep(x, name),
            )
        monkeypatch.setattr(
            trainer_mod.ElasticTrainer, "_activations", lambda self: None,
        )

    texts = []
    # (One call site for both: a Pallas kernel's serialized body holds
    # the Python stack it was traced under, line and column.)
    for limit, prepare in (
        (rehearsal.BYTES_LIMIT, lambda: None), (None, no_mechanism)
    ):
        prepare()
        before = len(trace.snapshot_spans())
        texts.append(lowered(limit))
        policies = [
            r["attrs"] for r in trace.snapshot_spans()[before:]
            if r["name"] == "remat.policy"
        ]
        assert policies and all(p["rungs"] == "" for p in policies)
    assert texts[0] == texts[1]


def test_smallthinker_step_fits_the_chip(
    v5e, chip_compile, monkeypatch, tmp_path
):
    """``smallthinker-21b-a3b-steady``'s step at real size as the
    trainer would run it on a 16 GB v5e (PR 60): 370.5 M parameters
    under the full AdaptDL recipe (``precondition`` "adam", 16 B a
    parameter of arguments) fit TWICE, so the step does not donate, and
    the compiler's count stays under the chip's 15.75 GiB (13.79 at PR
    60) — a later PR that pushes it over is told here and not by a chip
    call. The kernels under the names a device trace shows: the full
    layer's 28 heads in four runs of one kv head's group of SEVEN, the
    three sliding layers' bands (four K/V blocks before the tile's own)
    ONE call a layer."""
    from tools import compile_step_v5e as rehearsal

    gmm = importlib.import_module("adaptdl_tpu.ops.grouped_matmul")
    monkeypatch.setattr(gmm, "_use_interpret", lambda: False)
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    rehearsal.inject_limit(rehearsal.BYTES_LIMIT, monkeypatch.setattr)
    lower, facts = rehearsal.step_program(
        "smallthinker-21b-a3b-steady", bytes_limit=rehearsal.BYTES_LIMIT,
        topo=v5e,
    )
    assert not facts["donated"] and facts["chips"] == 1
    # (20 B a parameter, and a few scalars of state.)
    assert 0 <= facts["held_bytes"] - 20 * 370_547_200 < 4096
    compiled = lower().compile()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert total < rehearsal.BYTES_LIMIT, total / 2**30
    text = compiled.as_text()

    def calls(name):
        return len(re.findall(
            rf"^\s*%[\w\-]*{name}[\w\-]*[.\d]* = .*{flash_mod.MOSAIC_CALL}",
            text, re.M,
        ))

    assert calls(flash_mod.WINDOW_FWD_NAME) == 3
    assert calls(flash_mod.WINDOW_BWD_NAME) == 3
    assert calls(flash_mod.BWD_KERNEL_NAME) == 4
    assert len(re.findall(r"^\s*%attention[.\d]* = ", text, re.M)) == 4


def test_the_tpu_compiler_has_the_reduce_overlap_options(v5e, chip_compile):
    """The three (internal) names a step of several replicas is
    compiled under: this jaxlib's TPU compiler takes each of them, as
    it refuses a name it does not have. Were one dropped or renamed,
    every such step would fail to compile, jitted and AOT alike."""
    from adaptdl_tpu.trainer import REDUCE_OVERLAP_OPTIONS

    mesh = Mesh(np.array(v5e.devices), ("data",))
    mean = jax.shard_map(
        lambda x: jax.lax.pmean(x, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P(),
    )
    x = jax.ShapeDtypeStruct(
        (4, 128), jnp.float32, sharding=NamedSharding(mesh, P("data"))
    )
    assert len(REDUCE_OVERLAP_OPTIONS) == 3
    for name, value in REDUCE_OVERLAP_OPTIONS.items():
        jax.jit(mean, compiler_options={name: value}).lower(x).compile()
        with pytest.raises(Exception, match="No such compile option"):
            jax.jit(
                mean, compiler_options={name + "_": value}
            ).lower(x).compile()


def test_dp4_step_reduces_under_the_last_backward_on_v5e(
    v5e, chip_compile, monkeypatch, tmp_path
):
    """``gpt2-124m-dp4``'s step at real size on the described v5e:2x2
    with the chip's memory limit: the last micro-batch stands behind
    the accumulation loop and the model is traced ONCE for both (scan
    keeps its body's trace by the function); the compiled program
    holds both micro-batches' backwards in the entry computation (a loop of one
    trip is inlined), one after the other (no more memory than the
    all-in-scan step's), and the leaves' all-reduces run under the
    weight-gradient products the scheduler left for the end — the
    compiler writes such a pair as an ``async-collective-start`` /
    ``-done`` fusion around the op it runs under — the first of them
    before the last micro-batch's backward has ended."""
    from adaptdl_tpu import trace
    from adaptdl_tpu import trainer as trainer_mod
    from tools import compile_step_v5e as rehearsal

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    rehearsal.inject_limit(rehearsal.BYTES_LIMIT, monkeypatch.setattr)
    calls = []

    def counting(loss_fn, differentiate=trainer_mod._value_and_grad):
        def counted(*args):
            calls.append(1)
            return loss_fn(*args)

        return differentiate(counted)

    monkeypatch.setattr(trainer_mod, "_value_and_grad", counting)
    lower, facts = rehearsal.step_program(
        "gpt2-124m-dp4", bytes_limit=rehearsal.BYTES_LIMIT, topo=v5e
    )
    assert facts["chips"] == 4 and not facts["donated"]
    lowered = lower()
    assert len(calls) == 1
    (event,) = [
        r["attrs"] for r in trace.snapshot_spans()
        if r["name"] == "step.reduce_overlap"
    ]
    assert event == {
        "replicas": 4, "num_micro": 2, "scanned": 1, "tail": True,
        "groups": 74,
    }
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # (The all-in-scan step: 7.48 GiB of temporaries; both
    # micro-batches' activations alive at once: 10.24.)
    assert mem.temp_size_in_bytes < 8 * 2**30
    lines = rehearsal.entry_computation(compiled.as_text()).split("\n")
    backward = [
        i for i, line in enumerate(lines)
        if re.match(r"\s*%flash_bwd[.\d]* = ", line)
    ]
    started = [
        i for i, line in enumerate(lines)
        if re.match(rehearsal.ASYNC_START, line)
    ]
    assert len(backward) == 24
    # (48 matrices a step; the 25 LayerNorm vectors, 3 KB each, and
    # the tied table, whose gradient ends with the backward, stay
    # synchronous.)
    assert len(started) >= 40
    assert started[0] < backward[-1]
    for start in started:
        under = [
            line for line in lines[start:start + 120]
            if "calls=%async_collective_fusion" in line
        ]
        assert under and "dot_general" in under[0]


def test_one_chip_step_is_the_program_with_no_tail(
    v5e, chip_compile, monkeypatch, tmp_path
):
    """``gpt2-124m-steady``'s step on one described chip: one replica
    has no reduce to overlap, and the program the trainer lowers is,
    text for text, the one lowered with the tail (and with it its
    compiler options) switched off in the trainer."""
    from adaptdl_tpu import trace
    from adaptdl_tpu import trainer as trainer_mod
    from tools import compile_step_v5e as rehearsal

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    rehearsal.inject_limit(rehearsal.BYTES_LIMIT, monkeypatch.setattr)

    def lowered():
        lower, facts = rehearsal.step_program(
            "gpt2-124m-steady", bytes_limit=rehearsal.BYTES_LIMIT, topo=v5e
        )
        assert facts["chips"] == 1
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", lower().as_text())

    def no_tail():
        monkeypatch.setattr(
            trainer_mod.ElasticTrainer, "_reduce_has_tail",
            lambda self: False,
        )

    texts = []
    for prepare in (lambda: None, no_tail):
        prepare()
        texts.append(lowered())
    assert texts[0] == texts[1]
    events = [
        r["attrs"] for r in trace.snapshot_spans()
        if r["name"] == "step.reduce_overlap"
    ]
    assert [e["tail"] for e in events[:1]] == [False]


def test_chip_smoke_incarnations_on_cpu(tmp_path, monkeypatch):
    """The smoke's two incarnations (children of this process, which
    holds no chip) with a tiny ``TransformerConfig`` and the expected
    platform passed as function arguments — the command line exposes
    neither. Everything ``chip_smoke.py`` asserts on the chip except
    the Mosaic call and the memory reading is asserted here too:
    metrics pull, goodput fit, re-optimisation, two step programs (one
    accumulated), exit 143 with a complete manifest, resume at the
    same step / position / batch configuration with the loss inside
    the band, first step of incarnation 1 from a warm cache. (How long
    a child may take is the smoke's own limit, which guards a chip call
    against a hang: what this test asserts is what the children did,
    under whatever load the other workers put on the machine.)"""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    from adaptdl_tpu.models import TransformerConfig

    # A throw-away cache for the children instead of the checkout's.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("ADAPTDL_COMPILE_CACHE", str(tmp_path / "cc"))
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one CPU device
    tiny = TransformerConfig(
        vocab_size=128, num_layers=1, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=16, dtype=jnp.float32, remat=True,
    )
    device = chip_smoke.run_elastic_loop(
        config=tiny,
        expect_platform="cpu",
        sequences=4096,
        ready_after=52,
        steps=4,
    )
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert any((tmp_path / "cc" / ".jax_compile_cache").iterdir())
