"""The chip path, guarded without a chip: LAYERS compiled by the TPU's
own compiler for a DESCRIBED (not attached) v5e (``conftest.py``:
``v5e``, ``chip_compile``) at the cells' real widths — the routed layer
walking its bounded buffer, glm-4.7-flash's mixer and pieces, lfm2's
routers in the check and in the system, a block's projections, what
remat and its ladder keep. A compile that passes is not a chip run and
says nothing about results or speed. (The kernels alone:
``tests/test_chip_compile_kernels.py``; whole steps and the smoke:
``tests/test_chip_compile.py``.)"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

# ``import adaptdl_tpu.ops.flash_attention as m`` yields the FUNCTION
# (the package re-exports it under the module's name).
flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")


CELL = (16, 12, 1024, 64)  # the benchmark's gpt2-124m micro-batch


@pytest.mark.parametrize(
    "held, total, top_k, d_expert, router, bound, capacity",
    [
        (8, 32, 4, 1792, "sigmoid", 45056, 69632),  # lfm2-8b-a1b
        # keye-vl-2.0-30b-a3b: the rest would be longer than the bound,
        # so one pass over the worst case and no loop.
        (16, 128, 8, 768, "softmax", 139264, 139264),
    ],
)
def test_routed_layer_walks_the_bounded_buffer_on_v5e(
    v5e, chip_compile, monkeypatch, held, total, top_k, d_expert, router,
    bound, capacity,
):
    """A routed layer of either cell and its gradients, 16 384 tokens
    of 2048 in bf16. Where the layer bounds its buffer, the glue XLA
    generates has ``rows_bound`` rows in the usual pass and the plan's
    other rows in the loop that runs where the plan passes the bound,
    no array of the worst case's length is left in the program (the
    int32 row plan apart), and the program's temporaries are under the
    1.37 GiB the worst-case buffer took; the grouped products are in
    every pass under their names."""
    gmm = importlib.import_module("adaptdl_tpu.ops.grouped_matmul")
    moe = importlib.import_module("adaptdl_tpu.models.moe")
    monkeypatch.setattr(gmm, "_use_interpret", lambda: False)
    one = SingleDeviceSharding(v5e.devices[0])
    tokens, d = 16384, 2048
    assert moe.rows_bound(tokens, top_k, held, total, 512) == bound
    assert moe.rows_capacity(tokens, top_k, held, 512) == capacity

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(x, router_w, w_gate, w_up, w_down):
        y, load = moe.routed_experts(
            x, router_w,
            jnp.zeros((total,)) if router == "sigmoid" else None,
            w_gate, w_up, w_down, experts_total=total, first_expert=0,
            top_k=top_k, router_kind=router,
        )
        return y.astype(jnp.float32).sum(), load["fell_back"]

    compiled = jax.jit(
        jax.grad(loss, argnums=tuple(range(5)), has_aux=True)
    ).lower(
        arg((tokens, d), jnp.bfloat16), arg((d, total)),
        arg((held, d, d_expert)), arg((held, d, d_expert)),
        arg((held, d_expert, d)),
    ).compile()
    text = compiled.as_text()
    assert " conditional(" not in text
    assert bound == capacity or " while(" in text
    for rows in {bound, capacity - bound} - {0}:
        assert re.search(rf"(bf16|f32)\[{rows},", text), rows
    if bound < capacity:
        assert not re.search(rf"(bf16|f32)\[{capacity},", text)
    # Forward and both transposes, of each pass.
    passes = 2 if bound < capacity else 1
    assert text.count(f"%{gmm.GMM_KERNEL_NAME}") >= 6 * passes
    assert text.count(f"%{gmm.TGMM_KERNEL_NAME}") >= 3 * passes
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1.25 if bound < capacity else 2.5
    ) * 2**30


def test_glm_mixer_and_pieces_compile_for_v5e(v5e, chip_compile, monkeypatch):
    """glm-4.7-flash's two shapes no other cell has (PR 56). (1) Its
    latent-attention mixer and gradients at the cell's widths on one
    row of 16 384 (20 heads of q / k 256 of which 64 lanes are rotated,
    v 256, the query bottleneck of 768): the heads go two a call, ten
    forward and ten backward kernels under the names a device trace
    shows, and ``mla.schedule`` says so. (2) A routed layer of 8 held
    of 64, top 4, width 1536, told ``pieces_from`` 2.5 as the
    configuration tells it: the plan is walked in pieces of the bound
    (24 576 rows), no array of the worst case's 69 632 is left (the
    int32 row plan apart), where the layer's own threshold would keep
    the one pass."""
    import functools

    from adaptdl_tpu import trace
    from adaptdl_tpu.models.transformer import (
        LatentAttention,
        TransformerConfig,
    )

    gmm = importlib.import_module("adaptdl_tpu.ops.grouped_matmul")
    moe = importlib.import_module("adaptdl_tpu.models.moe")
    monkeypatch.setattr(gmm, "_use_interpret", lambda: False)
    one = SingleDeviceSharding(v5e.devices[0])

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = TransformerConfig(
        vocab_size=19360, num_layers=1, num_heads=20, d_model=2048,
        d_ff=10240, dtype=jnp.bfloat16, norm="rmsnorm", norm_eps=1e-5,
        rope=True, rope_theta=1e6, layer_types=("mla",), q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256,
        attention_fn=functools.partial(
            flash_mod.flash_attention, block_q=128, block_k=128
        ),
    )
    mixer = LatentAttention(cfg)
    x = arg((1, 16384, 2048), jnp.bfloat16)
    params = jax.tree.map(
        lambda leaf: arg(leaf.shape, leaf.dtype),
        jax.eval_shape(
            lambda: mixer.init(
                jax.random.key(0), jnp.zeros((1, 128, 2048), jnp.bfloat16),
                jnp.arange(128),
            )["params"]
        ),
    )

    def mixed(params, x):
        out = mixer.apply({"params": params}, x, jnp.arange(16384))
        return out.astype(jnp.float32).sum()

    since = len(trace.snapshot_spans())
    text = jax.jit(jax.grad(mixed, argnums=(0, 1))).lower(
        params, x
    ).compile().as_text()
    attrs = [
        r["attrs"] for r in trace.snapshot_spans()[since:]
        if r["name"] == "mla.schedule"
    ][-1]
    assert (
        attrs["heads"], attrs["heads_a_call"], attrs["qk_width"],
        attrs["v_width"], attrs["q_lora_rank"], attrs["rotary_dims"],
    ) == (20, 2, 256, 256, 768, 64)
    assert len(re.findall(r"^\s*%attention[.\d]* = ", text, re.M)) == 10
    assert len(
        re.findall(rf"^\s*%{flash_mod.BWD_KERNEL_NAME}[.\d]* = ", text, re.M)
    ) == 10

    tokens, d, held, total, top_k, f = 16384, 2048, 8, 64, 4, 1536
    assert moe.rows_bound(tokens, top_k, held, total, 512) == 69632
    assert moe.rows_bound(tokens, top_k, held, total, 512, 2.5) == 24576
    assert moe.rows_planned(tokens, top_k, held, total, 512, 2.5) == 73728

    def loss(x, router_w, w_gate, w_up, w_down):
        y, load = moe.routed_experts(
            x, router_w, jnp.zeros((total,)), w_gate, w_up, w_down,
            experts_total=total, first_expert=0, top_k=top_k,
            pieces_from=2.5,
        )
        return y.astype(jnp.float32).sum(), load["fell_back"]

    compiled = jax.jit(
        jax.grad(loss, argnums=tuple(range(5)), has_aux=True)
    ).lower(
        arg((tokens, d), jnp.bfloat16), arg((d, total)),
        arg((held, d, f)), arg((held, d, f)), arg((held, f, d)),
    ).compile()
    text = compiled.as_text()
    assert " while(" in text and " conditional(" not in text
    assert re.search(r"(bf16|f32)\[24576,", text)
    assert not re.search(r"(bf16|f32)\[(69632|73728),", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * 2**30


def _router_products(text, tokens, experts):
    """How the compiler tiles each float32 "highest" product with a
    ``[tokens, experts]`` result in an optimized program: the
    ``window_config`` of the fusion that holds it (its bounds say how
    the contraction is split, so which sums are taken in which
    order)."""
    found = []
    bodies = dict(
        re.findall(r"^(%[\w.\-]+) \(.*?\) -> .*? \{\n(.*?)^\}", text,
                   re.S | re.M)
    )
    for line in text.splitlines():
        called = re.search(r" fusion\(.*calls=(%[\w.\-]+)", line)
        if called and re.search(
            rf"f32\[{tokens},{experts}\]\S* convolution\(.*"
            r"operand_precision=\{highest,highest\}",
            bodies.get(called.group(1), ""),
        ):
            found.append(
                re.search(r'"window_config":\{(.*?)"estimated_cycles"',
                          line).group(1)
            )
    return found


def test_lfm2_check_and_system_tile_their_routers_alike_on_v5e(
    v5e, chip_compile, monkeypatch
):
    """``lfm2-8b-a1b-steady``'s own check holds every routed layer's
    output to a reference that routes for itself, token by token, with
    no allowance for a token whose 4th and 5th scores tie to the last
    bit (``benchmark/configs/lfm2-8b-a1b.py:routed_check``): such a
    token passes only while the reference's router product is summed
    in the system's order, and the compiler decides that per program
    from what else the program holds. With the row buffer at twice the
    rows expected the check's program split the contraction in four
    and one seed in eleven failed on the chip (PERF.md section 6, PR
    40). Until a benchmark PR lets the comparison skip disputed
    tokens, a change to the routed layer's shapes has to keep the two
    products tiled alike: compiled here for a described v5e, as the
    chip's compiler does it."""
    from benchmark import manifest
    from adaptdl_tpu.parallel import mesh as mesh_mod

    gmm = importlib.import_module("adaptdl_tpu.ops.grouped_matmul")
    monkeypatch.setattr(gmm, "_use_interpret", lambda: False)
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    one = SingleDeviceSharding(v5e.devices[0])
    mesh = Mesh(np.array(v5e.devices[:1]), ("data",))
    monkeypatch.setattr(
        mesh_mod, "create_mesh_from_topology", lambda **kw: mesh
    )
    cell = manifest.load_cell("lfm2-8b-a1b-steady")
    config = manifest.load_module(cell.config_py)
    sizes = cell.sizes
    # Abstract weights: nothing can be placed on a described device.
    real_jit = jax.jit
    monkeypatch.setattr(
        jax, "jit",
        lambda f, **kw: (lambda *a: jax.eval_shape(f, *a))
        if getattr(f, "__name__", "") == "<lambda>" else real_jit(f, **kw),
    )
    built = config.build(sizes, dict(cell.workload["geometry"]), 0)
    monkeypatch.setattr(jax, "jit", real_jit)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree,
        )

    params = on_chip(built["trainer"]._abstract_state().params)
    rows, seq = config.REFERENCE_SEQUENCES, sizes["sequence_length"]
    batch = {
        k: jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one)
        for k in ("inputs", "targets")
    }
    system = jax.jit(built["head_io"]).lower(
        params, batch, on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    ).compile().as_text()
    at = sizes["num_dense_layers"]
    layer = on_chip(
        jax.eval_shape(
            lambda p: config.reference_weights(p, sizes)["layers"][at],
            params,
        )
    )
    x = jax.ShapeDtypeStruct(
        (rows * seq, sizes["hidden_size"]), jnp.bfloat16, sharding=one
    )
    check = jax.jit(config.routed_check(built, sizes)).lower(
        layer, params[f"layer_{at}"]["moe"], x, x
    ).compile().as_text()
    experts = sizes["num_experts"]
    of_system = _router_products(system, rows * seq, experts)
    of_reference = _router_products(check, rows * seq, experts)
    assert len(of_system) == sizes["num_hidden_layers"] - at
    assert len(of_reference) == 1
    assert set(of_system) == set(of_reference), (of_system, of_reference)


def test_block_keeps_the_projections_layout_on_v5e(v5e, chip_compile):
    """One remat ``Block`` of the benchmark's model, forward and
    gradient at the cell's micro-batch, compiled for the described
    v5e: ``Attention``'s swaps and the kernels' own cancel against
    the layout XLA gives the projections (sequence minor-most), so
    every operand of both kernels is a bitcast of what a fusion wrote:
    q, k, v and out are never copied into ``[b, h, s, d]``, into a
    flat ``[b * h, s, d]`` or into the kernels' ``[b * h, d, s]``, and
    rotary makes no gather and no pair-shaped array (PERF.md, PR 27).
    The kernels keep the names the benchmark's readers find them by:
    the forward ONE Mosaic call ``%attention.<n>``, the backward
    ``flash_bwd``."""
    import functools

    import flax.linen as nn

    from adaptdl_tpu.models.transformer import Block, TransformerConfig

    batch, heads, seq, head_dim = CELL
    cfg = TransformerConfig(
        vocab_size=50257, num_layers=1, num_heads=heads,
        d_model=heads * head_dim, d_ff=4 * heads * head_dim,
        max_seq_len=seq, dtype=jnp.bfloat16, remat=True,
        attention_fn=functools.partial(
            flash_mod.flash_attention, block_q=128, block_k=128
        ),
    )
    block = nn.remat(Block, static_argnums=())(cfg)
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip
            ),
            tree,
        )

    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.bfloat16)
    positions = jnp.arange(seq)
    params = jax.eval_shape(
        lambda: block.init(
            jax.random.key(0), jnp.zeros(x.shape, x.dtype), positions, None
        )
    )

    def loss(params, x):
        out = block.apply(params, x, positions, None)
        return out.astype(jnp.float32).sum()

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1)))
        .lower(on_chip(params), on_chip(x))
        .compile()
        .as_text()
    )
    mosaic = flash_mod.MOSAIC_CALL
    # The forward kernel, under the module's scope name alone (first
    # pass and remat are one call here: XLA merges the two of a lone
    # block).
    assert re.findall(rf"^\s*%attention[.\d]* = .*{mosaic}", text, re.M)
    assert len(re.findall(
        rf"^\s*%{flash_mod.BWD_KERNEL_NAME}[.\d]* = .*{mosaic}", text, re.M
    )) == 1
    calls = re.findall(
        rf"^\s*%(?:attention|{flash_mod.BWD_KERNEL_NAME})[.\d]* = "
        rf".*? custom-call\(([^)]*)\), custom_call_target=\"{mosaic}",
        text, re.M,
    )
    assert len(calls) >= 2
    for operands in calls:
        assert "%copy." not in operands and "%transpose" not in operands
    moved = re.findall(
        r"^\s*(?:ROOT )?%[\w\-.]+ = (\w+\[[\d,]*\])\S* "
        r"(copy|transpose|gather|reshape)\(",
        text, re.M,
    )
    assert moved, "the pattern found no data movement at all"
    flat = batch * heads
    banned = [
        (op, shape) for shape, op in moved
        if shape in (
            f"bf16[{batch},{heads},{seq},{head_dim}]",
            f"bf16[{flat},{seq},{head_dim}]",
            f"bf16[{batch},{heads},{head_dim},{seq}]",
            f"bf16[{flat},{head_dim},{seq}]",
        )
        or shape.endswith(",2]")
    ]
    assert not banned, banned


def test_remat_keeps_the_kernels_output_on_v5e(v5e, chip_compile):
    """The gradient of a remat'd two-layer ``TransformerLM`` at the
    flagship widths and the cell's micro-batch, compiled for the
    described v5e, holds ONE forward and ONE backward Mosaic call a
    layer: a block keeps the kernel's ``out`` and ``lse`` by name
    (``block_remat``), where the bare ``nn.remat`` held two forwards a
    layer — the kernel re-run in every backward to rebuild an output
    it had (PERF.md, PR 29)."""
    import functools

    from adaptdl_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    batch, heads, seq, head_dim = CELL
    layers = 2
    cfg = TransformerConfig(
        vocab_size=512, num_layers=layers, num_heads=heads,
        d_model=heads * head_dim, d_ff=4 * heads * head_dim,
        max_seq_len=seq, dtype=jnp.bfloat16, remat=True,
        attention_fn=functools.partial(
            flash_mod.flash_attention, block_q=128, block_k=128
        ),
    )
    model = TransformerLM(cfg)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda: model.init(
                jax.random.key(0), jnp.zeros(tokens.shape, tokens.dtype)
            )
        ),
    )

    def loss(params, tokens):
        return model.apply(params, tokens, train=False).sum()

    text = jax.jit(jax.grad(loss)).lower(params, tokens).compile().as_text()

    def calls(name):
        return len(re.findall(
            rf"^\s*%{name}[.\d]* = .*{flash_mod.MOSAIC_CALL}", text, re.M
        ))

    assert calls("attention") == layers
    assert calls(flash_mod.BWD_KERNEL_NAME) == layers


def test_ladder_keeps_the_projections_results_on_v5e(v5e, chip_compile):
    """Two layers at the flagship widths and the cell's micro-batch,
    the gradient compiled for the described v5e: with the budget a
    16 GB chip leaves a ``gpt2-124m`` job (``block_remat``'s ladder
    takes its three rungs) the text holds ONE fused QKV projection a
    layer, where without a budget — the program before the ladder — it
    holds two, the forward's and the backward's re-run; the kernels
    are one forward and one backward a layer either way (PERF.md, PR
    41)."""
    import functools

    from adaptdl_tpu import device_budget, trace
    from adaptdl_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    batch, heads, seq, head_dim = CELL
    layers = 2
    cfg = TransformerConfig(
        vocab_size=512, num_layers=layers, num_heads=heads,
        d_model=heads * head_dim, d_ff=4 * heads * head_dim,
        max_seq_len=seq, dtype=jnp.bfloat16, remat=True,
        attention_fn=functools.partial(
            flash_mod.flash_attention, block_q=128, block_k=128
        ),
    )
    model = TransformerLM(cfg)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda: model.init(
                jax.random.key(0), jnp.zeros(tokens.shape, tokens.dtype)
            )
        ),
    )

    def loss(params, tokens):
        return model.apply(params, tokens, train=False).sum()

    def qkv_and_kernels(budget):
        with device_budget.tracing_with(budget):
            text = (
                jax.jit(jax.grad(loss)).lower(params, tokens).compile()
                .as_text()
            )
        qkv = len(re.findall(
            rf"^\s*%[\w.\-]+ = bf16\[3,{batch},{seq},{heads},{head_dim}\]"
            r"\S* fusion\(", text, re.M,
        ))
        kernels = tuple(
            len(re.findall(
                rf"^\s*%{name}[.\d]* = .*{flash_mod.MOSAIC_CALL}", text, re.M
            ))
            for name in ("attention", flash_mod.BWD_KERNEL_NAME)
        )
        return qkv, kernels

    assert qkv_and_kernels(None) == (2 * layers, (layers, layers))
    gib = 2**30
    # gpt2-124m on a v5e: 15.75 GiB less two copies of 2.30 GiB of
    # state and gradient and a sixteenth in reserve.
    budget = device_budget.Activations(int(10.17 * gib), int(15.75 * gib))
    assert qkv_and_kernels(budget) == (layers, (layers, layers))
    (attrs,) = [
        r["attrs"] for r in trace.snapshot_spans()
        if r["name"] == "remat.policy"
    ][-1:]
    assert attrs["rungs"] == "qkv,mixed,ff_up"
    assert attrs["rung_bytes"] == layers * batch * seq * 2 * 8 * cfg.d_model
