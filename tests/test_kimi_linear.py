"""What the kimi-linear-48b-a3b configuration forced (PR 46), at small
sizes against the configuration's own plain reference
(``benchmark/configs/kimi-linear-48b-a3b.py``, which imports nothing
from ``adaptdl_tpu``): the ``kda`` mixer, latent attention at a q/k width
that is not v's, the shared expert, the share of an expert-parallel
layer, the whole model and its save / restore round trip. (The chunked
gated delta rule and its Pallas kernels: ``tests/test_kda_op.py``.)"""

import functools

import configurations
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from configurations import loader_stub, rel

from adaptdl_tpu import trace
from adaptdl_tpu.models.transformer import (
    KDA,
    LatentAttention,
    RoutedFFN,
    TransformerConfig,
    causal_attention,
)
from adaptdl_tpu.ops import kda as kda_op
from adaptdl_tpu.ops.flash_attention import flash_attention

NAME = "kimi-linear-48b-a3b"


@pytest.fixture(autouse=True)
def _rows_of_several_chunks():
    """The rule's chunk is a constant of ``ops/kda.py`` (64); the
    models of these tests run rows of 64 tokens, several chunks at
    the tiny sizes' ``kda_chunk``."""
    with configurations.rows_of_several_chunks(NAME):
        yield


# ---- the kda mixer ------------------------------------------------------


def test_kda_mixer_in_head_groups_is_the_mixer(monkeypatch):
    """The module's own work on a head (convolutions, norms, the
    decay) done a group of heads at a time, as a long row forces:
    what all heads at once give, forward and backward."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    cfg = config.model_config(sizes)
    u = jax.random.normal(jax.random.key(9), (2, 64, 32))
    module = KDA(cfg)
    params = module.init(jax.random.key(1), u, None)["params"]

    def run(params, u):
        return module.apply({"params": params}, u, None)

    def loss(params, u):
        return jnp.sum(run(params, u) * jnp.cos(u))

    whole, whole_grads = run(params, u), jax.grad(loss, (0, 1))(params, u)
    monkeypatch.setattr(kda_op, "_GROUP_ELEMENTS", 2 * 64 * 8)
    assert kda_op.head_groups(2 * 64, 2, 8) == 2
    np.testing.assert_allclose(run(params, u), whole, rtol=1e-5, atol=1e-6)
    grads = jax.grad(loss, (0, 1))(params, u)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(whole_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---- latent attention -------------------------------------------------


@pytest.mark.parametrize("seq,qk,v", [(256, 24, 16), (128, 192, 128)])
def test_flash_kernels_take_a_v_narrower_than_q(seq, qk, v):
    keys = jax.random.split(jax.random.key(0), 4)
    shape = (1, 2, seq)
    q = jax.random.normal(keys[0], shape + (qk,))
    k = jax.random.normal(keys[1], shape + (qk,))
    val = jax.random.normal(keys[2], shape + (v,))
    run = functools.partial(
        flash_attention, causal=True, scale=None, block_q=128, block_k=128
    )
    want = causal_attention(q, k, val)
    got = run(q, k, val)
    assert got.shape == shape + (v,)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    cotangent = jax.random.normal(keys[3], got.shape)
    grads = jax.grad(lambda *a: jnp.sum(run(*a) * cotangent), (0, 1, 2))(
        q, k, val
    )
    ref = jax.grad(
        lambda *a: jnp.sum(causal_attention(*a) * cotangent), (0, 1, 2)
    )(q, k, val)
    for a, b in zip(grads, ref):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def _mixer_case(monkeypatch, kind):
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    at = config.checked_mixers(sizes)[kind]
    layer = config.reference_weights(params, sizes)["layers"][at][kind]
    u = jax.random.normal(jax.random.key(7), (2, 64, 32))
    return config, sizes, built, params[f"layer_{at}"][kind], layer, u


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_mixer_equals_the_reference(monkeypatch, kind):
    """The system's mixer alone (kda: convolutions, norms, gates and
    the chunked rule through the kernels; mla: the latent projections
    and the flash kernels at 12 / 8) against the reference's, forward
    and the gradient of every leaf and of the input."""
    config, sizes, built, mixer_params, layer, u = _mixer_case(
        monkeypatch, kind
    )
    cfg = config.model_config(
        sizes, functools.partial(flash_attention, block_q=64, block_k=64)
    )
    module = {"kda": KDA, "mla": LatentAttention}[kind](cfg)
    got = module.apply({"params": mixer_params}, u, None)
    want = config.reference_mixer(kind, layer, u, sizes)
    token, rms = config.layer_error(got, want)
    assert float(token) < 1e-5 and float(rms) < 1e-5
    errors = config.mixer_grad_errors(
        kind,
        built["mixer_vjp"](kind, mixer_params, u, u),
        config.reference_mixer_vjp(kind, layer, u, u, sizes),
    )
    assert all(float(e) < 2e-5 for e in errors.values()), errors


@pytest.mark.parametrize("variant", ["bf16_state", "bf16_decay"])
def test_a_lower_precision_reference_differs(monkeypatch, variant):
    """What ``kimi_precision.py`` reads on the chip is not a no-op."""
    config, sizes, _, _, layer, u = _mixer_case(monkeypatch, "kda")
    want = config.reference_mixer("kda", layer, u, sizes)
    low = config.reference_mixer("kda", layer, u, sizes, variant)
    assert float(config.layer_error(low, want)[1]) > 1e-4


def test_mla_schedule_is_journalled(monkeypatch):
    config, sizes, _, mixer_params, _, u = _mixer_case(monkeypatch, "mla")
    before = len(
        [r for r in trace.snapshot_spans() if r["name"] == "mla.schedule"]
    )
    LatentAttention(config.model_config(sizes)).apply(
        {"params": mixer_params}, u, None
    )
    events = [
        r for r in trace.snapshot_spans() if r["name"] == "mla.schedule"
    ]
    assert len(events) == before + 1
    attrs = events[-1]["attrs"]
    assert (attrs["qk_width"], attrs["v_width"], attrs["latent_rank"]) == (
        12, 8, 12
    )
    assert attrs["positions"] == "none"


@pytest.mark.parametrize(
    "seq,want",
    [(1024, 32), (8192, 8), (16384, 4), (32768, 2)],
)
def test_heads_a_call_follow_the_flash_schedule(seq, want):
    """32 heads of q/k 192 and v 128 in bf16: all in one call while a
    head's K and V stay in VMEM; past that a call's float32 dQ
    partials (one a key chunk) are held to the bytes of q — four heads
    at the cell's 16 384 keys, the schedule the chip runs measured."""
    import importlib

    flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")
    assert flash_mod.heads_a_call(32, seq, 192, 128, 2) == want
    made = flash_mod.make_flash_attention(block_q=128, block_k=128)
    assert made.heads_a_call(32, seq, 192, 128, 2) == want


@pytest.mark.parametrize("made", [True, False])
def test_any_attention_fn_is_asked_how_many_heads_a_call(monkeypatch, made):
    """A ``functools.partial`` of the kernel (what the cells' builders
    pass) says nothing of itself and is asked through the module's
    rule all the same: with all heads in one call the cell's step is
    refused by the chip's compiler (PR 46)."""
    import importlib

    flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")
    config, sizes, _, mixer_params, _, u = _mixer_case(monkeypatch, "mla")
    # K and V of a head (12 + 8 wide, float32, double-buffered) past
    # the budget at 16 keys: four key chunks a row of 64.
    monkeypatch.setattr(flash_mod, "_KV_VMEM_BUDGET", 2 * (12 + 8) * 4 * 16)
    monkeypatch.setattr(flash_mod, "_TILE_ROWS", 16)
    attn = (
        flash_mod.make_flash_attention(block_q=16, block_k=16) if made
        else functools.partial(flash_attention, block_q=16, block_k=16)
    )
    got = LatentAttention(config.model_config(sizes, attn)).apply(
        {"params": mixer_params}, u, None
    )
    attrs = [
        r for r in trace.snapshot_spans() if r["name"] == "mla.schedule"
    ][-1]["attrs"]
    assert (attrs["heads"], attrs["heads_a_call"]) == (2, 1)
    want = LatentAttention(config.model_config(sizes)).apply(
        {"params": mixer_params}, u, None
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# ---- the shared expert and the share ----------------------------------


def test_shared_expert_is_added_unweighted(monkeypatch):
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    moe = params["layer_1"]["moe"]
    layer = config.routed_weights(moe)
    x = jax.random.normal(jax.random.key(5), (96, 32))
    cfg = config.model_config(sizes)
    y, sown = RoutedFFN(cfg).apply(
        {"params": moe}, x, mutable=["moe_load", "moe_routing"]
    )
    with jax.default_matmul_precision("highest"):
        want, _ = config.reference_routed_ffn(layer, x, sizes)
        routed_only, _ = config.reference_routed_ffn(
            layer, x, sizes, shared=False
        )
        shared = config._gated(x, layer["s1"], layer["s3"], layer["s2"])
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(routed_only + shared, want, rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(shared).max()) > 0.1
    assert int(sown["moe_load"]["shared_rows"][0]) == 96
    no_shared = RoutedFFN(
        config.model_config(configurations.sizes(NAME, num_shared_experts=0))
    ).apply(
        {"params": {k: v for k, v in moe.items() if k != "shared"}}, x,
        mutable=["moe_load", "moe_routing"],
    )[1]
    assert "shared_rows" not in no_shared["moe_load"]


@pytest.mark.parametrize(
    "cell,shape,bound,planned",
    [
        # (tokens a micro-batch, top_k, held, total) at the cells' REAL
        # sizes; the bounds are what the commit before PR 46 gives
        # there (its ``rows_capacity`` where it walked the whole plan).
        ("lfm2-8b-a1b-steady", (16384, 4, 8, 32), 45056, 69632),
        ("keye-vl-2.0-30b-a3b-steady", (16384, 8, 16, 128), 139264, 139264),
        ("kimi-linear-48b-a3b-steady", (16384, 8, 8, 256), 14336, 143360),
        # PR 49: 320 rows an even router sends a group are under three
        # quarters of a tile of 512, so the tile is 256 (in tiles of
        # 512 the bound was 41 984 rows).
        ("qwen3-next-80b-a3b-steady", (16384, 10, 32, 512), 33792, 202752),
    ],
)
def test_the_row_bounds_of_the_routed_cells_at_their_real_sizes(
    cell, shape, bound, planned
):
    """The piece-walk's threshold moves neither routed cell the
    benchmark had: lfm2's two passes and keye's one are the rows of
    before; only a plan of four bounds or more is cut in pieces. Nor
    does the tile that follows the rows a group (PR 49) move them."""
    from adaptdl_tpu.models import moe
    from adaptdl_tpu.ops import grouped_matmul as gmm

    tokens, top_k, held, total = shape
    tile = gmm.tile_rows(tokens * min(top_k, held), tokens * top_k / total)
    assert tile == (256 if cell.startswith("qwen3") else 512)
    assert tile == gmm.tile_rows(tokens * min(top_k, held)) or tile == 256
    assert moe.rows_bound(tokens, top_k, held, total, tile) == bound
    assert moe.rows_planned(tokens, top_k, held, total, tile) == planned


@pytest.mark.parametrize("boost", [0.0, 50.0])
def test_a_plan_many_times_its_bound_is_walked_in_pieces(boost):
    """2 of 256 experts held, top 4 of 8192 tokens: the worst case is
    many bounds long, so the plan is pieces of the bound and the usual
    step walks one; a router that sends every token to the held
    experts walks as many as hold its rows, drops nothing, and gives
    the same layer."""
    from adaptdl_tpu.models import moe
    from adaptdl_tpu.ops import grouped_matmul as gmm

    tokens, d, f, total, held, top_k = 8192, 16, 8, 256, 2, 4
    tile = gmm.tile_rows(tokens * min(top_k, held), tokens * top_k / total)
    capacity = moe.rows_capacity(tokens, top_k, held, tile)
    bound = moe.rows_bound(tokens, top_k, held, total, tile)
    planned = moe.rows_planned(tokens, top_k, held, total, tile)
    assert capacity >= moe.ROWS_PIECES_FROM * bound
    assert planned % bound == 0 and capacity <= planned < capacity + bound
    keys = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(keys[0], (tokens, d))
    router = jax.random.normal(keys[1], (d, total))
    w_gate = jax.random.normal(keys[2], (held, d, f))
    w_up = jax.random.normal(keys[3], (held, d, f))
    w_down = jax.random.normal(keys[4], (held, f, d))
    bias = jnp.zeros((total,)).at[:held].set(boost)

    def layer(x, w_gate, w_up, w_down):
        return moe.routed_experts(
            x, router, bias, w_gate, w_up, w_down, experts_total=total,
            first_expert=0, top_k=top_k,
        )

    y, load = layer(x, w_gate, w_up, w_down)
    experts, weights = load["experts"], load["weights"]

    def plain(x, w_gate, w_up, w_down):
        y = jnp.zeros_like(x)
        for e in range(held):
            weight = jnp.where(experts == e, weights, 0).sum(-1, keepdims=True)
            y = y + weight * (
                (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
            )
        return y

    np.testing.assert_allclose(
        y, plain(x, w_gate, w_up, w_down), rtol=1e-4, atol=1e-4
    )
    assert int(load["dropped"]) == 0
    pieces = -(-int(load["rows_active"]) // bound)
    assert int(load["rows_walked"]) == max(pieces, 1) * bound
    # (Every token on both held experts: tokens x held rows; 8 pieces
    # in tiles of 512, 19 since PR 49 gave this shape's 128 rows a
    # group tiles of 128 and a bound of 896.)
    assert pieces == (1 if boost == 0 else -(-tokens * held // bound))
    assert int(load["fell_back"]) == (boost > 0)
    grads = jax.grad(
        lambda *a: jnp.sum(layer(*a)[0] ** 2), (0, 1, 2, 3)
    )(x, w_gate, w_up, w_down)
    want = jax.grad(
        lambda *a: jnp.sum(plain(*a) ** 2), (0, 1, 2, 3)
    )(x, w_gate, w_up, w_down)
    # (The input's gradient also passes through the router's weights,
    # which ``plain`` holds fixed: the experts' leaves are compared.)
    for a, b in zip(grads[1:], want[1:]):
        assert rel(a, b) < 1e-4


def test_the_shares_add_up_to_the_whole_layer(monkeypatch):
    """A 32-expert layer cut into 4 shares of 8: what the four chips
    compute of the routed result, with the shared expert (which every
    chip computes alike) counted ONCE, adds up to the uncut
    reference's layer."""
    config = configurations.module(NAME)
    sizes = configurations.sizes(
        NAME, router_width=32, experts_held=8, num_experts=8,
        num_experts_per_token=4, num_experts_per_tok=4,
    )
    keys = jax.random.split(jax.random.key(11), 9)
    d, f = 32, 16
    whole = {
        "router": 0.5 * jax.random.normal(keys[0], (d, 32)),
        "bias": 0.1 * jax.random.normal(keys[1], (32,)),
        "w1": jax.random.normal(keys[2], (32, d, f)) / d**0.5,
        "w3": jax.random.normal(keys[3], (32, d, f)) / d**0.5,
        "w2": jax.random.normal(keys[4], (32, f, d)) / f**0.5,
        "s1": jax.random.normal(keys[5], (d, f)) / d**0.5,
        "s3": jax.random.normal(keys[6], (d, f)) / d**0.5,
        "s2": jax.random.normal(keys[7], (f, d)) / f**0.5,
    }
    x = jax.random.normal(keys[8], (64, d))
    with jax.default_matmul_precision("highest"):
        want, counts = config.reference_routed_ffn(
            whole, x, {**sizes, "first_expert": 0}
        )
    assert int(counts.sum()) == 64 * 4
    total = jnp.zeros_like(x)
    for share in range(4):
        first = 8 * share
        cfg = config.model_config({**sizes, "first_expert": first})
        held = slice(first, first + 8)
        y, sown = RoutedFFN(cfg).apply(
            {"params": {
                "router": whole["router"], "expert_bias": whole["bias"],
                "w_gate": whole["w1"][held], "w_up": whole["w3"][held],
                "w_down": whole["w2"][held],
                "shared": {
                    "ff_gate": {"kernel": whole["s1"]},
                    "ff_up": {"kernel": whole["s3"]},
                    "ff_down": {"kernel": whole["s2"]},
                },
            }},
            x, mutable=["moe_load", "moe_routing"],
        )
        np.testing.assert_array_equal(
            sown["moe_load"]["held_rows"][0], counts[held]
        )
        total = total + y
    with jax.default_matmul_precision("highest"):
        shared = config._gated(x, whole["s1"], whole["s3"], whole["s2"])
    np.testing.assert_allclose(
        total - 3 * shared, want, rtol=2e-5, atol=2e-5
    )


# ---- the whole model ---------------------------------------------------


def test_loss_and_gradients_equal_the_reference(monkeypatch):
    """Five layers of the cell's pattern (kda + dense FFN, kda, kda,
    mla, kda; four routed with a shared expert), remat on, the flash
    kernels, the delta rule's kernels, a share of 4 of 16 experts, the
    untied head."""
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    built = configurations.built(monkeypatch, NAME, sizes)
    params = built["trainer"].params_tree(built["trainer"].init_state())
    data = config.make_dataset(sizes, 5, 4)
    batch = {k: jnp.asarray(v[:2]) for k, v in data.items()}

    def system(params):
        return built["loss_fn"](params, batch, jax.random.key(0))[0]

    def reference(params):
        return config.reference_loss(
            config.reference_weights(params, sizes),
            batch["inputs"], batch["targets"], sizes,
        )[0]

    loss, grads = jax.value_and_grad(system)(params)
    want, want_grads = jax.value_and_grad(reference)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        if "expert_bias" in jax.tree_util.keystr(path):
            continue  # a buffer: no gradient reaches it on either side
        scale = max(float(jnp.abs(ref).max()), 1e-6)
        assert float(jnp.abs(got - ref).max()) / scale < 5e-4, (
            jax.tree_util.keystr(path)
        )
    report = config.reference_check(built, params, data, sizes)
    assert report["ok"], report


def test_run_step_save_restore_round_trip(tmp_path, monkeypatch):
    """One ``ElasticTrainer.run_step`` of the tiny model, ``moe.load``
    journalled with ``shared_rows``, a save through ``checkpoint.py``,
    and a restore into a fresh trainer that steps on bit-equal."""
    from adaptdl_tpu import checkpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    config, sizes = configurations.module(NAME), configurations.sizes(NAME)
    data = config.make_dataset(sizes, 5, 8)
    batch = {k: v[:4] for k, v in data.items()}
    built = configurations.built(monkeypatch, NAME, sizes)
    trainer = built["trainer"]
    holder = {"state": trainer.init_state()}
    ck = trainer.make_checkpoint_state(
        lambda: holder["state"], lambda s: holder.__setitem__("state", s)
    )
    trainer._calibrated.add(2)
    holder["state"], metrics = trainer.run_step(
        holder["state"], batch, loader_stub(2, 1)
    )
    assert np.isfinite(float(metrics["loss"]))
    load = metrics["counters"]["moe.load"]
    np.testing.assert_array_equal(load["shared_rows"], [4 * 64] * 4)
    attrs = [
        r for r in trace.snapshot_spans() if r["name"] == "moe.load"
    ][-1]["attrs"]
    assert attrs["shared_rows"] == [256] * 4
    checkpoint.save_all_states()
    saved = jax.tree.map(np.asarray, trainer.params_tree(holder["state"]))
    holder["state"], after = trainer.run_step(
        holder["state"], batch, loader_stub(2, 1)
    )
    ck.unregister()

    again = configurations.built(monkeypatch, NAME, sizes, seed=11)["trainer"]
    holder2 = {"state": again.init_state()}
    ck2 = again.make_checkpoint_state(
        lambda: holder2["state"], lambda s: holder2.__setitem__("state", s)
    )
    assert checkpoint.load_state(ck2)
    for a, b in zip(
        jax.tree.leaves(saved),
        jax.tree.leaves(again.params_tree(holder2["state"])),
    ):
        np.testing.assert_array_equal(a, np.asarray(b))
    again._calibrated.add(2)
    holder2["state"], resumed = again.run_step(
        holder2["state"], batch, loader_stub(2, 1)
    )
    assert float(resumed["loss"]) == float(after["loss"])
    for a, b in zip(
        jax.tree.leaves(trainer.params_tree(holder["state"])),
        jax.tree.leaves(again.params_tree(holder2["state"])),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ck2.unregister()


# ---- what a config may not ask for --------------------------------------


_BASE = dict(
    vocab_size=64, num_layers=1, num_heads=2, d_model=16, d_ff=32,
    dtype=jnp.float32, head_dim=8,
)


@pytest.mark.parametrize(
    "options,field",
    [
        (dict(layer_types=("kda",), kda_gate_rank=4, rope=False,
              seq_axis="seq"), "seq_axis"),
        (dict(layer_types=("kda",), rope=False), "kda_gate_rank"),
        (dict(layer_types=("mla",), kv_lora_rank=8, qk_nope_head_dim=8,
              v_head_dim=8), "rope"),
        (dict(layer_types=("mla",), rope=False, qk_nope_head_dim=8,
              v_head_dim=8), "kv_lora_rank"),
        (dict(d_shared_expert=16), "d_shared_expert"),
        (dict(layer_types=("sparse_attention",), rope=False), "rope"),
    ],
)
def test_config_refuses_at_build_with_the_fields_name(options, field):
    with pytest.raises(ValueError, match=field):
        TransformerConfig(**_BASE, **options)


def test_a_remat_block_keeps_the_rules_output_by_name(monkeypatch):
    """``block_remat`` of a model with kda layers saves ``kda_out``
    beside the flash kernel's names; a model without them does not."""
    from adaptdl_tpu.models import transformer

    def saved(config):
        before = len(trace.snapshot_spans())
        transformer.block_remat(config, (2, 64))
        events = [
            r for r in trace.snapshot_spans()[before:]
            if r["name"] == "remat.policy"
        ]
        return events[-1]["attrs"]["saved_names"].split(",")

    config = configurations.module(NAME).model_config(
        configurations.sizes(NAME)
    )
    assert saved(config) == ["flash_out", "flash_lse", "kda_out"]
    plain = TransformerConfig(**_BASE, layer_types=("full_attention",))
    assert saved(plain) == ["flash_out", "flash_lse"]
