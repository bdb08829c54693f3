"""Transformer language model, optionally sequence-parallel.

The reference's transformer/BERT example family (reference:
examples/transformer/transformer.py:163-175, examples/BERT/) on the
elastic stack, plus the long-context capability the reference lacks:
``--seq-shards k`` splits every sequence across k chips, with either
ring attention (K/V blocks rotating over ICI, the default) or
``--seq-mode ulysses`` (two all_to_all head exchanges around one
full-sequence attention — composable with ``--flash`` as the
within-chip block engine).

Run:   python examples/transformer_lm.py --cpu --epochs 2
Long sequences over a 4x2 (data x seq) mesh:
       python examples/transformer_lm.py --cpu --seq-shards 2
Ulysses with the Pallas kernel inside:
       python examples/transformer_lm.py --seq-shards 2 \
           --seq-mode ulysses --flash
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _data import force_cpu_devices, synthetic_tokens  # noqa: E402

# The batch-size recipe: adamw(3e-4) is tuned at 32 sequences; the
# goodput model may grow the global batch to 1024 with 4..64 sequences
# per chip per microbatch, accumulating beyond that. The upper bound
# is the job's memory contract, and the goodput model does take it
# when throughput says so. For the flagship preset on a 16 GB chip
# (TPU v5e) 64 is what fits with room to spare: compiled for that chip,
# the accumulating step needs 9.9 GiB at 64 sequences, 13.1 GiB at 96
# and over 16.4 GiB at 128 (the dense head's float32 logits alone are
# 128 * 512 * 32000 * 4 B = 8.4 GB).
LEARNING_RATE = 3e-4
INIT_BATCH_SIZE = 32
MAX_BATCH_SIZE = 1024
LOCAL_BSZ_BOUNDS = (4, 64)


def lm_config(on_cpu: bool, seq_len: int | None = None, **overrides):
    """The example's model preset: a CPU demo size, or the flagship
    this repo runs on the chip — 12 layers, d_model 768, 12 heads of
    64, d_ff 3072, vocab 32000, seq 512, bf16, remat. The one copy:
    ``chip_smoke.py`` drives the chip with exactly this."""
    import jax.numpy as jnp

    from adaptdl_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=256 if on_cpu else 32000,
        num_layers=2 if on_cpu else 12,
        num_heads=2 if on_cpu else 12,
        d_model=64 if on_cpu else 768,
        d_ff=128 if on_cpu else 3072,
        max_seq_len=seq_len or (32 if on_cpu else 512),
        dtype=jnp.float32 if on_cpu else jnp.bfloat16,
        remat=True,
        **overrides,
    )


def flash_attention_fn(seq_len: int):
    """``--flash``: the Pallas kernel as the within-chip attention."""
    import functools

    from adaptdl_tpu.ops.flash_attention import flash_attention

    block = min(128, seq_len)
    return functools.partial(
        flash_attention, block_q=block, block_k=block
    )


def dense_lm_loss(model, chunked_xent: int = 0):
    """Next-token loss over ``{"inputs", "targets"}`` batches, plus the
    MoE aux loss; ``chunked_xent`` > 0 streams the output head that
    many rows at a time (ops/chunked_xent.py)."""
    import optax

    from adaptdl_tpu.models.transformer import apply_with_moe_aux

    if chunked_xent > 0:
        import jax.numpy as jnp

        from adaptdl_tpu.ops.chunked_xent import weighted_xent_sum

        def loss_fn(params, batch, rng):
            hidden, aux = apply_with_moe_aux(
                model, params, batch["inputs"], rng,
                return_hidden=True,
            )
            flat = hidden.reshape(-1, hidden.shape[-1])
            mean, _ = weighted_xent_sum(
                flat,
                params["embed"]["embedding"],
                batch["targets"].reshape(-1),
                jnp.full(flat.shape[:1], 1.0 / flat.shape[0], jnp.float32),
                chunked_xent,
            )
            return mean + aux

    else:

        def loss_fn(params, batch, rng):
            logits, aux = apply_with_moe_aux(
                model, params, batch["inputs"], rng
            )
            return (
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, batch["targets"]
                ).mean()
                + aux
            )

    return loss_fn


def make_trainer(loss_fn, params, mesh, **layout):
    """The example's optimizer and scaling recipe around a loss;
    ``layout`` carries the parallelism arguments (sharding fn,
    pipeline micro, zero modes)."""
    import optax

    from adaptdl_tpu.scaling_rules import AdamScale
    from adaptdl_tpu.trainer import ElasticTrainer

    return ElasticTrainer(
        loss_fn=loss_fn,
        params=params,
        optimizer=optax.adamw(LEARNING_RATE),
        init_batch_size=INIT_BATCH_SIZE,
        scaling_rule=AdamScale(),
        precondition="adam",
        mesh=mesh,
        **layout,
    )


def make_loader(dataset):
    from adaptdl_tpu.data import AdaptiveDataLoader

    loader = AdaptiveDataLoader(dataset, batch_size=INIT_BATCH_SIZE)
    loader.autoscale_batch_size(
        MAX_BATCH_SIZE,
        local_bsz_bounds=LOCAL_BSZ_BOUNDS,
        gradient_accumulation=True,
    )
    return loader


def shifted(raw):
    """Token rows -> the ``{"inputs", "targets"}`` next-token pair."""
    return {
        "inputs": raw[:, :-1].copy(),
        "targets": raw[:, 1:].copy(),
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--epochs", type=int, default=5)
    # Default to the scheduler's chosen factorization (exported as
    # ADAPTDL_SEQ_SHARDS / ADAPTDL_MODEL_SHARDS by the launcher when
    # the goodput topology search picks a dp x sp x tp mesh); flags
    # override for manual runs.
    parser.add_argument("--seq-shards", type=int, default=None)
    # How attention runs over the seq axis: "ring" (ppermute K/V
    # rotation, any head count) or "ulysses" (all_to_all head
    # exchange; needs num_heads % seq_shards == 0).
    parser.add_argument(
        "--seq-mode", choices=("ring", "ulysses"), default="ring"
    )
    parser.add_argument("--tp-shards", type=int, default=None)
    # Pallas flash-attention kernel for the within-chip attention
    # (blocked online softmax, no [seq, seq] intermediate). Composable
    # with --seq-shards only under --seq-mode ulysses (the kernel then
    # runs on the gathered full sequence); ring attention owns its
    # blocked softmax.
    parser.add_argument("--flash", action="store_true")
    parser.add_argument("--seq-len", type=int, default=None)
    # Stream the output head this many rows at a time instead of
    # materializing [tokens, vocab] logits (ops/chunked_xent.py) —
    # the HBM saving buys batch size at large vocab. 0 = dense head.
    parser.add_argument("--chunked-xent", type=int, default=0)
    # ZeRO-1: shard the Adam moments across the data axis (8 bytes/
    # param -> 8/dp) at the cost of one extra parameter-sized
    # all-reduce per step. Composes with dp/seq; stage/expert/tp
    # manage their own optimizer layouts.
    parser.add_argument("--zero1", action="store_true")
    # ZeRO-3-lite: additionally shard the PARAMETER storage (params +
    # moments live as [dp, shard] rows; the step assembles the full
    # tree on the fly). Same composition rules as --zero1.
    parser.add_argument("--zero3", action="store_true")
    # Per-layer ZeRO-3/FSDP: params/moments/GNS-carry persist as
    # per-BLOCK rows and the layer scan gathers one block at a time
    # (models/zero3_lm.py) — per-step peak HBM is params/dp + one
    # block, where --zero3 still materializes the whole tree in-step.
    # Composes with dp and --seq-shards (long-context: seq-parallel
    # attention + per-layer FSDP); tp/stage/expert are excluded.
    parser.add_argument("--zero3-blocks", action="store_true")
    # Rematerialisation policy (jax.checkpoint_policies name): trade
    # recompute FLOPs for activation HBM per block.
    parser.add_argument("--remat-policy", type=str, default=None)
    # Mixture-of-experts: every 2nd block's FFN becomes a Switch/
    # GShard MoE with this many experts; the expert axis shards over
    # the scheduler's chosen expertShards (ADAPTDL_EXPERT_SHARDS).
    parser.add_argument(
        "--moe-experts", type=int, default=0,
        help="experts of the capacity-dropping Switch FFN. The other "
        "block options are TransformerConfig fields with no flag "
        "here: norm / norm_eps (RMSNorm), ffn='swiglu', num_kv_heads "
        "and qk_norm (grouped-query attention; these two change the "
        "parameter tree), rope_theta, layer_types + conv_kernel "
        "(gated short convolutions beside attention) and the "
        "dropless routed experts (experts_total, experts_held, "
        "first_expert, experts_top_k, d_expert, num_dense_layers; "
        "loss: models.transformer.routed_lm_loss_fn): "
        "benchmark/configs/lfm2-8b-a1b.py sets them all",
    )
    parser.add_argument("--moe-top-k", type=int, default=1)
    # Pipeline parallelism: the block stack runs the GPipe (or
    # interleaved, when the chunk count admits v = chunks/ss > 1)
    # schedule over a "stage" axis. Defaults to the scheduler's
    # ADAPTDL_STAGE_SHARDS / ADAPTDL_PIPELINE_MICRO. --pipeline opts
    # the job into the pipeline FAMILY: the hints advertise the stage
    # axis (composable with tensor parallelism; sp/ep advertise 1),
    # and checkpoints use the canonical layer-major layout so the
    # scheduler can move the job between ss = 1 and ss > 1 across
    # restarts. The flag lives in the submitted command line, so the
    # advertisement is stable across incarnations.
    parser.add_argument("--pipeline", action="store_true")
    parser.add_argument("--stage-shards", type=int, default=None)
    parser.add_argument("--pipeline-micro", type=int, default=None)
    args = parser.parse_args()
    if args.cpu:
        force_cpu_devices()

    import jax

    import adaptdl_tpu
    from adaptdl_tpu import checkpoint, env, epoch, metrics
    from adaptdl_tpu.models import init_transformer
    from adaptdl_tpu.parallel import create_mesh

    adaptdl_tpu.initialize_job()
    on_cpu = args.cpu
    seq_shards = (
        args.seq_shards if args.seq_shards is not None else env.seq_shards()
    )
    seq_len = args.seq_len or (32 if on_cpu else 512)
    assert seq_len % max(seq_shards, 1) == 0

    attention_fn = None
    if args.flash:
        assert seq_shards <= 1 or args.seq_mode == "ulysses", (
            "--flash composes with sequence sharding only under "
            "--seq-mode ulysses (full sequence gathered per head "
            "slice); ring attention owns its blocked softmax"
        )
        flash_inner = flash_attention_fn(seq_len)
        if seq_shards > 1:
            from adaptdl_tpu.parallel.ulysses import (
                make_ulysses_attention,
            )

            attention_fn = make_ulysses_attention(
                "seq", inner_attention=flash_inner
            )
        else:
            attention_fn = flash_inner
    # Expert parallelism: scheduler-chosen (ADAPTDL_EXPERT_SHARDS);
    # only meaningful when the model actually has experts.
    expert_shards = env.expert_shards() if args.moe_experts > 0 else 1
    stage_shards = (
        args.stage_shards
        if args.stage_shards is not None
        else env.stage_shards()
    )
    pipeline_family = args.pipeline or stage_shards > 1
    if args.zero3_blocks:
        assert not (args.zero1 or args.zero3), (
            "--zero3-blocks is a storage mode of its own; drop "
            "--zero1/--zero3"
        )
        assert (
            not pipeline_family
            and args.moe_experts == 0
            and (args.tp_shards or env.model_shards()) <= 1
            and not args.flash
            and args.chunked_xent == 0
        ), (
            "--zero3-blocks shards parameter storage over the data "
            "axis and composes with data and sequence parallelism "
            "only"
        )
    if args.zero3:
        args.zero1 = True  # zero3 implies the zero1 constraints below
    if args.zero1:
        assert (
            not pipeline_family
            and args.moe_experts == 0
            and (args.tp_shards or env.model_shards()) <= 1
        ), (
            "--zero1 shards optimizer state over the data axis and "
            "composes with dp/seq only; stage/expert/tensor axes "
            "manage their own optimizer layouts"
        )
    if pipeline_family:
        assert (
            seq_shards <= 1
            and args.moe_experts == 0
            and not args.flash
            and args.chunked_xent == 0
        ), (
            "this example composes the stage axis with dp and tensor "
            "parallelism (ring attention / MoE / flash / chunked-xent "
            "own their axes or loss head); drop "
            "--pipeline/--stage-shards to use them"
        )
        # Export NOW: env.pipeline_micro()'s stage-aware default and
        # the trainer's topology registration both read it.
        os.environ["ADAPTDL_STAGE_SHARDS"] = str(stage_shards)
    config = lm_config(
        on_cpu,
        seq_len,
        remat_policy=args.remat_policy,
        seq_axis="seq" if seq_shards > 1 else None,
        seq_attention=args.seq_mode,
        attention_fn=attention_fn,
        moe_every_n=2 if args.moe_experts > 0 else 0,
        moe_num_experts=args.moe_experts,
        moe_axis="expert" if expert_shards > 1 else None,
        moe_top_k=args.moe_top_k,
    )
    transform_save = transform_load = None
    pipeline_micro = 1
    if stage_shards > 1:
        # Pipelined body: GPipe, or the interleaved schedule when the
        # layer count divides into v = L/ss > 1 chunks per device and
        # M covers the wrap-hop window (models/pipeline_lm.py).
        from adaptdl_tpu.models.pipeline_lm import (
            init_pipeline_lm,
            pipeline_checkpoint_transforms,
        )

        pipeline_micro = (
            args.pipeline_micro
            if args.pipeline_micro is not None
            else env.pipeline_micro()
        )
        interleave = 1
        if (
            config.num_layers % stage_shards == 0
            and config.num_layers // stage_shards > 1
            and pipeline_micro >= stage_shards
        ):
            interleave = config.num_layers // stage_shards
        loss_fn, params = init_pipeline_lm(
            config,
            num_stages=stage_shards,
            num_micro=pipeline_micro,
            interleave=interleave,
            seq_len=seq_len,
        )
        transform_save, transform_load = pipeline_checkpoint_transforms(
            stage_shards, interleave
        )
    elif args.zero3_blocks:
        from adaptdl_tpu.models import init_zero3_lm

        # The zero3_lm loss is written against Zero3View (per-block
        # gather inside its layer scan) and consumes raw token rows.
        # Its canonical checkpoint layout is ALREADY the shared
        # {embed, ln_f, blocks layer-major} tree, so no transforms.
        loss_fn, params = init_zero3_lm(config, seq_len=seq_len)
    else:
        model, params = init_transformer(config, seq_len=seq_len)
        if args.moe_experts == 0:
            # Persist the same canonical layout the pipelined build
            # uses, so the scheduler can move this job between ss=1
            # and ss>1 across restarts and either incarnation
            # restores the other's checkpoint. (MoE stacks are
            # heterogeneous and cannot canonicalize.)
            from adaptdl_tpu.models.pipeline_lm import (
                dense_lm_checkpoint_transforms,
            )

            transform_save, transform_load = (
                dense_lm_checkpoint_transforms(config.num_layers)
            )

        loss_fn = dense_lm_loss(model, args.chunked_xent)

    # ADAPTDL_NUM_REPLICAS counts CHIPS at launch; a seq-, tensor- or
    # expert-sharded group of chips forms one data-parallel replica,
    # so rewrite it to the derived dp count (env.data_parallel_replicas
    # divides by every shard axis the scheduler assigned).
    tp_shards = (
        args.tp_shards if args.tp_shards is not None else env.model_shards()
    )
    group = seq_shards * tp_shards * expert_shards * stage_shards
    if group > 1:
        os.environ["ADAPTDL_SEQ_SHARDS"] = str(seq_shards)
        os.environ["ADAPTDL_MODEL_SHARDS"] = str(tp_shards)
        os.environ["ADAPTDL_EXPERT_SHARDS"] = str(expert_shards)
        os.environ["ADAPTDL_STAGE_SHARDS"] = str(stage_shards)
        data_shards = env.data_parallel_replicas()
        os.environ["ADAPTDL_NUM_REPLICAS"] = str(data_shards)
    else:
        data_shards = env.num_replicas()
    num_devices = data_shards * group
    mesh_axes = {"data": data_shards}
    if seq_shards > 1:
        mesh_axes["seq"] = seq_shards
    if tp_shards > 1:
        mesh_axes["model"] = tp_shards
    if stage_shards > 1:
        mesh_axes["stage"] = stage_shards
    if expert_shards > 1:
        mesh_axes["expert"] = expert_shards
    mesh = create_mesh(mesh_axes, devices=jax.devices()[:num_devices])
    param_sharding_fn = None
    if stage_shards > 1:
        if tp_shards > 1:
            # Stage x tensor parallelism composed: block leaves
            # manual on "stage", GSPMD-auto on "model".
            from adaptdl_tpu.models.pipeline_lm import (
                pipeline_lm_tp_sharding_fn,
            )

            param_sharding_fn = pipeline_lm_tp_sharding_fn
        else:
            from adaptdl_tpu.models.pipeline_lm import (
                pipeline_lm_sharding_fn,
            )

            param_sharding_fn = pipeline_lm_sharding_fn
    elif tp_shards > 1:
        from adaptdl_tpu.parallel.tensor_parallel import (
            transformer_tp_specs,
        )

        param_sharding_fn = transformer_tp_specs
    if expert_shards > 1:
        from adaptdl_tpu.models.transformer import (
            moe_param_sharding_fn,
        )

        tp_fn = param_sharding_fn

        def param_sharding_fn(path, leaf):  # noqa: F811
            from jax.sharding import PartitionSpec as P

            spec = moe_param_sharding_fn(path, leaf)
            if spec != P():
                return spec
            return tp_fn(path, leaf) if tp_fn is not None else P()
    trainer = make_trainer(
        loss_fn,
        params,
        mesh,
        param_sharding_fn=param_sharding_fn,
        # The M the pipelined loss_fn was actually built with — the
        # dataloader sizes per-replica batches to divide by it.
        pipeline_micro=pipeline_micro if stage_shards > 1 else None,
        zero1=args.zero1,
        zero3=args.zero3,
        zero3_blocks="blocks" if args.zero3_blocks else None,
    )
    holder = {"state": trainer.init_state()}
    ckpt = trainer.make_checkpoint_state(
        lambda: holder["state"],
        lambda s: holder.__setitem__("state", s),
        # Layer-major canonical disk layout: a scheduler-driven change
        # of (stage_shards, interleave) between restarts restores
        # weights and optimizer moments restacked for the new schedule.
        transform_save=transform_save,
        transform_load=transform_load,
    )
    checkpoint.load_state(ckpt)
    metrics.ensure_checkpoint_registered()

    raw = synthetic_tokens(
        4096 if on_cpu else 65536, seq_len, config.vocab_size
    )["tokens"]
    if args.zero3_blocks and seq_shards > 1:
        # Long-context zero3_blocks: pre-split so the seq dim shards
        # cleanly (models/zero3_lm.py's seq contract).
        dataset = shifted(raw)
    elif stage_shards > 1 or args.zero3_blocks:
        # The pipelined and zero3-blocks losses consume raw token rows
        # and shift internally (models/{pipeline_lm,zero3_lm}.py).
        dataset = {"tokens": raw}
    else:
        dataset = shifted(raw)
    loader = make_loader(dataset)
    # Advertise how far this model can shard each sample: the largest
    # power of two dividing seq_len (the scheduler only picks
    # power-of-two factorizations, and a non-dividing choice would
    # assert on every restart), and TP up to the head count. Ulysses
    # additionally swaps the sharded axis onto heads, so its cap is
    # also bounded by the largest power of two dividing num_heads
    # (ulysses_attention raises on a non-dividing shard count —
    # advertising one would crash-loop every restart). --flash with
    # ring mode advertises 1 for the same reason: the flash path
    # asserts against ring sharding.
    max_sp = 1
    if not args.flash or args.seq_mode == "ulysses":
        while max_sp * 2 <= 8 and seq_len % (max_sp * 2) == 0:
            max_sp *= 2
    if args.seq_mode == "ulysses":
        while max_sp > 1 and config.num_heads % max_sp != 0:
            max_sp //= 2
    # Advertise ONLY topologies this process would actually run: the
    # pipeline family composes with dp and TENSOR parallelism
    # (pipeline_lm_tp_sharding_fn), so tp advertises normally while
    # sp/ep advertise 1 — the scheduler never prices a combination
    # the build can't execute. The family is flag-stable across
    # restarts, so ss = 1 incarnations keep advertising the stage
    # axis (canonical checkpoints restore either way).
    stage_mode = pipeline_family
    metrics.set_topology_config(
        max_seq_shards=1 if stage_mode else max_sp,
        # pallas_call is opaque to GSPMD: under a model axis the
        # flash kernel's q/k/v would be all-gathered and attention
        # recomputed per shard, so don't advertise TP with --flash.
        # ...and under --zero1 advertise NO tp/stage/expert axes: the
        # trainer rejects them (sharded-param layouts manage their own
        # optimizer state), so a scheduler-chosen tp rescale would
        # crash-loop every restart.
        max_model_shards=(
            1
            if args.flash or args.zero1 or args.zero3_blocks
            else min(config.num_heads, 8)
        ),
        # Stage shards must divide the layer count (uniform chunks);
        # advertise the largest power of two dividing L, and declare
        # the interleaved schedule's chunk pool (= the layer count) so
        # the topology search prices v = L/ss stage candidates.
        max_stage_shards=(
            (config.num_layers & -config.num_layers)
            if stage_mode
            else 1
        ),
        pipeline_chunks=config.num_layers if stage_mode else 0,
        pipeline_microbatches=max(pipeline_micro, 1),
        # Expert shards must divide the expert count (a shard owns
        # E/ep whole experts) and the scheduler only picks powers of
        # two — advertise the largest power of two dividing E.
        max_expert_shards=(
            (args.moe_experts & -args.moe_experts)
            if args.moe_experts > 0 and not stage_mode
            else 1
        ),
    )
    # Optional TensorBoard export (native writer, no TF needed):
    # active when ADAPTDL_SHARE_PATH points at a log directory.
    from adaptdl_tpu.tensorboard import MetricsWriter

    tb = MetricsWriter()
    for e in epoch.remaining_epochs_until(args.epochs):
        for batch in loader:
            holder["state"], m = trainer.run_step(
                holder["state"], batch, loader
            )
        # TB step = the trainer's optimizer-step counter: it restores
        # from the checkpoint, so steps stay monotonic across elastic
        # restarts (a process-local counter would reset and garble
        # the charts).
        tb.write(int(holder["state"].step), m, dataloader=loader)
        tb.flush()
        print(
            f"epoch {e}: loss={float(m['loss']):.4f} "
            f"batch_size={loader.current_batch_size} "
            f"mesh={dict(mesh.shape)}"
        )


if __name__ == "__main__":
    main()
