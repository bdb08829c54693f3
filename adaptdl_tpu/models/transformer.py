"""Flagship decoder-only transformer LM (reference families:
examples/transformer/ WMT LM and examples/BERT/ MLM).

TPU-first design notes:

- einsum-shaped attention and MLP so XLA tiles every contraction onto
  the MXU; compute dtype bfloat16 on TPU, params float32.
- pre-LN blocks with optional per-block rematerialisation
  (``jax.checkpoint`` via ``nn.remat``) to trade FLOPs for HBM.
- RoPE positions (no position table to re-shard on sequence-length
  changes).
- the attention inner function is pluggable: the default is plain
  causal attention; the sequence-parallel path substitutes ring
  attention from ``adaptdl_tpu.parallel.ring_attention`` without
  touching the rest of the model.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.ad_checkpoint import checkpoint_name

from adaptdl_tpu import device_budget, trace


@dataclass(frozen=True)
class Yarn:
    """YaRN's frequencies for a rotary of base ``theta`` over ``D``
    lanes (``yarn_frequencies``): pair ``i`` turns by ``f_i (1 - r_i)
    + (f_i / factor) r_i``, ``r`` a ramp from the pair that makes
    ``beta_fast`` turns in ``original_max_position`` positions to the
    one that makes ``beta_slow``; cosine and sine of the rotated lanes
    are multiplied by ``attention_factor`` (None: ``0.1 ln(factor) +
    1``)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None

    @property
    def scale(self) -> float:
        if self.attention_factor is not None:
            return float(self.attention_factor)
        return 0.1 * math.log(self.factor) + 1.0


@dataclass(frozen=True)
class AttentionKind:
    """What ONE softmax-attention mixer kind of ``layer_types`` has of
    its own (``TransformerConfig.attention_kinds``); None = the
    config's. ``window``: query ``i`` sees the keys ``j <= i`` with
    ``i - j < window``. ``rope``: whether the kind's q and k are turned
    by rotary at all (False: a layer without positional encoding
    beside layers that have one)."""

    num_heads: int | None = None
    rope_theta: float | None = None
    rotary_dims: int | None = None
    yarn: Yarn | None = None
    window: int | None = None
    rope: bool | None = None


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 2048
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # Rematerialisation policy (a jax.checkpoint_policies name, e.g.
    # "dots_with_no_batch_dims_saveable" to keep matmul outputs and
    # recompute only the cheap elementwise ops). A remat'd block
    # ALWAYS keeps the flash kernel's output and log-sum-exp
    # (block_remat: attention is never computed twice; per layer one
    # [batch, seq, d_model] activation plus 4 bytes a head and
    # position), and beyond them, where a trainer traces the model on
    # a device that has the bytes, as many rungs as fit of q / k / v,
    # the residual after the mixer and ff_up's result (3 + 1 + d_ff /
    # d_model activations a layer; _remat_ladder: no knob, the
    # trainer's budget decides). A named policy ADDS to that, so None
    # and "nothing_saveable" add nothing. The policy trades recompute
    # FLOPs for HBM — the knob to turn when a job should keep MORE
    # than the ladder takes by itself.
    remat_policy: str | None = None
    # attention_fn(q, k, v) -> out; q/k/v are [batch, heads, seq,
    # head_dim]; None selects plain causal attention (or ring
    # attention when seq_axis is set).
    attention_fn: Callable | None = None
    # Mesh axis the sequence dim is sharded over (sequence
    # parallelism): positions become global and attention defaults to
    # ``seq_attention`` over this axis.
    seq_axis: str | None = None
    # Which sequence-parallel attention runs over seq_axis: "ring"
    # (ppermute K/V rotation, any head count, O(seq/shards) memory —
    # parallel/ring_attention.py) or "ulysses" (two all_to_all head
    # exchanges around one full-sequence attention; needs
    # num_heads % seq_shards == 0 — parallel/ulysses.py).
    seq_attention: str = "ring"
    # causal=False gives bidirectional (encoder / BERT-style)
    # attention — the MLM families (reference: examples/BERT/) — for
    # both the plain and the ring attention paths.
    causal: bool = True
    # Mixture-of-experts: every ``moe_every_n``-th block (1-indexed;
    # 0 disables) replaces its dense FFN with a Switch/GShard MoE of
    # ``moe_num_experts`` experts. With ``moe_axis`` set the experts
    # shard over that mesh axis (all_to_all dispatch inside the
    # trainer's shard_map); otherwise they run densely on-device.
    # The load-balancing auxiliary loss is sown into the
    # "moe_losses" collection — lm_loss_fn/mlm_loss_fn add it with
    # weight ``moe_aux_weight`` (without it the router collapses onto
    # one expert).
    moe_every_n: int = 0
    moe_num_experts: int = 0
    moe_axis: str | None = None
    moe_capacity_factor: float = 2.0
    moe_top_k: int = 1
    moe_aux_weight: float = 1e-2
    # "tokens" (Switch/GShard token-choice) or "experts"
    # (expert-choice, arXiv:2202.09368: structural balance, aux = 0).
    # CAVEAT: expert-choice ranks across the whole token slice, so a
    # token's routing depends on LATER tokens — not causally valid for
    # autoregressive training/decoding; intended for encoder/MLM
    # models (causal=False), the paper's setting.
    moe_router: str = "tokens"
    # Test/equivalence knob: the dense (moe_axis=None) path bins
    # token slices as if the batch were split across this many
    # devices, matching an expert-parallel run's per-device capacity.
    moe_dense_slices: int = 1
    # ---- block options beyond the GPT-2 shape -----------------------
    # "layernorm" (scale only) or "rmsnorm"; ``norm_eps`` is either's.
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    # "gelu" (up, gelu, down) or "swiglu" (down(silu(gate) * up)).
    ffn: str = "gelu"
    # Grouped-query attention: ``num_heads`` query heads on this many
    # key/value heads (query head i on kv head i // group); None =
    # one kv head a query head. ``qk_norm``: RMSNorm over each head of
    # q and k (one learned scale of head_dim), before rotary. These
    # two, and no other option, change the attention's parameter tree
    # (``q`` + ``kv`` for the fused ``qkv``) and have no
    # sequence-parallel path.
    num_kv_heads: int | None = None
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # One mixer kind a layer: "full_attention", "conv" (the gated
    # short convolution, ``conv_kernel`` causal taps a channel) or
    # "sparse_attention" (below); None = attention everywhere.
    layer_types: tuple[str, ...] | None = None
    conv_kernel: int = 3
    # Dropless routed experts (models/moe.py: routed_experts): layers
    # from ``num_dense_layers`` on replace the FFN with a sigmoid
    # top-``experts_top_k`` router over ``experts_total`` experts of
    # width ``d_expert``, of which THIS model holds ``experts_held``
    # starting at ``first_expert`` (all of them when None) — one
    # chip's share of an expert-parallel deployment; what the absent
    # experts would add is left out. 0 experts = no routed layer.
    experts_total: int = 0
    experts_held: int | None = None
    first_expert: int = 0
    experts_top_k: int = 4
    d_expert: int = 0
    num_dense_layers: int = 0
    expert_weight_eps: float = 1e-20
    routed_scaling_factor: float = 1.0
    # "sigmoid" (scores + a selection-only bias buffer) or "softmax"
    # (probabilities over all experts, no bias buffer); either way the
    # chosen weights are divided by their sum.
    experts_router: str = "sigmoid"
    # From how many times the rows an even router places (with their
    # room: ``models.moe.rows_bound``) of WORST case on a routed layer
    # walks its plan in pieces of the bound, instead of holding and
    # walking buffers of the worst case on every step; None = the
    # layer's own threshold (``models.moe.ROWS_PIECES_FROM``, 4). The
    # knob to lower for a share whose router stays near even routing
    # and whose device has no room for the worst case: what pieces
    # cost a share whose router settles on its held experts is in
    # ``models/moe.py``.
    experts_pieces_from: float | None = None
    # What a routed layer's router reads: "ffn_input", the normed
    # state its experts multiply (after the mixer), or "block_input",
    # the residual stream as the block RECEIVES it, un-normed, ahead
    # of the mixer — the choice then depends on nothing the block
    # computes, and the router's gradient reaches the block's input
    # directly (``models.moe.routed_experts``: ``routed_on``).
    experts_routed_on: str = "ffn_input"
    # A routed expert's gate function: "silu" (SwiGLU) or "relu"
    # (ReGLU: ``down(relu(gate x) * up x)``); the shared expert and the
    # dense FFNs stay SwiGLU.
    experts_activation: str = "silu"
    # Width of one attention head where it is not ``d_model //
    # num_heads`` (the q / kv projections then map ``d_model`` to
    # ``heads * head_dim`` and ``out`` maps back).
    head_dim: int | None = None
    # The "sparse_attention" mixer kind of ``layer_types``: grouped-
    # query attention over the ``index_topk`` keys a learned indexer
    # (``index_heads`` heads of ``index_dim`` on ONE key head) selects
    # for each query (ops/sparse_attention.py); the indexer trains on
    # its own loss, sown into "indexer_loss".
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # False: an output table of its own (``lm_head``) instead of the
    # embedding's.
    tie_embeddings: bool = True
    # Sandwich normalisation: a second norm on the mixer's and on the
    # FFN's RESULT, before each is added to the residual stream (four
    # norms a block, ``a = x + N2(Mixer(N1 x))``, ``y = a + N4(FFN(N3
    # a))``).
    sandwich_norm: bool = False
    # A looped stack: ``layer_0 .. layer_{num_layers - 1}`` applied
    # ``loop_passes`` times a forward pass with ONE set of parameters
    # (a ``lax.scan`` over the passes, the parameters broadcast), the
    # final norm after every pass — the next pass starts from the
    # NORMED state — and after every pass an exit: the normed state
    # through the one output table, and a learned gate on it
    # (``exit_gate``, one linear ``d_model -> 1`` with bias, float32).
    # 1 = every block once, no gate: the tree and program of before.
    # Dense blocks without dropout only (``looped_lm_loss_fn``).
    loop_passes: int = 1
    # False: no rotary turn on any layer's q and k (a model whose
    # recurrent layers order the tokens has no positions).
    rope: bool = True
    # The "kda" mixer kind of ``layer_types``: gated delta-rule linear
    # attention with a per-channel decay (ops/kda.py), ``num_heads``
    # heads of ``attention_head_dim`` for q, k and v, depthwise causal
    # convolutions of ``conv_kernel`` taps on each, the decay and the
    # output gate through low-rank pairs of ``kda_gate_rank``, the
    # recurrence carried between chunks of ``ops.kda.CHUNK`` tokens. No
    # sequence-parallel path: the state crosses the whole row.
    kda_gate_rank: int = 0
    # The "mla" mixer kind: latent attention. ``q`` is ``num_heads``
    # heads of ``qk_nope_head_dim + qk_rope_head_dim``; ``kv_a`` maps
    # to ``kv_lora_rank`` (normed) and ONE shared key part of
    # ``qk_rope_head_dim``; ``kv_b`` expands the latent to each head's
    # ``qk_nope_head_dim`` of key and ``v_head_dim`` of value. Under
    # ``rope`` the ``qk_rope_head_dim`` lanes (and no other) are turned
    # by rotary at ``rope_theta``: every head's of q, the shared key
    # part once, before it goes onto the heads. ``q_lora_rank`` > 0:
    # the query bottleneck, ``q = rmsnorm(x W_qa) W_qb`` through that
    # many channels (``q_a``, ``q_norm``, ``q_b`` for ``q``).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: int = 0
    # A shared expert beside the routed ones: one gated FFN of this
    # width on EVERY token of a routed layer, unweighted, added to the
    # routed experts' result. 0 = none.
    d_shared_expert: int = 0
    # The shared expert's result multiplied by ``sigmoid(x w_s)``, one
    # learned gate ``d_model -> 1`` a routed layer (``shared_gate``).
    shared_expert_gate: bool = False
    # Rotary over the first ``rotary_dims`` lanes of a head only (the
    # others pass untouched); None = every lane.
    rotary_dims: int | None = None
    # An output gate on softmax attention: ``q`` is projected twice as
    # wide, a head's second half the gate, and ``out`` takes ``o *
    # sigmoid(gate)``. Selects ``GroupedQueryAttention``.
    attention_gate: bool = False
    # With ``norm="rmsnorm"``: every norm of the model (block norms,
    # final norm, the attention's head norms) scales by ``1 + w``, ``w``
    # initialised 0 (``ZeroCentredRMSNorm``).
    norm_zero_centred: bool = False
    # The "gdn" mixer kind: the gated delta rule with ONE decay a value
    # head and token (``GatedDeltaNet``): ``linear_key_heads`` heads of
    # ``linear_key_head_dim`` for q and k serve ``linear_value_heads``
    # heads of ``linear_value_head_dim`` for v, one depthwise causal
    # convolution of ``conv_kernel`` taps over q, k and v.
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    # What differs between the softmax-attention kinds of
    # ``layer_types``, said once a kind: ``(("full_attention",
    # AttentionKind(...)), ("sliding_attention", AttentionKind(...,
    # window=512)))`` — the number of query heads (on the config's
    # ``num_kv_heads``), the rotary base, the rotated lanes, YaRN's
    # frequencies, the window. A kind with an entry runs
    # ``GroupedQueryAttention``; "sliding_attention" needs one with a
    # ``window``. Empty = every attention layer as the config says.
    attention_kinds: tuple[tuple[str, AttentionKind], ...] = ()
    # A per-head output gate on softmax attention: ``sigmoid(x W_g)``,
    # ``W_g: d_model -> heads`` (``gate``), ONE gate a head and token,
    # multiplied into the head's output before ``out``
    # (``attention_gate`` is the full-rank one). Selects
    # ``GroupedQueryAttention``.
    attention_head_gate: bool = False
    # Multi-token prediction (DeepSeek-V3, arXiv:2412.19437, section
    # 2.2), depth 0 or 1: one module after the trunk (``mtp``) that
    # predicts the token after the next. It has no tables of its own:
    # ``u_i = W_eh [norm_e(Emb(t_{i+1})) ; norm_h(h_i)]`` with the
    # MODEL's embedding and the trunk's state ``h`` before the final
    # norm, one whole block on ``u`` (index ``num_layers``: the last
    # trunk layer's mixer kind, routed where the trunk's layers are),
    # a final norm of its own, and the model's output table.
    # ``routed_lm_loss_fn`` adds ``mtp_loss_weight`` times its mean
    # cross-entropy against the targets one place further on.
    mtp_depth: int = 0
    mtp_loss_weight: float = 0.1

    def __post_init__(self):
        kinds = self.layer_types or ()
        of_kind = dict(self.attention_kinds)
        if len(of_kind) != len(self.attention_kinds):
            raise ValueError("attention_kinds names a kind twice")
        for name, kind in of_kind.items():
            if name not in ("full_attention", "sliding_attention"):
                raise ValueError(
                    f"attention_kinds: {name!r} is no softmax-attention "
                    "kind ('full_attention', 'sliding_attention')"
                )
            heads = kind.num_heads or self.num_heads
            if heads % (self.num_kv_heads or heads):
                raise ValueError(
                    f"attention_kinds[{name!r}]: {heads} query heads on "
                    f"{self.num_kv_heads} kv heads"
                )
            if kind.window is not None and kind.window < 1:
                raise ValueError(
                    f"attention_kinds[{name!r}]: window {kind.window}"
                )
            turned = self.rope if kind.rope is None else kind.rope
            if kind.yarn is not None and not turned:
                raise ValueError(
                    f"attention_kinds[{name!r}]: yarn without rope"
                )
            if kind.rotary_dims is not None and not turned:
                raise ValueError(
                    f"attention_kinds[{name!r}]: rotary_dims without rope"
                )
        if "sliding_attention" in kinds:
            if getattr(of_kind.get("sliding_attention"), "window", None) is None:
                raise ValueError(
                    "a 'sliding_attention' layer needs attention_kinds to "
                    "give the kind its window"
                )
            if self.seq_axis is not None:
                raise ValueError(
                    "seq_axis: a 'sliding_attention' layer has no "
                    "sequence-parallel path (ring attention does not "
                    "fold a window)"
                )
        if self.attention_head_gate and self.attention_gate:
            raise ValueError(
                "attention_head_gate and attention_gate: one output gate"
            )
        if self.experts_routed_on not in ("ffn_input", "block_input"):
            raise ValueError(
                "experts_routed_on must be 'ffn_input' or 'block_input', "
                f"got {self.experts_routed_on!r}"
            )
        if self.experts_activation not in ("silu", "relu"):
            raise ValueError(
                "experts_activation must be 'silu' or 'relu', got "
                f"{self.experts_activation!r}"
            )
        if "gdn" in kinds:
            if self.seq_axis is not None:
                raise ValueError(
                    "seq_axis: a 'gdn' layer has no sequence-parallel "
                    "path (its state crosses the whole row)"
                )
            for name in ("linear_key_heads", "linear_value_heads",
                         "linear_key_head_dim", "linear_value_head_dim"):
                if getattr(self, name) <= 0:
                    raise ValueError(f"{name} must be set for 'gdn' layers")
            if self.linear_value_heads % self.linear_key_heads:
                raise ValueError(
                    f"{self.linear_key_heads} key heads do not divide "
                    f"{self.linear_value_heads} value heads"
                )
        if self.norm_zero_centred and self.norm != "rmsnorm":
            raise ValueError("norm_zero_centred takes norm='rmsnorm'")
        if self.shared_expert_gate and self.d_shared_expert <= 0:
            raise ValueError(
                "shared_expert_gate: no shared expert (d_shared_expert is 0)"
            )
        if "kda" in kinds and self.seq_axis is not None:
            raise ValueError(
                "seq_axis: a 'kda' layer has no sequence-parallel path "
                "(its state crosses the whole row)"
            )
        if "kda" in kinds and self.kda_gate_rank <= 0:
            raise ValueError("kda_gate_rank must be set for 'kda' layers")
        if "sparse_attention" in kinds and not self.rope:
            raise ValueError(
                "rope: the 'sparse_attention' mixer always turns q and k"
            )
        if "mla" in kinds:
            if self.rope and (
                self.qk_rope_head_dim <= 0 or self.qk_rope_head_dim % 2
            ):
                raise ValueError(
                    "rope: the 'mla' mixer turns its qk_rope_head_dim "
                    f"lanes ({self.qk_rope_head_dim}: set an even number "
                    "of them, or rope=False)"
                )
            for name in ("kv_lora_rank", "qk_nope_head_dim", "v_head_dim"):
                if getattr(self, name) <= 0:
                    raise ValueError(f"{name} must be set for 'mla' layers")
        if self.q_lora_rank and "mla" not in kinds:
            raise ValueError(
                "q_lora_rank: the query bottleneck is the 'mla' mixer's"
            )
        if self.mtp_depth not in (0, 1):
            raise ValueError(
                f"mtp_depth {self.mtp_depth}: one prediction module or none"
            )
        if self.mtp_depth and (
            self.loop_passes > 1 or self.seq_axis is not None
            or self.dropout_rate > 0
        ):
            raise ValueError(
                "mtp_depth: the prediction module takes neither "
                "loop_passes, seq_axis nor dropout_rate"
            )
        if self.d_shared_expert > 0 and self.experts_total <= 0:
            raise ValueError(
                "d_shared_expert: a shared expert stands beside routed "
                "experts (experts_total is 0)"
            )

    @property
    def attention_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def switch_moe(self, layer: int) -> bool:
        """Whether block ``layer`` has the Switch FFN (moe_every_n)."""
        return (
            self.moe_every_n > 0
            and self.moe_num_experts > 0
            and (layer + 1) % self.moe_every_n == 0
        )

    def mixer(self, layer: int) -> str:
        """The mixer kind of block ``layer``; the prediction module's
        block (``num_layers``) has the last trunk layer's."""
        return (
            "full_attention"
            if self.layer_types is None
            else self.layer_types[min(layer, self.num_layers - 1)]
        )

    def routed(self, layer: int) -> bool:
        return self.experts_total > 0 and layer >= self.num_dense_layers

    def attention_kind(self, kind: str) -> AttentionKind:
        """The softmax-attention kind ``kind`` with the config's values
        where ``attention_kinds`` says nothing (``rotary_dims`` None =
        every lane, as the config's; of a kind without rotary, none)."""
        own = dict(self.attention_kinds).get(kind, AttentionKind())
        turned = self.rope if own.rope is None else own.rope
        return AttentionKind(
            num_heads=own.num_heads or self.num_heads,
            rope_theta=own.rope_theta or self.rope_theta,
            rotary_dims=(own.rotary_dims or self.rotary_dims) if turned
            else None,
            yarn=own.yarn,
            window=own.window,
            rope=turned,
        )

    def layer_heads(self, layer: int) -> int:
        """Query heads of layer ``layer``'s softmax attention."""
        return self.attention_kind(self.mixer(layer)).num_heads


def yarn_frequencies(theta: float, rotary_dims: int, yarn: Yarn):
    """The ``rotary_dims / 2`` pair frequencies of a YaRN rotary
    (``Yarn``), float32: ``f_i = theta ** (-2i / D)`` blended with
    ``f_i / factor`` by the ramp ``clip((i - lo) / (hi - lo), 0, 1)``,
    ``lo`` / ``hi`` the (floored / ceiled, clamped) pairs that make
    ``beta_fast`` / ``beta_slow`` turns in the original context.
    Computed in float64 on the host: a table, not part of the
    program."""
    import numpy as np

    def pair_of(turns: float) -> float:
        return (
            rotary_dims
            * math.log(yarn.original_max_position / (turns * 2 * math.pi))
            / (2 * math.log(theta))
        )

    lo = max(math.floor(pair_of(yarn.beta_fast)), 0)
    hi = min(math.ceil(pair_of(yarn.beta_slow)), rotary_dims - 1)
    pair = np.arange(rotary_dims // 2, dtype=np.float64)
    base = theta ** (-2.0 * pair / rotary_dims)
    ramp = np.clip((pair - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (base * (1.0 - ramp) + base / yarn.factor * ramp).astype(
        np.float32
    )


def rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
    rotary_dims: int | None = None, freqs=None, scale: float = 1.0,
) -> jnp.ndarray:
    """Rotary position embedding over the last (head_dim) axis:
    adjacent pairs ``(x[2i], x[2i + 1])`` turned by ``positions *
    theta ** (-2i / head_dim)``. With ``rotary_dims`` only the first
    that many lanes turn (by ``theta ** (-2i / rotary_dims)``); the
    others have cosine 1 and sine 0, exactly. ``freqs``: the pairs'
    frequencies as a table (``yarn_frequencies``; two lanes a pair,
    the first lanes of a head) in place of ``theta``'s, and ``scale``
    what the rotated lanes' cosine and sine are multiplied by.

    x: [batch, seq, heads, head_dim], the projection's own layout;
    positions: [seq].

    Written as ``x * cos + partner(x) * (-+sin)`` at full width, the
    partner of a lane the other one of its pair, taken by a
    ``[head_dim, head_dim]`` permutation as a matmul (exact: one 1 a
    column): one pass that XLA fuses, in its own layout. Strided halves
    (``x[..., 0::2]``) and their ``stack`` cost gathers and a relayout
    through an array whose minor dimension is 2, and a lane roll is
    slices and pads that XLA leaves unfused (PERF.md, PR 27); the
    values are the same to the bit.
    """
    lane = jnp.arange(x.shape[-1])
    if freqs is not None:
        table = jnp.asarray(freqs, jnp.float32)
        rotary_dims = 2 * table.shape[0]
        assert rotary_dims <= x.shape[-1]
        freqs = jnp.where(
            lane < rotary_dims,
            table[jnp.minimum(lane // 2, table.shape[0] - 1)], 0.0,
        )
    elif rotary_dims is None:
        freqs = 1.0 / (theta ** ((lane // 2 * 2) / x.shape[-1]))
    else:
        assert rotary_dims % 2 == 0 and rotary_dims <= x.shape[-1]
        freqs = jnp.where(
            lane < rotary_dims,
            1.0 / (theta ** ((lane // 2 * 2) / rotary_dims)), 0.0,
        )
    angles = positions[:, None] * freqs[None, :]  # [seq, head_dim]
    sin, cos = jnp.sin(angles), None
    if scale != 1.0:  # of the rotated lanes: the others keep 0 and 1
        turned = lane < (x.shape[-1] if rotary_dims is None else rotary_dims)
        sin = sin * scale
        cos = jnp.where(turned, jnp.cos(angles) * scale, 1.0)
    sin = sin.astype(x.dtype)
    sin = jnp.where(lane % 2 == 0, -sin, sin)[:, None, :]
    cos = (jnp.cos(angles) if cos is None else cos).astype(x.dtype)
    cos = cos[:, None, :]
    swap = (lane[:, None] == (lane ^ 1)[None, :]).astype(x.dtype)
    partner = jnp.einsum(
        "...d,de->...e", x, swap, precision=jax.lax.Precision.HIGHEST
    )
    return x * cos + partner * sin


def causal_attention(q, k, v, axis_name=None, causal=True, window=None):
    """Plain attention; q/k/v: [batch, heads, seq, head_dim].
    ``causal=False`` attends bidirectionally (encoder-style).
    ``window`` (causal only): a query sees itself and the ``window -
    1`` keys before it."""
    del axis_name
    seq_len = q.shape[2]
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale
    if causal:
        mask = jnp.tril(jnp.ones((seq_len, seq_len), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((seq_len, seq_len), bool), -window)
        logits = jnp.where(mask[None, None], logits, -1e30)
    elif window is not None:
        raise ValueError("window: causal attention only")
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


class Attention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        head_dim = cfg.attention_head_dim
        qkv = nn.DenseGeneral(
            (3, cfg.num_heads, head_dim),
            axis=-1,
            dtype=cfg.dtype,
            use_bias=False,
            name="qkv",
        )(x)
        q, k, v = jnp.moveaxis(qkv, -3, 0)  # each [b, s, h, d]
        if cfg.rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        # attention_fn's contract is [b, h, s, d]. The flash kernels
        # index [b * h, d, s], which is how XLA lays these arrays out
        # by itself, and swap into it themselves: their swap and this
        # one (and the one of ``out``) cost no copy between the
        # projections and the kernels (PERF.md, PR 27).
        q = jnp.swapaxes(q, 1, 2)
        k = jnp.swapaxes(k, 1, 2)
        v = jnp.swapaxes(v, 1, 2)
        attn = cfg.attention_fn
        if attn is None:
            if cfg.seq_axis is not None:
                if cfg.seq_attention == "ulysses":
                    from adaptdl_tpu.parallel.ulysses import (
                        make_ulysses_attention,
                    )

                    attn = make_ulysses_attention(
                        cfg.seq_axis, causal=cfg.causal
                    )
                elif cfg.seq_attention == "ring":
                    from adaptdl_tpu.parallel.ring_attention import (
                        make_ring_attention,
                    )

                    attn = make_ring_attention(
                        cfg.seq_axis, causal=cfg.causal
                    )
                else:
                    raise ValueError(
                        "seq_attention must be 'ring' or 'ulysses', "
                        f"got {cfg.seq_attention!r}"
                    )
            else:
                from functools import partial

                attn = partial(causal_attention, causal=cfg.causal)
                # The plain path's rung (a) of ``block_remat``'s
                # ladder; the flash kernel's forward rule names its
                # own operands, in its own layout.
                q, k, v = (checkpoint_name(t, SAVED_QKV) for t in (q, k, v))
        out = attn(q, k, v)  # [b, h, s, d]
        out = jnp.swapaxes(out, 1, 2).reshape(
            x.shape[:-1] + (cfg.num_heads * head_dim,)
        )
        return nn.DenseGeneral(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="out"
        )(out)


class ZeroCentredRMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + epsilon) * (1 + w)`` over the last
    axis, ``w`` initialised 0; statistics and the scaling in float32,
    the result in ``dtype``."""

    epsilon: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros, (x.shape[-1],), jnp.float32
        )
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, -1, keepdims=True) + self.epsilon
        )
        return (normed * (1.0 + scale)).astype(self.dtype)


def _rms_norm(cfg: TransformerConfig, name: str | None = None):
    """The config's RMSNorm (a block's, the final one, a head's)."""
    kind = ZeroCentredRMSNorm if cfg.norm_zero_centred else nn.RMSNorm
    return kind(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)


def make_norm(cfg: TransformerConfig, name: str | None = None):
    """The config's normalisation, scale only: LayerNorm or RMSNorm
    with ``norm_eps`` (statistics in float32, result in ``dtype``)."""
    if cfg.norm == "rmsnorm":
        return _rms_norm(cfg, name)
    if cfg.norm != "layernorm":
        raise ValueError(
            f"norm must be 'layernorm' or 'rmsnorm', got {cfg.norm!r}"
        )
    return nn.LayerNorm(
        epsilon=cfg.norm_eps, dtype=cfg.dtype, use_bias=False, name=name
    )


def _heads_a_call(
    attn, heads, seq_len, qk_width, v_width, itemsize, window=None, group=1
):
    """How many heads one call of ``attn`` is given: as many as the
    flash kernels want at once (``ops.flash_attention.heads_a_call``,
    at the attention's own blocks where it says them: past 16k keys
    the backward writes a float32 dQ a key chunk for the heads it is
    given). Any ``attention_fn`` is asked so: a ``functools.partial``
    of the kernel says nothing of itself but its blocks. All of them
    for plain attention (``attn`` None). ``window``: the layer's,
    where it has one. ``group``: query heads a kv head, where that is
    more than one: a run is then whole groups or a divisor of one."""
    if attn is None:
        return heads
    from adaptdl_tpu.ops.flash_attention import heads_a_call

    said = {
        k: v for k, v in getattr(attn, "keywords", {}).items()
        if k in ("block_q", "block_k")
    }
    if window is not None:
        said["window"] = window
    if group > 1:
        said["group"] = group
    return getattr(attn, "heads_a_call", heads_a_call)(
        heads, seq_len, qk_width, v_width, itemsize, **said,
    )


def _takes_kv_heads(attn) -> bool:
    """Whether ``attn`` takes k and v with FEWER heads than q, each kv
    head serving ``heads // kv_heads`` query heads in
    ``jnp.repeat``'s order: the flash kernels do, and say so of
    themselves (``ops.flash_attention``: ``flash_attention`` and
    ``make_flash_attention``'s result carry ``takes_kv_heads``). Read
    behind any ``functools.partial``, which says nothing of itself
    but its keywords; any other function, and plain attention
    (``attn`` None), is handed equal head counts."""
    while attn is not None and not hasattr(attn, "takes_kv_heads"):
        attn = getattr(attn, "func", None)
    return bool(getattr(attn, "takes_kv_heads", False))


def _attend_in_runs(attn, q, k, v, run, kv_of=None):
    """``attn`` on ``[b, s, h, d]`` operands, ``run`` heads a call, a
    run's result let go before the next run's: ``[b, h, s, d_v]``.
    ``kv_of(t, at)``: what the heads ``at .. at + run`` are handed of k
    or v where that is not the same slice of it (kv heads shared by
    several query heads)."""
    def own(t, at):
        return t[:, :, at:at + run]

    parts = ((q, own), (k, kv_of or own), (v, kv_of or own))
    return jnp.concatenate(
        [
            attn(*(jnp.swapaxes(part(t, at), 1, 2) for t, part in parts))
            for at in range(0, q.shape[2], run)
        ],
        axis=1,
    )


class GroupedQueryAttention(nn.Module):
    """Causal attention with fewer key/value heads than query heads,
    optional per-head RMSNorm on q and k, and the config's rotary
    base (over the first ``rotary_dims`` lanes where that is set).
    The flash kernels (``takes_kv_heads``) are handed a call's query
    heads and THEIR kv heads, unrepeated: the kernels index k and v by
    kv head and sum dK / dV over a group's query heads themselves.
    Any other ``attention_fn``, and plain attention, keeps its
    ``[b, h, s, d]`` contract with equal head counts: each kv head is
    repeated ``group`` times on the way in, and autodiff sums dK / dV
    over the group on the way out (``kv_repeat`` in the journal: how
    many copies of a kv head a call is made). With
    ``attention_gate`` the ``q`` projection is twice as wide, a head's
    second half a gate, and ``out`` takes ``o * sigmoid(gate)``; the
    heads then go in runs of as many as the flash kernels want at
    once, and ``gated_attn.schedule`` is journalled where the module
    is traced. ``kind``: the layer's mixer kind; what
    ``attention_kinds`` says of it (query heads, rotary, YaRN, the
    window) is this layer's, ``attention_fn`` is handed the window as
    ``window=`` (a function given to a model with sliding layers takes
    the keyword), and ``attn_kind.schedule`` is journalled. With
    ``attention_head_gate`` one sigmoid gate a head (``gate``)."""

    config: TransformerConfig
    kind: str = "full_attention"

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        if cfg.seq_axis is not None:
            raise ValueError(
                "grouped-query attention has no sequence-parallel path"
            )
        head_dim = cfg.attention_head_dim
        own = cfg.attention_kind(self.kind)
        heads, by_kind = own.num_heads, self.kind in dict(cfg.attention_kinds)
        kv_heads = cfg.num_kv_heads or heads
        group = heads // kv_heads
        assert group * kv_heads == heads, (
            f"{heads} query heads on {kv_heads} kv heads"
        )
        q = nn.DenseGeneral(
            (heads, (2 if cfg.attention_gate else 1) * head_dim),
            axis=-1, dtype=cfg.dtype, use_bias=False, name="q",
        )(x)
        if cfg.attention_gate:
            q, gate = q[..., :head_dim], q[..., head_dim:]
        elif cfg.attention_head_gate:
            gate = nn.DenseGeneral(
                heads, dtype=cfg.dtype, use_bias=False, name="gate"
            )(x)[..., None]  # [b, s, h, 1]: one a head and token
        kv = nn.DenseGeneral(
            (2, kv_heads, head_dim), axis=-1, dtype=cfg.dtype,
            use_bias=False, name="kv",
        )(x)
        k, v = jnp.moveaxis(kv, -3, 0)  # each [b, s, kv_heads, d]
        if cfg.qk_norm:
            q = _rms_norm(cfg, "q_norm")(q)
            k = _rms_norm(cfg, "k_norm")(k)
        if own.rope:
            table = {}
            if own.yarn is not None:
                table = {
                    "freqs": yarn_frequencies(
                        own.rope_theta, own.rotary_dims or head_dim, own.yarn
                    ),
                    "scale": own.yarn.scale,
                }
            q = rope(q, positions, own.rope_theta, own.rotary_dims, **table)
            k = rope(k, positions, own.rope_theta, own.rotary_dims, **table)
        attn = cfg.attention_fn
        run = heads
        grouped = _takes_kv_heads(attn)
        gated = cfg.attention_gate or cfg.attention_head_gate
        if gated or by_kind:
            run = _heads_a_call(
                attn, heads, x.shape[1], head_dim, head_dim,
                jnp.dtype(cfg.dtype).itemsize, own.window, group,
            )
            of_kind = {}
            if by_kind:
                of_kind = {
                    "kind": self.kind, "window": own.window or 0,
                    "rope_theta": own.rope_theta,
                    "yarn_factor": own.yarn.factor if own.yarn else 0,
                }
            trace.event(
                "attn_kind.schedule" if by_kind else "gated_attn.schedule",
                heads=heads,
                kv_heads=kv_heads,
                head_dim=head_dim,
                rotary_dims=(own.rotary_dims or head_dim) if own.rope else 0,
                gate="head" if cfg.attention_head_gate
                else "sigmoid" if cfg.attention_gate else "none",
                **of_kind,
                heads_a_call=run,
                kv_repeat=1 if grouped else min(run, group),
                seq_len=x.shape[1],
                dtype=jnp.dtype(cfg.dtype).name,
                attention="attention_fn" if attn is not None
                else "plain causal attention",
            )
        from functools import partial

        if attn is None:
            attn = partial(causal_attention, causal=cfg.causal)
        if own.window is not None:
            attn = partial(attn, window=own.window)
        if run < heads:
            # A run of heads a call with the kv heads that serve it:
            # the one the run is part of (16 heads of 256 on 2 kv
            # heads go two a call: autodiff adds the runs' dK / dV of
            # a kv head), or its whole groups'. Repeated for the run's
            # heads only for a function that wants equal counts.
            assert run % group == 0 or group % run == 0, (run, group)

            def kv_of(t, at):  # the run's heads of k or v
                first = at // group
                some = t[:, :, first:max(first + 1, (at + run) // group)]
                if grouped:
                    return some
                return jnp.repeat(some, min(run, group), axis=2)

            out = _attend_in_runs(attn, q, k, v, run, kv_of)
        else:
            if not grouped:
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            out = attn(
                jnp.swapaxes(q, 1, 2),
                jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2),
            )  # [b, h, s, d]
        out = jnp.swapaxes(out, 1, 2)
        if gated:
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                cfg.dtype
            )
        out = out.reshape(x.shape[:-1] + (heads * head_dim,))
        return nn.DenseGeneral(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="out"
        )(out)


class Indexer(nn.Module):
    """The lightning indexer's three projections of a block's (stopped)
    input, as the kernels take them: ``index_q`` [b, heads, s,
    index_dim] and ``index_k`` [b, s, index_dim] (ONE key head) in the
    compute dtype, rotary as on q / k; ``index_w`` [b, s, heads]
    float32 with both scales folded in (``index_dim ** -0.5`` of the
    dot and ``heads ** -0.5`` of the weights: a positive factor
    commutes with the ReLU)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        index_q = rope(
            nn.DenseGeneral(
                (cfg.index_heads, cfg.index_dim), axis=-1,
                dtype=cfg.dtype, use_bias=False, name="index_q",
            )(x),
            positions, cfg.rope_theta,
        )
        index_k = rope(
            nn.Dense(
                cfg.index_dim, dtype=cfg.dtype, use_bias=False,
                name="index_k",
            )(x)[:, :, None, :],
            positions, cfg.rope_theta,
        )[:, :, 0, :]
        index_w = nn.Dense(
            cfg.index_heads, dtype=jnp.float32, use_bias=False,
            precision=jax.lax.Precision.HIGHEST, name="index_w",
        )(x) * (cfg.index_heads**-0.5 * cfg.index_dim**-0.5)
        return jnp.swapaxes(index_q, 1, 2), index_k, index_w


class SparseAttention(nn.Module):
    """Grouped-query attention over the keys a learned indexer selects
    (``ops.sparse_attention``): q / kv / head norms / rotary / out as
    ``GroupedQueryAttention``, beside them the ``Indexer`` on the
    STOPPED input. Every query keeps the
    ``index_topk`` keys of largest index score (all earlier keys while
    it has fewer); no gradient passes through the choice. The indexer
    learns from its own loss alone, the Kullback-Leibler divergence of
    its scores' softmax over the selected keys from the attention's
    head-mean probabilities, sown token by token into "indexer_loss"; the loss of the model never reaches it. The
    selection's counters are sown into "sparse_select". Told nothing
    about chips; no sequence-parallel path."""

    config: TransformerConfig

    def setup(self):
        cfg = self.config
        head_dim = cfg.attention_head_dim
        self.kv_heads = cfg.num_kv_heads or cfg.num_heads
        assert cfg.num_heads % self.kv_heads == 0, (
            f"{cfg.num_heads} query heads on {self.kv_heads} kv heads"
        )
        self.q = nn.DenseGeneral(
            (cfg.num_heads, head_dim), axis=-1, dtype=cfg.dtype,
            use_bias=False,
        )
        self.kv = nn.DenseGeneral(
            (2, self.kv_heads, head_dim), axis=-1, dtype=cfg.dtype,
            use_bias=False,
        )
        if cfg.qk_norm:
            self.q_norm = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype)
            self.k_norm = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype)
        self.indexer = Indexer(cfg)
        self.out = nn.DenseGeneral(
            cfg.d_model, dtype=cfg.dtype, use_bias=False
        )

    def project(self, x, positions):
        """What the kernels take of a block's input: ``(q [b, heads, s,
        d], k, v [b, kv_heads, s, d], index_q, index_k, index_w)``."""
        cfg = self.config
        if cfg.seq_axis is not None:
            raise ValueError(
                "sparse attention has no sequence-parallel path: the "
                "selection ranks ALL earlier keys of a query"
            )
        q = self.q(x)
        k, v = jnp.moveaxis(self.kv(x), -3, 0)  # each [b, s, kv_heads, d]
        if cfg.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        return (
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2),
            *self.indexer(jax.lax.stop_gradient(x), positions),
        )

    def __call__(self, x, positions):
        from adaptdl_tpu.ops import sparse_attention as sparse

        cfg = self.config
        out, index_loss, count, tied = sparse.sparse_attention(
            *self.project(x, positions), cfg.index_topk
        )
        self.sow("indexer_loss", "loss", index_loss)  # [b, s]
        seq_len = x.shape[1]
        for name, value in (
            ("queries", jnp.int32(x.shape[0] * seq_len)),
            ("keys_selected", jnp.sum(count)),
            (
                "keys_visited",
                jnp.int32(x.shape[0] * sparse.keys_visited(seq_len)),
            ),
            ("tied_queries", jnp.sum(tied)),
        ):
            self.sow("sparse_select", name, value)
        out = jnp.swapaxes(out, 1, 2).reshape(
            x.shape[:-1] + (cfg.num_heads * cfg.attention_head_dim,)
        )
        return self.out(out)


class ShortConv(nn.Module):
    """The gated short convolution: ``[B, C, X] = split3(W_in u)``,
    ``z = B * X``, a depthwise causal convolution of ``conv_kernel``
    taps over ``z`` (zero history, no bias), ``y = W_out (C * c)``."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        del positions
        cfg = self.config
        taps = cfg.conv_kernel
        trace.event(
            "conv.schedule",
            channels=cfg.d_model,
            taps=taps,
            seq_len=x.shape[1],
            dtype=jnp.dtype(cfg.dtype).name,
        )
        bcx = nn.DenseGeneral(
            (3, cfg.d_model), axis=-1, dtype=cfg.dtype, use_bias=False,
            name="in_proj",
        )(x)
        gate_b, gate_c, inner = jnp.moveaxis(bcx, -2, 0)
        # weight[j] multiplies z[t - (taps - 1 - j)]: the last tap is
        # the current position.
        weight = self.param(
            "conv",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            (taps, cfg.d_model),
            jnp.float32,
        ).astype(cfg.dtype)
        z = gate_b * inner
        seq_len = z.shape[1]
        padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
        mixed = sum(
            weight[j] * padded[:, j:j + seq_len] for j in range(taps)
        )
        return nn.DenseGeneral(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="out_proj"
        )(gate_c * mixed)


def _dense_f32(x, kernel):
    """``x @ kernel`` on operands in ``x``'s dtype, accumulated and
    returned in float32."""
    return jnp.einsum(
        "...d,de->...e", x, kernel.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )


KDA_L2_EPS = 1e-6


def _l2_unit(t, dtype):
    """``t`` L2-normalised over its last axis (a head), statistics in
    float32, the result in ``dtype``."""
    t32 = t.astype(jnp.float32)
    return (
        t32 * jax.lax.rsqrt(
            jnp.sum(t32 * t32, -1, keepdims=True) + KDA_L2_EPS
        )
    ).astype(dtype)


class KDA(nn.Module):
    """Gated delta-rule linear attention with a per-channel decay
    (``ops.kda``). ``q, k, v = silu(conv(x W))`` (depthwise causal
    convolutions, no bias), q and k L2-normalised a head; the
    log-decay ``g = -exp(A_log[h]) softplus((x Wf_a) Wf_b + dt_bias)``
    a channel and the step ``beta = sigmoid(x Wb)`` a head, both
    float32; the state is zero at a row's start and crosses the whole
    row (packed documents included). ``y = (rmsnorm_head(o) * scale *
    sigmoid((x Wg_a) Wg_b)) Wo``. Journals ``kda.schedule`` where it
    is traced."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        from adaptdl_tpu.ops import kda as kda_op

        del positions  # the recurrence orders the tokens
        cfg = self.config
        heads, head_dim = cfg.num_heads, cfg.attention_head_dim
        width, rank = heads * head_dim, cfg.kda_gate_rank
        by_head = x.shape[:2] + (heads, head_dim)
        fan_in = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        qkv = nn.DenseGeneral(
            (3, width), axis=-1, dtype=cfg.dtype, use_bias=False,
            name="qkv",
        )(x)
        taps = self.param(
            "conv",
            nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
                batch_axis=(0,),
            ),
            (3, cfg.conv_kernel, width), jnp.float32,
        )

        def low_rank(name):
            inner = nn.Dense(
                rank, dtype=cfg.dtype, use_bias=False, name=f"{name}_a"
            )(x)
            return inner, self.param(
                f"{name}_b", fan_in, (rank, width), jnp.float32
            )

        a_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
            ),
            (heads,),
        )

        def dt_bias_init(key, shape):
            # softplus(dt_bias) log-uniform in [1e-3, 1e-1].
            dt = jnp.exp(
                jax.random.uniform(
                    key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)
                )
            )
            return dt + jnp.log(-jnp.expm1(-dt))

        dt_bias = self.param("dt_bias", dt_bias_init, (width,))
        decay_inner, decay_out = low_rank("f")
        beta = jax.nn.sigmoid(
            _dense_f32(
                x, self.param("beta", fan_in, (cfg.d_model, heads),
                              jnp.float32)
            )
        )

        def prepare(q, k, v, _, beta, taps, decay_out, dt_bias, a_log):
            """A group of heads: the projections' [b, s, h, d] into the
            rule's operands; ``taps`` [h, 3, taps, d], ``decay_out``
            [h, rank, d], ``dt_bias`` [h, d], ``a_log`` [h]."""
            taps = taps.astype(cfg.dtype)

            def conv(z, which):
                n, seq_len = taps.shape[2], z.shape[1]
                padded = jnp.pad(z, ((0, 0), (n - 1, 0), (0, 0), (0, 0)))
                return nn.silu(sum(
                    taps[:, which, j] * padded[:, j:j + seq_len]
                    for j in range(n)
                ))

            decay = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                jnp.einsum(
                    "bsr,hrd->bshd", decay_inner,
                    decay_out.astype(decay_inner.dtype),
                    preferred_element_type=jnp.float32,
                ) + dt_bias
            )
            q, k = (_l2_unit(conv(t, i), cfg.dtype) for i, t in enumerate((q, k)))
            return q, k, conv(v, 2), decay, beta

        def per_head(t, axis):  # the heads' axis first, heads apart
            shape = t.shape[:axis] + (heads, head_dim) + t.shape[axis + 1:]
            return jnp.moveaxis(t.reshape(shape), axis, 0)

        q, k, v = (qkv[:, :, i].reshape(by_head) for i in range(3))
        out = kda_op.kda(
            q, k, v, None, beta, prepare=prepare,
            per_head=(
                per_head(taps, 2), per_head(decay_out, 1),
                dt_bias.reshape(heads, head_dim), a_log,
            ),
        )  # [b, s, heads, head_dim]
        out = nn.RMSNorm(
            epsilon=cfg.norm_eps, dtype=cfg.dtype, name="o_norm"
        )(out)
        gate = jax.nn.sigmoid(_dense_f32(*low_rank("g"))).reshape(out.shape)
        out = (out * gate.astype(cfg.dtype)).reshape(
            x.shape[:2] + (width,)
        )
        return nn.DenseGeneral(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="out"
        )(out)


class GatedDeltaNet(nn.Module):
    """The gated delta rule with ONE decay a value head and token
    (``ops.kda`` with ``g`` a head): ``[q, k, v, z] = x W`` (key
    heads' q and k, value heads' v and z), ``[b, a] = x W_ba``; ``[q,
    k, v] = silu(conv([q, k, v]))``, one depthwise causal convolution
    over all their channels (zero history, no bias); q and k
    L2-normalised a key head, key head j on the value heads ``j *
    value_heads / key_heads ..``; ``beta = sigmoid(b)`` and the
    log-decay ``g = -exp(A_log[h]) softplus(a + dt_bias[h])`` a value
    head, float32; the state is zero at a row's start and crosses the
    whole row. ``y = (rmsnorm_head(o) * scale * silu(z)) Wo``, the head
    norm's scale plain (initialised 1, shared by the heads). Journals
    ``kda.schedule`` where it is traced."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        from adaptdl_tpu.ops import kda as kda_op

        del positions  # the recurrence orders the tokens
        cfg = self.config
        k_heads, v_heads = cfg.linear_key_heads, cfg.linear_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        k_width, v_width = k_heads * dk, v_heads * dv
        fan_in = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        qkvz = nn.Dense(
            2 * k_width + 2 * v_width, dtype=cfg.dtype, use_bias=False,
            name="in_proj",
        )(x)
        # taps[j] multiplies the channel's value at t - (taps - 1 - j).
        taps = self.param(
            "conv",
            nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=-2, out_axis=-1
            ),
            (cfg.conv_kernel, 2 * k_width + v_width), jnp.float32,
        )
        a_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(
                16.0 * (1.0 - jax.random.uniform(key, shape, jnp.float32))
            ),  # log of uniform (0, 16]
            (v_heads,),
        )
        dt_bias = self.param("dt_bias", nn.initializers.ones, (v_heads,))
        b, a = jnp.moveaxis(
            jnp.einsum(
                "...d,dgh->...gh", x,
                self.param(
                    "ba", fan_in, (cfg.d_model, 2, v_heads), jnp.float32
                ).astype(x.dtype),
                preferred_element_type=jnp.float32,
            ),
            -2, 0,
        )
        beta = jax.nn.sigmoid(b)
        decay = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)  # [b, s, h]

        def prepare(q, k, v, decay, beta, *taps):
            """A group of heads: the projections' [b, s, h, d] into the
            rule's operands; ``taps`` of q, k and v, each [h, taps, d]."""

            def conv(z, w):
                w = w.astype(cfg.dtype)
                n, seq_len = w.shape[1], z.shape[1]
                padded = jnp.pad(z, ((0, 0), (n - 1, 0), (0, 0), (0, 0)))
                return nn.silu(sum(
                    w[:, j] * padded[:, j:j + seq_len] for j in range(n)
                ))

            q, k, v = (conv(z, w) for z, w in zip((q, k, v), taps))
            q, k = (_l2_unit(t, cfg.dtype) for t in (q, k))
            return q, k, v, decay, beta

        def heads_of(t, at, heads, width):  # channels at.. as heads
            return t[..., at:at + heads * width].reshape(
                t.shape[:-1] + (heads, width)
            )

        parts = (
            (0, k_heads, dk), (k_width, k_heads, dk),
            (2 * k_width, v_heads, dv),
        )
        q, k, v = (heads_of(qkvz, *part) for part in parts)
        out = kda_op.kda(
            q, k, v, decay, beta, prepare=prepare,
            per_head=tuple(
                jnp.moveaxis(heads_of(taps, *part), 0, 1) for part in parts
            ),
        )  # [b, s, v_heads, dv]
        out = nn.RMSNorm(
            epsilon=cfg.norm_eps, dtype=cfg.dtype, name="o_norm"
        )(out)
        z = heads_of(qkvz, 2 * k_width + v_width, v_heads, dv)
        out = (
            out * jax.nn.silu(z.astype(jnp.float32)).astype(cfg.dtype)
        ).reshape(x.shape[:2] + (v_width,))
        return nn.DenseGeneral(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="out"
        )(out)


class LatentAttention(nn.Module):
    """Latent attention: ``q = x Wq`` (or, with ``q_lora_rank``,
    ``rmsnorm(x Wq_a) Wq_b``) as heads of ``qk_nope_head_dim +
    qk_rope_head_dim``; ``[c, k_pe] = x Wkv_a``; ``[k_nope, v] =
    rmsnorm(c) Wkv_b`` a head; ``k = [k_nope, k_pe]``, the one
    ``k_pe`` on every head; causal softmax attention at ``q``'s width,
    values and output at ``v_head_dim``. Under ``rope`` every head's
    ``q_pe`` and the one ``k_pe`` (before it goes onto the heads) are
    turned by rotary over their ``qk_rope_head_dim`` lanes; the nope
    lanes never are. ``attention_fn`` must take a v of another width
    than q and k (``ops.flash_attention`` does). Journals
    ``mla.schedule`` where it is traced."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        if cfg.seq_axis is not None:
            raise ValueError(
                "seq_axis: the 'mla' mixer has no sequence-parallel path"
            )
        heads, nope, pe = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rank, v_dim = cfg.kv_lora_rank, cfg.v_head_dim
        q_in = x
        if cfg.q_lora_rank:
            q_in = nn.RMSNorm(
                epsilon=cfg.norm_eps, dtype=cfg.dtype, name="q_norm"
            )(
                nn.Dense(
                    cfg.q_lora_rank, dtype=cfg.dtype, use_bias=False,
                    name="q_a",
                )(x)
            )
        q = nn.DenseGeneral(
            (heads, nope + pe), axis=-1, dtype=cfg.dtype, use_bias=False,
            name="q_b" if cfg.q_lora_rank else "q",
        )(q_in)
        kv_a = nn.Dense(
            rank + pe, dtype=cfg.dtype, use_bias=False, name="kv_a"
        )(x)
        latent = nn.RMSNorm(
            epsilon=cfg.norm_eps, dtype=cfg.dtype, name="kv_norm"
        )(kv_a[..., :rank])
        kv = nn.DenseGeneral(
            (heads, nope + v_dim), axis=-1, dtype=cfg.dtype,
            use_bias=False, name="kv_b",
        )(latent)
        k = kv[..., :nope]
        if pe:
            k_pe = kv_a[:, :, None, rank:]  # [b, s, 1, pe]: ONE a token
            if cfg.rope:
                k_pe = rope(k_pe, positions, cfg.rope_theta)
                q = jnp.concatenate(
                    [q[..., :nope],
                     rope(q[..., nope:], positions, cfg.rope_theta)],
                    axis=-1,
                )
            k = jnp.concatenate(
                [k, jnp.broadcast_to(k_pe, k.shape[:3] + (pe,))], axis=-1
            )
        v = kv[..., nope:]
        attn = cfg.attention_fn
        # Heads in runs, one call a run (at 16 384 keys of 192: four).
        run = _heads_a_call(
            attn, heads, x.shape[1], nope + pe, v_dim,
            jnp.dtype(cfg.dtype).itemsize,
        )
        trace.event(
            "mla.schedule",
            heads=heads,
            heads_a_call=run,
            qk_width=nope + pe,
            v_width=v_dim,
            latent_rank=rank,
            q_lora_rank=cfg.q_lora_rank,
            seq_len=x.shape[1],
            positions="rotary" if cfg.rope else "none",
            rotary_dims=pe if cfg.rope else 0,
            dtype=jnp.dtype(cfg.dtype).name,
            attention="attention_fn" if attn is not None
            else "plain causal attention",
        )
        if attn is None:
            from functools import partial

            attn = partial(causal_attention, causal=cfg.causal)
        # The scope the flash kernels' readers know the forward by.
        with jax.named_scope("attention"):
            out = _attend_in_runs(attn, q, k, v, run)  # [b, h, s, v_dim]
        out = jnp.swapaxes(out, 1, 2).reshape(
            x.shape[:-1] + (heads * v_dim,)
        )
        return nn.DenseGeneral(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="out"
        )(out)


class GatedFFN(nn.Module):
    """SwiGLU: ``down(silu(gate x) * up x)``, no biases."""

    config: TransformerConfig
    width: int | None = None  # ``d_ff`` unless given

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        width = self.width or cfg.d_ff
        gate = nn.Dense(
            width, dtype=cfg.dtype, use_bias=False, name="ff_gate"
        )(x)
        up = nn.Dense(
            width, dtype=cfg.dtype, use_bias=False, name="ff_up"
        )(x)
        return nn.Dense(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="ff_down"
        )(nn.silu(gate) * up)


class RoutedFFN(nn.Module):
    """This model's share of a dropless top-k expert layer
    (``models.moe.routed_experts``): the router over all
    ``experts_total`` experts in float32, gated experts of width
    ``d_expert`` for the ``experts_held`` held here. The expert bias
    shifts the selection only and no gradient reaches it. With
    ``d_shared_expert`` a shared expert (``GatedFFN`` of that width,
    ``shared``) runs on every token and is added unweighted, or with
    ``shared_expert_gate`` times ``sigmoid(x w_s)`` (``shared_gate``);
    the tokens it multiplied are sown as ``shared_rows``. The layer's
    load counters are sown into the "moe_load" collection, the
    router's choice (``experts``, ``weights``) into "moe_routing".
    ``routed_on``: what the router reads where the config places it on
    the block's input (``experts_routed_on``), in ``x``'s shape."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, routed_on=None):
        from adaptdl_tpu.models.moe import routed_experts

        cfg = self.config
        held = cfg.experts_held or cfg.experts_total
        router = self.param(
            "router", nn.initializers.normal(0.02),
            (cfg.d_model, cfg.experts_total), jnp.float32,
        )
        bias = (
            self.param(
                "expert_bias", nn.initializers.zeros,
                (cfg.experts_total,), jnp.float32,
            )
            if cfg.experts_router == "sigmoid"
            else None  # the softmax router has no bias buffer
        )
        fan_in = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,),
        )
        w_gate = self.param(
            "w_gate", fan_in, (held, cfg.d_model, cfg.d_expert),
            jnp.float32,
        )
        w_up = self.param(
            "w_up", fan_in, (held, cfg.d_model, cfg.d_expert),
            jnp.float32,
        )
        w_down = self.param(
            "w_down", fan_in, (held, cfg.d_expert, cfg.d_model),
            jnp.float32,
        )
        y, load = routed_experts(
            x.reshape(-1, cfg.d_model),
            router,
            bias,
            w_gate,
            w_up,
            w_down,
            experts_total=cfg.experts_total,
            first_expert=cfg.first_expert,
            top_k=cfg.experts_top_k,
            norm_eps=cfg.expert_weight_eps,
            scale=cfg.routed_scaling_factor,
            router_kind=cfg.experts_router,
            shared_gate="sigmoid" if cfg.shared_expert_gate else "none",
            pieces_from=cfg.experts_pieces_from,
            routed_on=None if routed_on is None
            else routed_on.reshape(-1, cfg.d_model),
            activation=cfg.experts_activation,
        )
        for name, value in load.items():
            self.sow(
                "moe_routing" if name in ("experts", "weights")
                else "moe_load",
                name, value,
            )
        y = y.reshape(x.shape).astype(cfg.dtype)
        if cfg.d_shared_expert > 0:
            self.sow(
                "moe_load", "shared_rows",
                jnp.int32(math.prod(x.shape[:-1])),
            )
            shared = GatedFFN(cfg, cfg.d_shared_expert, name="shared")(x)
            if cfg.shared_expert_gate:
                gate = nn.Dense(
                    1, dtype=cfg.dtype, use_bias=False, name="shared_gate"
                )(x)
                shared = shared * jax.nn.sigmoid(
                    gate.astype(jnp.float32)
                ).astype(cfg.dtype)
            y = y + shared
        return y


class MoEFFN(nn.Module):
    """Switch/GShard FFN: expert-stacked parameters (leading axis =
    experts) so the trainer shards them ``P("expert")``; under the
    trainer's manual shard_map each device sees its local slice and
    ``switch_moe`` exchanges tokens with all_to_all. The aux
    load-balancing loss is sown into the "moe_losses" collection."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from adaptdl_tpu.models.moe import dense_switch_moe, switch_moe

        cfg = self.config
        num_experts = cfg.moe_num_experts
        router = self.param(
            "router",
            nn.initializers.normal(0.02),
            (cfg.d_model, num_experts),
            jnp.float32,
        )
        # Expert-stacked leaves: full [E, d, f] at init (moe_axis is
        # None there — init_transformer strips it); inside the
        # trainer's shard_map this module sees the device's local
        # [E/ep, d, f] slice, so declare THAT shape (flax validates
        # declared vs received shapes at apply time).
        local_experts = num_experts
        if cfg.moe_axis is not None:
            ep = jax.lax.axis_size(cfg.moe_axis)
            assert num_experts % ep == 0, (
                f"{num_experts} experts cannot shard over {ep} devices"
                " (each shard owns a whole number of experts)"
            )
            local_experts = num_experts // ep
        w_up = self.param(
            "w_up",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            (local_experts, cfg.d_model, cfg.d_ff),
            jnp.float32,
        )
        w_down = self.param(
            "w_down",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            (local_experts, cfg.d_ff, cfg.d_model),
            jnp.float32,
        )
        flat = x.reshape(-1, cfg.d_model)
        if cfg.moe_axis is not None:
            out, aux = switch_moe(
                {"router": router, "w_up": w_up, "w_down": w_down},
                flat,
                axis_name=cfg.moe_axis,
                capacity_factor=cfg.moe_capacity_factor,
                top_k=cfg.moe_top_k,
                return_aux=True,
                router_type=cfg.moe_router,
            )
        else:
            out, aux = dense_switch_moe(
                router,
                {"w_up": w_up, "w_down": w_down},
                flat,
                num_slices=cfg.moe_dense_slices,
                capacity_factor=cfg.moe_capacity_factor,
                top_k=cfg.moe_top_k,
                return_aux=True,
                router_type=cfg.moe_router,
            )
        self.sow("moe_losses", "aux", aux)
        return out.reshape(x.shape).astype(cfg.dtype)


def _mixer(cfg: TransformerConfig, layer: int) -> nn.Module:
    """The layer's sequence mixer. ``num_kv_heads`` and ``qk_norm``
    are the two options that change the attention's parameter tree
    (``qkv`` becomes ``q`` and ``kv``, plus the head norms' scales)
    and give up the sequence-parallel paths: they select
    ``GroupedQueryAttention``, as ``attention_gate`` does. Everything
    else, ``rope_theta`` included, is the plain ``Attention``'s."""
    kind = cfg.mixer(layer)
    if kind == "conv":
        return ShortConv(cfg, name="short_conv")
    if kind == "sparse_attention":
        return SparseAttention(cfg, name="attention")
    if kind == "kda":
        return KDA(cfg, name="kda")
    if kind == "gdn":
        return GatedDeltaNet(cfg, name="gdn")
    if kind == "mla":
        return LatentAttention(cfg, name="mla")
    if kind not in ("full_attention", "sliding_attention"):
        raise ValueError(
            f"layer_types[{layer}] must be 'full_attention', "
            "'sliding_attention', 'conv', 'sparse_attention', 'kda', "
            f"'gdn' or 'mla', got {kind!r}"
        )
    if kind in dict(cfg.attention_kinds):
        # The kind has values of its own (heads, rotary, a window).
        return GroupedQueryAttention(cfg, kind, name="attention")
    if (
        cfg.num_kv_heads not in (None, cfg.num_heads) or cfg.qk_norm
        or cfg.attention_gate or cfg.attention_head_gate
    ):
        return GroupedQueryAttention(cfg, name="attention")
    return Attention(cfg, name="attention")


class Block(nn.Module):
    config: TransformerConfig
    use_moe: bool = False
    layer: int = 0

    @nn.compact
    def __call__(self, x, positions, dropout_rng=None):
        cfg = self.config
        received = x  # what a router on the block's input reads
        y = make_norm(cfg)(x)
        y = _mixer(cfg, self.layer)(y, positions)
        if cfg.sandwich_norm:
            y = make_norm(cfg)(y)
        if cfg.dropout_rate > 0 and dropout_rng is not None:
            y = nn.Dropout(cfg.dropout_rate, deterministic=False)(
                y, rng=dropout_rng
            )
        x = checkpoint_name(x + y, SAVED_MIXED)
        y = make_norm(cfg)(x)
        if self.use_moe:
            y = MoEFFN(cfg, name="moe")(y)
        elif cfg.routed(self.layer):
            on_input = cfg.experts_routed_on == "block_input"
            y = RoutedFFN(cfg, name="moe")(y, received if on_input else None)
        elif cfg.ffn == "swiglu":
            y = GatedFFN(cfg, name="ffn")(y)
        else:
            y = nn.Dense(
                cfg.d_ff, dtype=cfg.dtype, use_bias=False, name="ff_up"
            )(y)
            y = nn.gelu(checkpoint_name(y, SAVED_FF_UP))
            y = nn.Dense(
                cfg.d_model, dtype=cfg.dtype, use_bias=False,
                name="ff_down",
            )(y)
        if cfg.sandwich_norm:
            y = make_norm(cfg)(y)
        return x + y


class PredictionModule(nn.Module):
    """The multi-token-prediction depth (``TransformerConfig.
    mtp_depth``; DeepSeek-V3, arXiv:2412.19437, equations 21-23):
    ``u_i = W_eh [enorm(e_i) ; hnorm(h_i)]``, ``h`` the trunk's state
    before its final norm and ``e_i`` the MODEL's embedding of the
    token after position ``i``; one whole block on ``u`` at the same
    positions (``layer_<num_layers>``, of ``block``: the trunk's class,
    so under the same remat rule); its own final ``norm``. Neither an
    embedding nor an output table: the model's are the caller's to
    apply on either side."""

    config: TransformerConfig
    block: Any

    @nn.compact
    def __call__(self, h, e, positions):
        cfg = self.config
        u = nn.Dense(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="eh_proj"
        )(
            jnp.concatenate(
                [make_norm(cfg, "enorm")(e), make_norm(cfg, "hnorm")(h)],
                axis=-1,
            )
        )
        layer = cfg.num_layers
        g = self.block(
            cfg, cfg.switch_moe(layer), layer, name=f"layer_{layer}"
        )(u, positions, None)
        return make_norm(cfg, "norm")(g)


# Only the zero-config policies are valid by NAME — the other
# jax.checkpoint_policies attributes are factories (they build a
# policy from arguments) and passing one where a policy is expected
# silently disables remat or crashes mid-trace.
_REMAT_POLICIES = (
    "everything_saveable",
    "nothing_saveable",
    "dots_saveable",
    "checkpoint_dots",
    "dots_with_no_batch_dims_saveable",
    "checkpoint_dots_with_no_batch_dims",
)


def _check_remat_policy(config: TransformerConfig) -> None:
    if (
        config.remat_policy is not None
        and config.remat_policy not in _REMAT_POLICIES
    ):
        raise ValueError(
            f"unknown remat_policy {config.remat_policy!r}; valid "
            f"names: {sorted(_REMAT_POLICIES)} (policy FACTORIES like "
            "save_only_these_names need arguments — build them "
            "yourself and wrap the Block with nn.remat directly)"
        )


# What a block names (``jax.ad_checkpoint.checkpoint_name``) beyond
# the kernels' own names, for the rungs of ``block_remat``'s ladder:
# q and k after rotary and v on the plain attention path (the flash
# kernel names its operands itself: ``flash_attention.SAVED_QKV``),
# the residual stream after the mixer, ``ff_up``'s result before the
# gelu. Outside a remat, and in a remat that does not save it, a name
# is an identity.
SAVED_QKV = "attention_qkv"
SAVED_MIXED = "block_mixed"
SAVED_FF_UP = "block_ff_up"


def _remat_ladder(config: TransformerConfig, tokens_shape):
    """What a remat'd block keeps beyond its kernels' outputs: ``(names,
    attributes of the remat.policy event)``.

    The rungs, dearest recomputation a byte first (PERF.md, PR 41: 15.4
    / 9.6 / 8.8 ms a GiB at gpt2-124m): q, k, v as attention reads them
    (no QKV projection and no rotary in the backward), the residual
    after the mixer (neither the mixer's out projection nor the second
    norm's input), ``ff_up``'s result (the gelu FFN's first matmul).
    A rung costs block applications (layers x ``loop_passes``) x this
    device's tokens of the micro-batch x its width x the compute
    dtype's bytes — priced for every layer, so too high where a
    layer's mixer or FFN has no such value — and is taken
    while the sum fits what the trainer says the device has free
    (``device_budget``) less the model's own large temporaries: the
    float32 logits and their gradient (of ONE exit: a looped model's
    loss streams its exits' heads). No budget (no trainer, or a
    device that does not say its ``bytes_limit``), no rung. A pure
    function of the config, the shape and the budget.
    """
    budget = device_budget.activations()
    if budget is None or tokens_shape is None:
        return (), {"rungs": "", "rung_bytes": 0, "budget_bytes": -1,
                    "bytes_limit": -1}
    from adaptdl_tpu.ops.flash_attention import SAVED_QKV as FLASH_QKV

    tokens = math.prod(tokens_shape)
    left = budget.free_bytes - 2 * tokens * config.vocab_size * 4
    # A rung's width summed over the layers: blocks of unequal size
    # (``attention_kinds``: a kind's own number of query heads) are
    # each priced at their own.
    blocks = config.num_layers + config.mtp_depth
    layers = range(blocks)
    ladder = [
        ("qkv", (FLASH_QKV, SAVED_QKV),
         sum(
             3 * config.layer_heads(at) * config.attention_head_dim
             for at in layers
         )),
        ("mixed", (SAVED_MIXED,), blocks * config.d_model),
    ]
    if config.ffn == "gelu":
        ladder.append(("ff_up", (SAVED_FF_UP,), blocks * config.d_ff))
    per_width = (
        config.loop_passes * tokens * jnp.dtype(config.dtype).itemsize
    )
    priced = [
        (rung, rung_names, per_width * width)
        for rung, rung_names, width in ladder
    ]
    names, rungs, spent = (), [], 0
    for rung, rung_names, cost in priced:
        if spent + cost > left:
            break
        names += rung_names
        rungs.append(rung)
        spent += cost
    return names, {
        "rungs": ",".join(rungs), "rung_bytes": spent,
        "budget_bytes": left, "bytes_limit": budget.bytes_limit,
    }


def block_remat(config: TransformerConfig, tokens_shape=None):
    """The ``Block`` class a model of this config stacks: ``Block``
    itself, or with ``config.remat`` its ``nn.remat`` — the one place
    that knows what a remat'd block keeps from forward to backward.

    Always the flash kernel's ``out`` and ``lse``, by the names its
    forward rule gives them: the kernel's FLOPs per byte of output
    grow with the sequence, so its output is the dearest byte of the
    block at every shape, and no policy a user can name would keep it
    (a ``pallas_call`` is not a dot). Then, as far as the device's
    bytes allow for a micro-batch of ``tokens_shape`` on this device,
    the rungs of ``_remat_ladder``. A named ``remat_policy`` adds what
    it saves to that. Blocks without the kernel and with no budget
    name nothing and are remat'd as the policy alone would.

    Records one ``remat.policy`` event a call: a model calls this once
    each time it is traced.
    """
    if not config.remat:
        return Block
    _check_remat_policy(config)
    from adaptdl_tpu.ops.flash_attention import SAVED_LSE, SAVED_OUT

    saved_names = (SAVED_OUT, SAVED_LSE)
    if "sparse_attention" in (config.layer_types or ()):
        # The selection (its threshold), the indexer's statistics and
        # loss, and the sparse kernel's out and lse: neither the index
        # scores, the selection nor the attention forward run twice.
        from adaptdl_tpu.ops.sparse_attention import SAVED_NAMES

        saved_names += SAVED_NAMES
    if {"kda", "gdn"} & set(config.layer_types or ()):
        # The delta rule's output (one activation of heads x head_dim a
        # layer): the block's recomputation then does not run the rule
        # again before the rule's own backward does.
        from adaptdl_tpu.ops.kda import SAVED_OUT as KDA_OUT

        saved_names += (KDA_OUT,)
    rung_names, ladder_attrs = _remat_ladder(config, tokens_shape)
    saved_names += rung_names
    policy = jax.checkpoint_policies.save_only_these_names(*saved_names)
    if config.remat_policy is not None:
        policy = jax.checkpoint_policies.save_from_both_policies(
            getattr(jax.checkpoint_policies, config.remat_policy),
            policy,
        )
    trace.event(
        "remat.policy",
        saved_names=",".join(saved_names),
        policy=config.remat_policy or "none",
        blocks=config.num_layers * config.loop_passes + config.mtp_depth,
        **ladder_attrs,
    )
    return nn.remat(Block, static_argnums=(), policy=policy)


class TransformerLM(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(
        self,
        tokens,
        *,
        train: bool = True,
        rng=None,
        return_hidden: bool = False,
        return_exits: bool = False,
        next_tokens=None,
    ):
        """``next_tokens`` [b, s] (a model with ``mtp_depth``): the
        token after each position, the prediction module's input. With
        it the result is a pair, the trunk's and the module's (logits,
        or with ``return_hidden`` each one's normed final state: both
        go through the ONE output table); without it the module does
        not run."""
        cfg = self.config
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            name="embed",
        )
        x = embed(tokens)
        if cfg.seq_axis is not None:
            # Sequence-sharded: this device holds one contiguous block
            # of the global sequence; positions must be global for RoPE
            # and the ring-attention causal mask to line up.
            positions = jax.lax.axis_index(
                cfg.seq_axis
            ) * tokens.shape[1] + jnp.arange(tokens.shape[1])
        else:
            positions = jnp.arange(tokens.shape[1])
        block_cls = block_remat(cfg, tokens.shape)
        if cfg.loop_passes > 1:
            exits = self._looped(block_cls, x, positions)
            if return_exits:
                # For ``looped_lm_loss_fn``: every exit's normed state
                # [passes, b, s, d] and gate logit [passes, b, s].
                return exits
            x = exits[0][-1]  # the last exit's: no exit is taken early
        else:
            if return_exits:
                raise ValueError("return_exits needs loop_passes > 1")
            for layer in range(cfg.num_layers):
                dropout_rng = (
                    jax.random.fold_in(rng, layer)
                    if (train and rng is not None and cfg.dropout_rate > 0)
                    else None
                )
                x = block_cls(
                    cfg, cfg.switch_moe(layer), layer,
                    name=f"layer_{layer}",
                )(x, positions, dropout_rng)
            trunk = x  # the state the prediction module reads
            x = make_norm(cfg)(x)
        predicted = None
        if next_tokens is not None:
            if not cfg.mtp_depth:
                raise ValueError("next_tokens: the model has no mtp_depth")
            predicted = PredictionModule(cfg, block_cls, name="mtp")(
                trunk, embed(next_tokens), positions
            )
        if return_hidden:
            # For losses that stream the output head themselves (the
            # chunked cross-entropy, ops/chunked_xent.py): no
            # [tokens, vocab] logits tensor is ever built.
            return x if predicted is None else (x, predicted)
        if predicted is not None:
            x = jnp.stack([x, predicted])  # both through the one table
        if not cfg.tie_embeddings:
            # An output table of its own: operands in the compute
            # dtype, accumulation and logits in float32.
            table = self.param(
                "lm_head",
                nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=-1, out_axis=-2
                ),
                (cfg.vocab_size, cfg.d_model),
                jnp.float32,
            )
            logits = untied_logits(x, table)
        else:
            # Tied output head through the embedding table keeps the
            # only O(vocab x d_model) matmul single-sourced.
            logits = embed.attend(x).astype(jnp.float32)
        return logits if predicted is None else (logits[0], logits[1])

    def _looped(self, block_cls, x, positions):
        """The stack applied ``loop_passes`` times with one set of
        parameters, as one ``lax.scan`` over the passes (``nn.scan``,
        the parameters broadcast: a program of ``num_layers`` blocks
        whatever the passes; a shared leaf's cotangent is summed in
        the backward scan's carry). Returns every pass's normed state
        ``[passes, b, s, d]`` and gate logit ``[passes, b, s]``."""
        cfg = self.config
        if (
            cfg.dropout_rate > 0
            or cfg.experts_total > 0
            or cfg.moe_every_n > 0
            or "sparse_attention" in (cfg.layer_types or ())
        ):
            raise ValueError(
                "loop_passes > 1 takes dense blocks without dropout: "
                "what a routed, Switch or sparse layer sows has no "
                "pass axis"
            )

        def exit_of(mdl, x):
            # (Under a remat of its own: a pass keeps its un-normed
            # state for the backward, not the norm's float32 insides.)
            z = make_norm(cfg)(x)
            gate = nn.Dense(
                1, dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
                name="exit_gate",
            )(z)
            return z, gate[..., 0]

        def one_pass(mdl, x, _):
            for layer in range(cfg.num_layers):
                x = block_cls(cfg, False, layer, name=f"layer_{layer}")(
                    x, positions, None
                )
            z, gate = nn.remat(exit_of)(mdl, x)
            return z, (z, gate)

        _, exits = nn.scan(
            one_pass,
            variable_broadcast="params",
            split_rngs={"params": False},
            length=cfg.loop_passes,
        )(self, x, None)
        return exits


def untied_logits(hidden, table):
    """The untied head: ``hidden`` [..., d] in the compute dtype
    against the output table [vocab, d] rounded to it, accumulated in
    float32; float32 logits."""
    return jnp.einsum(
        "...d,vd->...v", hidden, table.astype(hidden.dtype),
        preferred_element_type=jnp.float32,
    )


def init_transformer(config: TransformerConfig, rng=None, seq_len=None):
    import dataclasses

    if (
        config.moe_router == "experts"
        and config.causal
        and config.moe_every_n > 0
        and config.moe_num_experts > 0
    ):
        # Expert-choice gating ranks across the whole token slice, so
        # a token's routing depends on LATER tokens — silently invalid
        # for autoregressive training/decoding. Fail loud; the
        # encoder/MLM families (causal=False) are the paper's setting.
        raise ValueError(
            "moe_router='experts' is not causally valid with "
            "causal=True (expert-choice gating sees future tokens); "
            "use causal=False (encoder/MLM) or moe_router='tokens'"
        )
    # Fail at configuration time, not deep inside the first step's
    # jit trace (which on TPU wastes the whole startup).
    _check_remat_policy(config)
    model = TransformerLM(config)
    # Parameter shapes don't depend on the parallelism config, and the
    # mapped seq/expert axes don't exist outside shard_map — init
    # unsharded (expert leaves come out full-stacked [E, ...]).
    init_model = TransformerLM(
        dataclasses.replace(
            config, seq_axis=None, attention_fn=None, moe_axis=None
        )
    )
    rng = rng if rng is not None else jax.random.key(0)
    seq_len = seq_len or min(config.max_seq_len, 128)
    dummy = jnp.zeros((1, seq_len), jnp.int32)
    params = init_model.init(
        rng, dummy, train=False,
        next_tokens=dummy if config.mtp_depth else None,
    )["params"]
    return model, params


def apply_with_moe_aux(
    model: TransformerLM, params, inputs, rng, return_hidden=False
):
    """model.apply that also returns the weighted MoE load-balancing
    aux loss (0.0 for dense models) from the "moe_losses" collection —
    the building block for custom losses over MoE configs (the
    lm/mlm loss factories below use it; example:
    examples/transformer_lm.py). ``return_hidden`` passes through to
    the model (final hidden states instead of logits — for losses
    that stream the output head, ops/chunked_xent.py).
    """
    cfg = model.config
    if cfg.moe_every_n > 0 and cfg.moe_num_experts > 0:
        out, mutated = model.apply(
            {"params": params},
            inputs,
            train=True,
            rng=rng,
            return_hidden=return_hidden,
            mutable=["moe_losses"],
        )
        auxes = jax.tree.leaves(mutated.get("moe_losses", {}))
        aux = (
            cfg.moe_aux_weight * sum(jnp.mean(a) for a in auxes)
            if auxes
            else jnp.zeros(())
        )
        return out, aux
    out = model.apply(
        {"params": params},
        inputs,
        train=True,
        rng=rng,
        return_hidden=return_hidden,
    )
    return out, jnp.zeros(())


def moe_load_counters(config: TransformerConfig, mutated) -> dict:
    """The routed layers' sown load (the "moe_load" collection of an
    ``apply(..., mutable=["moe_load"])``) stacked over the routed
    layers in order: ``{"held_rows": int32 [layers, held], "left_out":
    [layers], "dropped": [layers], "rows_active": [layers],
    "rows_walked": [layers], "fell_back": [layers]}``, and
    ``"shared_rows": [layers]`` where the layers have a shared
    expert, ``"hidden_zero": [layers]`` where the experts' gate is
    "relu". A prediction module that ran (``mtp_depth``) is the last
    layer."""
    sown = mutated["moe_load"]
    blocks = [
        sown[f"layer_{i}"] for i in range(config.num_layers)
        if config.routed(i)
    ]
    if "mtp" in sown:
        blocks.append(sown["mtp"][f"layer_{config.num_layers}"])
    return {
        name: jnp.stack([block["moe"][name][0] for block in blocks])
        for name in (
            "held_rows", "left_out", "dropped", "rows_active",
            "rows_walked", "fell_back",
        ) + (("shared_rows",) if config.d_shared_expert > 0 else ())
        + (("hidden_zero",) if config.experts_activation == "relu" else ())
    }


def sparse_layers(config: TransformerConfig) -> list[int]:
    return [
        i for i in range(config.num_layers)
        if config.mixer(i) == "sparse_attention"
    ]


def sparse_select_counters(config: TransformerConfig, mutated) -> dict:
    """What the sparse mixers sowed (an ``apply(..., mutable=
    ["indexer_loss", "sparse_select"])``) stacked over those layers in
    order, under the names the trainer journals them by: ``{
    "sparse.select": {"queries", "keys_selected", "keys_visited",
    "tied_queries": int32 [layers]}, "indexer.loss": {"loss": float32
    [layers] (the layer's mean over tokens), "micro_batches": 1}}``.
    The model's loss adds ``loss.mean()``."""
    layers = sparse_layers(config)

    def sown(collection, name, layer):
        return mutated[collection][f"layer_{layer}"]["attention"][name][0]

    return {
        "sparse.select": {
            name: jnp.stack([sown("sparse_select", name, i) for i in layers])
            for name in (
                "queries", "keys_selected", "keys_visited",
                "tied_queries",
            )
        },
        "indexer.loss": {
            "loss": jnp.stack(
                [jnp.mean(sown("indexer_loss", "loss", i)) for i in layers]
            ),
            "micro_batches": jnp.int32(1),
        },
    }


def routed_lm_loss_fn(
    model: TransformerLM, head_chunk_rows: int | None = None
):
    """Next-token cross-entropy of a model with routed experts, plus
    the indexer's loss (mean over sparse layers and tokens) where the
    model has sparse attention layers;
    batch = {"inputs", "targets"}, each [b, s] int32. Returns ``(loss,
    {"moe.load": counters})``: a loss_fn that returns such a pair has
    the counters summed over the step by the trainer and journalled as
    ``moe.load`` events where it pulls its statistics
    (``ElasticTrainer.run_step``). With ``head_chunk_rows`` the head
    is streamed that many rows at a time (``ops.chunked_xent.
    weighted_xent_sum``: the same operands and float32 accumulation,
    its gradients formed in the forward pass) and no ``[tokens,
    vocab]`` array exists in the step.

    A model with a prediction module (``mtp_depth``) is handed
    ``targets`` as the module's input and the loss is ``mean_i
    CE(logits_i, targets_i) + mtp_loss_weight x mean_{i < s - 1}
    CE(logits'_i, targets_{i + 1})``: the module's stream has no
    target at a row's last position, which weighs 0. Streamed, both
    streams' rows go through ONE call against the one table (twice
    the rows, one accumulator of the table's gradient). Journals
    ``mtp.schedule`` each time it is traced and returns the two means
    as the ``mtp.loss`` counters (``main``, ``mtp``, ``micro_batches``)
    beside ``moe.load``, whose last layer is the module's router."""

    sparse = sparse_layers(model.config)
    cfg = model.config
    if cfg.mtp_depth:
        return _predicting_lm_loss_fn(model, head_chunk_rows)

    def loss_fn(params, batch, rng):
        out, mutated = model.apply(
            {"params": params}, batch["inputs"], train=True, rng=rng,
            return_hidden=head_chunk_rows is not None,
            mutable=["moe_load", "indexer_loss", "sparse_select"]
            if sparse else ["moe_load"],
        )
        if head_chunk_rows is None:
            loss = optax.softmax_cross_entropy_with_integer_labels(
                out, batch["targets"]
            ).mean()
        else:
            from adaptdl_tpu.ops.chunked_xent import weighted_xent_sum

            rows = out.reshape(-1, cfg.d_model)
            loss, _ = weighted_xent_sum(
                rows,
                params["embed"]["embedding"] if cfg.tie_embeddings
                else params["lm_head"],
                batch["targets"].reshape(-1),
                jnp.full(rows.shape[:1], 1.0 / rows.shape[0], jnp.float32),
                head_chunk_rows,
            )
        counters = {"moe.load": moe_load_counters(model.config, mutated)}
        if sparse:
            selected = sparse_select_counters(model.config, mutated)
            loss = loss + selected["indexer.loss"]["loss"].mean()
            counters.update(selected)
        return loss, counters

    loss_fn.has_counters = True
    return loss_fn


def _predicting_lm_loss_fn(model: TransformerLM, head_chunk_rows):
    """``routed_lm_loss_fn`` of a model with a prediction module: two
    streams of rows (the trunk's against ``targets``, the module's
    against ``targets`` one place further on, a row's last position
    at weight 0) through the one output table."""
    cfg = model.config
    if sparse_layers(cfg) or cfg.experts_total <= 0:
        raise ValueError(
            "mtp_depth: the loss takes routed layers and no sparse ones"
        )
    table_of = (
        (lambda params: params["embed"]["embedding"]) if cfg.tie_embeddings
        else (lambda params: params["lm_head"])
    )

    def loss_fn(params, batch, rng):
        targets = batch["targets"]
        rows, seq_len = targets.size, targets.shape[1]
        trace.event(
            "mtp.schedule",
            depth=cfg.mtp_depth,
            rows=rows,
            tokens=rows,
            head_rows=2 * rows,
            head_calls=1,
            shares_embedding=True,
            shares_head=True,
            loss_weight=cfg.mtp_loss_weight,
            head="logits" if head_chunk_rows is None
            else f"xent_sum, {head_chunk_rows} rows a chunk",
        )
        streams, mutated = model.apply(
            {"params": params}, batch["inputs"], train=True, rng=rng,
            return_hidden=head_chunk_rows is not None,
            mutable=["moe_load"], next_tokens=targets,
        )
        # Each stream's targets and a position's weight in ITS mean.
        further = jnp.roll(targets, -1, axis=1)  # the last: weight 0
        weights = (
            jnp.full((rows,), 1.0 / rows, jnp.float32),
            jnp.broadcast_to(
                (jnp.arange(seq_len) < seq_len - 1).astype(jnp.float32)
                / (targets.shape[0] * (seq_len - 1)),
                targets.shape,
            ).reshape(-1),
        )
        if head_chunk_rows is None:
            main, predicted = (
                jnp.sum(
                    optax.softmax_cross_entropy_with_integer_labels(
                        logits, aim
                    ).reshape(-1) * weight
                )
                for logits, aim, weight in zip(
                    streams, (targets, further), weights
                )
            )
            loss = main + cfg.mtp_loss_weight * predicted
        else:
            from adaptdl_tpu.ops.chunked_xent import weighted_xent_sum

            loss, xent = weighted_xent_sum(
                jnp.concatenate(
                    [hidden.reshape(-1, cfg.d_model) for hidden in streams]
                ),
                table_of(params),
                jnp.concatenate([targets.reshape(-1), further.reshape(-1)]),
                jnp.concatenate(
                    [weights[0], cfg.mtp_loss_weight * weights[1]]
                ),
                head_chunk_rows,
            )
            # The two means apart, for the counters alone (``xent``
            # carries no gradient).
            main = jnp.sum(xent[:rows] * weights[0])
            predicted = jnp.sum(xent[rows:] * weights[1])
        return loss, {
            "moe.load": moe_load_counters(cfg, mutated),
            "mtp.loss": {
                "main": main, "mtp": predicted,
                "micro_batches": jnp.int32(1),
            },
        }

    loss_fn.has_counters = True
    return loss_fn


def exit_log_probs(gate):
    """The exit distribution of gate logits ``[passes, ...]``, as
    logarithms: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t <
    passes`` and the rest of the mass on the last exit, ``lambda =
    sigmoid(gate)``."""
    stay = jax.nn.log_sigmoid(-gate)  # log (1 - lambda_t)
    stayed = jnp.cumsum(stay, axis=0) - stay  # log prod_{j<t} (1 - lambda_j)
    return jnp.concatenate(
        [stayed[:-1] + jax.nn.log_sigmoid(gate[:-1]), stayed[-1:]]
    )


def looped_lm_loss_fn(
    model: TransformerLM, beta: float = 0.1, chunk_size: int = 2048
):
    """The expected next-token cross-entropy of a looped model
    (``loop_passes > 1``) over its exits, less ``beta`` times the
    exit distribution's entropy: ``mean_tokens(sum_t p_t CE(l_t) -
    beta H(p))``, ``l_t`` the exit's normed state through the ONE
    output table, ``p`` from the gate (``exit_log_probs``), everything
    from the cross-entropies on in float32. batch = {"inputs",
    "targets"}, each [b, s] int32.

    The exits' heads, softmax and per-token losses stream the rows of
    all exits together, ``chunk_size`` rows at a time against the whole
    table (``ops.chunked_xent.weighted_xent_sum``: compute dtype
    operands, float32 accumulation). A row's weight in the loss,
    ``p_t / tokens``, comes from the gate and not from the head, so
    the head forms ``dx`` and the table's gradient in the forward
    pass, three products a chunk and none repeated; the gate's
    gradient flows through the weights. Nothing ``[tokens,
    vocab]``-sized ever exists, and the table's gradient is one
    accumulator over every exit's rows, not a sum of one an exit.

    Returns ``(loss, {"loop.exit": ...})`` (``has_counters``, as
    ``routed_lm_loss_fn``): per exit the mean cross-entropy ``xent``
    and mean probability ``p``, the mean ``entropy`` and the mean
    ``expected_exit = sum_t t p_t`` (1 .. passes), each summed over
    the step's ``micro_batches``. Journals one ``loop.schedule`` event
    each time it is traced."""
    from adaptdl_tpu.ops.chunked_xent import (
        rows_per_chunk,
        weighted_xent_sum,
    )

    cfg = model.config
    passes = cfg.loop_passes
    assert passes > 1, "looped_lm_loss_fn needs loop_passes > 1"

    def loss_fn(params, batch, rng):
        tokens = batch["targets"].size
        rows = passes * tokens
        trace.event(
            "loop.schedule",
            passes=passes,
            blocks=cfg.num_layers,
            applications=passes * cfg.num_layers,
            how="scan",
            exits=passes,
            head=f"xent_sum, {rows_per_chunk(rows, chunk_size)} of {rows} "
            "rows a chunk, gradients in the forward",
        )
        states, gate = model.apply(
            {"params": params}, batch["inputs"], train=True, rng=rng,
            return_exits=True,
        )
        table = (
            params["embed"]["embedding"] if cfg.tie_embeddings
            else params["lm_head"]
        )
        log_p = exit_log_probs(gate.reshape(passes, -1))
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
        expected_xent, xent = weighted_xent_sum(
            states.reshape(-1, cfg.d_model), table,
            jnp.tile(batch["targets"].reshape(-1), passes),
            p.reshape(-1) / tokens, chunk_size,
        )
        xent = xent.reshape(passes, -1)
        loss = expected_xent - beta * jnp.mean(entropy)
        exit_at = jnp.arange(1, passes + 1, dtype=jnp.float32)
        return loss, {
            "loop.exit": {
                "xent": xent.mean(axis=1),
                "p": p.mean(axis=1),
                "entropy": entropy.mean(),
                "expected_exit": (exit_at[:, None] * p).sum(axis=0).mean(),
                "micro_batches": jnp.int32(1),
            }
        }

    loss_fn.has_counters = True
    return loss_fn


def mlm_loss_fn(
    model: TransformerLM, mask_token: int, mask_rate: float = 0.15
):
    """Masked-LM cross-entropy (the reference's BERT-family objective,
    examples/BERT/mlm_task_adaptdl.py): each step masks ``mask_rate``
    of tokens (fresh mask per step from the step rng) and scores only
    the masked positions. Use with ``TransformerConfig(causal=False)``
    so attention is bidirectional. batch = {"tokens": [b, s] int32}.
    """

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        mask_rng = jax.random.fold_in(rng, 0x3A5)
        mask = jax.random.uniform(mask_rng, tokens.shape) < mask_rate
        inputs = jnp.where(mask, mask_token, tokens)
        logits, aux = apply_with_moe_aux(model, params, inputs, rng)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens
        )
        weights = mask.astype(jnp.float32)
        return (
            jnp.sum(losses * weights)
            / jnp.maximum(jnp.sum(weights), 1.0)
            + aux
        )

    return loss_fn


def lm_loss_fn(model: TransformerLM):
    """Next-token cross-entropy (+ weighted MoE aux loss when the
    config enables experts); batch = {"tokens": [b, s+1] int32}."""

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits, aux = apply_with_moe_aux(model, params, inputs, rng)
        return (
            optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            ).mean()
            + aux
        )

    return loss_fn


def moe_param_sharding_fn(path, leaf):
    """``param_sharding_fn`` for expert-parallel MoE transformers:
    expert-stacked leaves (under a ``moe`` module, except the
    replicated router) shard over the expert mesh axis; everything
    else replicates.
    """
    from jax.sharding import PartitionSpec as P

    from adaptdl_tpu.parallel.mesh import EXPERT_AXIS

    keys = tuple(
        str(p.key) if hasattr(p, "key") else str(p) for p in path
    )
    if "moe" in keys and keys[-1] in ("w_gate", "w_up", "w_down"):
        return P(EXPERT_AXIS)
    return P()
