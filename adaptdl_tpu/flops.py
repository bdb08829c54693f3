"""Model-FLOPs accounting and MFU (model FLOPs utilization).

The reference framework never reports hardware utilization — its
throughput story is samples/s from torch hooks (reference:
adaptdl/adaptdl/torch/_metrics.py). On TPU the honest headline number
is MFU: achieved model FLOPs per second over the chip's peak bf16
FLOPs. This module implements the standard matmul-only accounting
(the PaLM-appendix convention): 2 FLOPs per multiply-accumulate,
backward pass costed at 2x forward, attention scored causally (half
the full [seq, seq] rectangle when ``causal``).

Used by ``chip_smoke.py`` for the flagship transformer's MFU line and
available to user code for their own reporting.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

# Peak dense bf16 FLOP/s per chip by TPU generation. Keyed by
# substrings of ``jax.Device.device_kind`` (e.g. "TPU v5 lite").
# Public figures: v2 45T, v3 123T (2 cores), v4 275T, v5e ("v5 lite")
# 197T, v5p 459T, v6e ("Trillium") 918T.
_PEAK_BF16: tuple[tuple[str, float], ...] = (
    ("v6 lite", 918e12),
    ("v6e", 918e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4 lite", 138e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def device_peak_flops(device) -> float | None:
    """Peak dense bf16 FLOP/s for a ``jax.Device``; None when unknown
    (CPU, new TPU generations, GPU)."""
    kind = getattr(device, "device_kind", "").lower()
    if "tpu" not in kind and getattr(device, "platform", "") != "tpu":
        return None
    for needle, peak in _PEAK_BF16:
        if needle in kind:
            return peak
    return None


@dataclass(frozen=True)
class FlopsBreakdown:
    """Per-train-step model FLOPs, split for reporting."""

    matmul: float  # projections + FFN + LM head (fwd+bwd)
    attention: float  # QK^T and PV contractions (fwd+bwd)

    @property
    def total(self) -> float:
        return self.matmul + self.attention


def transformer_train_flops(
    config, batch_size: int, seq_len: int
) -> FlopsBreakdown:
    """Model FLOPs for ONE optimizer step (forward + backward) of the
    flagship ``TransformerConfig`` LM at the given batch/sequence.

    Matmul-only accounting; layernorms, softmax, RoPE, and residual
    adds are ignored (sub-percent at real widths). MoE blocks cost
    ``top_k`` expert FFNs plus the router per token — the capacity
    padding all_to_all moves is communication, not model FLOPs. A
    looped model (``loop_passes`` > 1) runs every block and, in
    training over all its exits, the head once a pass. A "kda", "gdn"
    or "mla" layer (``layer_types``), and a "full_attention" layer of
    a model with ``attention_gate``, is counted at its own projections
    and mixing (``_kda_layer`` / ``_gdn_layer`` / ``_mla_layer`` /
    ``_gated_attention_layer``), a layer of a kind that
    ``attention_kinds`` describes at the kind's own heads and, under a
    window, over its band (``_kind_attention_layer``); a layer with routed
    experts at its router, the HELD share of the routed experts under
    even routing, and the shared expert (the same products whatever
    the experts' gate function, ``experts_activation``, and whatever
    the router reads, ``experts_routed_on``; a kind without rotary,
    ``AttentionKind(rope=False)``, saves nothing counted here). A
    prediction module
    (``mtp_depth``) is one more block of the last trunk layer's kind
    (its mixer, its routed or dense FFN), the ``[2 d, d]`` projection
    into it and a second pass of the head over its rows.
    """
    d = config.d_model
    d_ff = config.d_ff
    tokens = batch_size * seq_len
    passes = getattr(config, "loop_passes", 1)

    # up + down projections (and the gate's of a SwiGLU), per token
    ffn_matmuls = 3 if getattr(config, "ffn", "gelu") == "swiglu" else 2
    dense_ffn = 2 * (ffn_matmuls * d * d_ff)
    moe_every = getattr(config, "moe_every_n", 0) or 0
    num_moe = (
        sum(
            1
            for i in range(1, config.num_layers + 1)
            if i % moe_every == 0
        )
        if moe_every
        else 0
    )
    num_dense = config.num_layers - num_moe
    top_k = max(getattr(config, "moe_top_k", 1), 1)
    # (A Switch expert is up + down whatever ``ffn`` says.)
    moe_ffn = top_k * 2 * (2 * d * d_ff) + 2 * d * max(
        getattr(config, "moe_num_experts", 0), 0
    )

    proj = 2 * (4 * d * d)  # fused QKV (3 d^2) + output (d^2), per token
    head = 2 * d * config.vocab_size  # LM head, per token
    kinds = getattr(config, "layer_types", None) or ()
    apart = {"kda": _kda_layer, "mla": _mla_layer, "gdn": _gdn_layer}
    if getattr(config, "attention_gate", False):
        apart["full_attention"] = _gated_attention_layer
    for kind, _ in getattr(config, "attention_kinds", ()):
        apart[kind] = functools.partial(_kind_attention_layer, kind=kind)
    new_kinds = [k for k in kinds if k in apart]
    extra_matmul = extra_attn = 0.0
    if new_kinds:
        # These layers' mixers are not ``proj`` + softmax attention at
        # d_model, and their FFN may be routed: counted apart.
        for layer, kind in enumerate(kinds):
            if kind not in apart:
                continue
            mix, attn = apart[kind](config, seq_len)
            extra_matmul += mix - proj
            extra_attn += attn
            if config.routed(layer):
                extra_matmul += _routed_ffn(config) - dense_ffn
    # Attention contractions: QK^T and PV are each 2*S*d_model FLOPs
    # per token (summed over heads); the causal mask discards half the
    # rectangle, and backward recomputes both contractions twice.
    attn_per_token = 2 * (2 * seq_len * d)
    if getattr(config, "causal", True):
        attn_per_token /= 2
    if getattr(config, "mtp_depth", 0):
        # The module's block as the layer it is, its projection and
        # its pass of the head.
        at, kind = config.num_layers, config.mixer(config.num_layers)
        mix, attn = (
            apart[kind](config, seq_len) if kind in apart
            else (proj, attn_per_token)
        )
        extra_matmul += (
            mix + 2 * (2 * d) * d + head
            + (_routed_ffn(config) if config.routed(at) else dense_ffn)
        )
        extra_attn += attn
    fwd_matmul = tokens * passes * (
        config.num_layers * proj
        + num_dense * dense_ffn
        + num_moe * moe_ffn
        + head
        + extra_matmul
    )
    fwd_attn = tokens * passes * (
        (config.num_layers - len(new_kinds)) * attn_per_token + extra_attn
    )

    return FlopsBreakdown(
        matmul=3.0 * fwd_matmul, attention=3.0 * fwd_attn
    )


def _kda_layer(config, seq_len: int = 0) -> tuple[float, float]:
    """(projection FLOPs, mixing FLOPs) a token of one "kda" layer:
    q, k, v and out at ``heads * head_dim``, the two low-rank gate
    pairs and beta; the chunked delta rule's products a chunk (its own
    four and the four that carry the state), as
    ``benchmark/kda.py:forward_flops_per_token`` counts them."""
    from adaptdl_tpu.ops.kda import CHUNK as chunk  # where it is used

    d, heads = config.d_model, config.num_heads
    hd, rank = config.attention_head_dim, config.kda_gate_rank
    width = heads * hd
    proj = 2 * (
        3 * d * width + 2 * (d * rank + rank * width) + d * heads + width * d
    )
    mixing = 2 * heads * (4 * chunk * hd + 3 * hd * hd + chunk * hd)
    return float(proj), float(mixing)


def _gdn_layer(config, seq_len: int = 0) -> tuple[float, float]:
    """(projection FLOPs, mixing FLOPs) a token of one "gdn" layer:
    q and k of the key heads, v and z of the value heads, b and a, and
    out; the chunked delta rule's products a chunk and value head, as
    ``_kda_layer`` counts them."""
    from adaptdl_tpu.ops.kda import CHUNK as chunk  # where it is used

    d, heads = config.d_model, config.linear_value_heads
    dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
    k_width, v_width = config.linear_key_heads * dk, heads * dv
    proj = 2 * (
        d * (2 * k_width + 2 * v_width) + d * 2 * heads + v_width * d
    )
    mixing = 2 * heads * (
        3 * chunk * dk + chunk * dv + 3 * dk * dv + chunk * dv
    )
    return float(proj), float(mixing)


def _gated_attention_layer(config, seq_len: int) -> tuple[float, float]:
    """(projection FLOPs, attention FLOPs) a token of a grouped-query
    attention layer with an output gate: q twice as wide (the gate), k
    and v of the kv heads, out; QK^T and PV at ``head_dim``, the causal
    half."""
    d, heads, hd = config.d_model, config.num_heads, config.attention_head_dim
    kv_heads = config.num_kv_heads or heads
    proj = 2 * (
        d * heads * 2 * hd + d * 2 * kv_heads * hd + heads * hd * d
    )
    attn = 2 * seq_len * heads * 2 * hd
    if getattr(config, "causal", True):
        attn /= 2
    return float(proj), float(attn)


def _kind_attention_layer(
    config, seq_len: int, kind: str
) -> tuple[float, float]:
    """(projection FLOPs, attention FLOPs) a token of a grouped-query
    attention layer of a kind ``attention_kinds`` describes: the
    kind's own number of query heads (q, a per-head gate, out), k and
    v of the kv heads; QK^T and PV over the causal half, or, where the
    kind has a window, over the BAND: a query's keys are ``min(i + 1,
    window)``, their mean over the row what a token is counted at."""
    own = config.attention_kind(kind)
    d, heads, hd = config.d_model, own.num_heads, config.attention_head_dim
    kv_heads = config.num_kv_heads or heads
    gate = (
        heads * hd if getattr(config, "attention_gate", False)
        else heads if getattr(config, "attention_head_gate", False) else 0
    )
    proj = 2 * (
        d * heads * hd + d * gate + d * 2 * kv_heads * hd + heads * hd * d
    )
    from adaptdl_tpu.ops.flash_attention import keys_in_window

    keys = float(seq_len)
    if getattr(config, "causal", True):
        keys = seq_len / 2 if own.window is None else (
            keys_in_window(seq_len, own.window) / seq_len
        )
    return float(proj), float(2 * keys * heads * 2 * hd)


def _mla_layer(config, seq_len: int) -> tuple[float, float]:
    """(projection FLOPs, attention FLOPs) a token of one "mla"
    layer: q (through its bottleneck where ``q_lora_rank`` is set),
    kv_a, kv_b and out; QK^T at the q/k width and PV at the v width,
    the causal half."""
    d, heads = config.d_model, config.num_heads
    qk = config.qk_nope_head_dim + config.qk_rope_head_dim
    q_rank = getattr(config, "q_lora_rank", 0)
    proj = 2 * (
        (d * q_rank + q_rank * heads * qk if q_rank else d * heads * qk)
        + d * (config.kv_lora_rank + config.qk_rope_head_dim)
        + config.kv_lora_rank * heads
        * (config.qk_nope_head_dim + config.v_head_dim)
        + heads * config.v_head_dim * d
    )
    attn = 2 * seq_len * heads * (qk + config.v_head_dim)
    if getattr(config, "causal", True):
        attn /= 2
    return float(proj), float(attn)


def _routed_ffn(config) -> float:
    """FLOPs a token of a routed layer's FFN: the router over all
    experts, ``top_k * held / total`` gated experts (even routing),
    the shared expert and its gate."""
    d = config.d_model
    held = config.experts_held or config.experts_total
    experts = config.experts_top_k * held / config.experts_total
    return float(
        2 * d * config.experts_total
        + experts * 2 * 3 * d * config.d_expert
        + 2 * 3 * d * config.d_shared_expert
        + (2 * d if getattr(config, "shared_expert_gate", False) else 0)
    )


def mfu(
    flops_per_step: float,
    step_time_s: float,
    num_devices: int = 1,
    device=None,
    peak_flops: float | None = None,
) -> float | None:
    """Achieved model FLOPs / peak; None off-TPU (no honest peak)."""
    if peak_flops is None:
        if device is None:
            import jax

            device = jax.devices()[0]
        peak_flops = device_peak_flops(device)
    if not peak_flops or step_time_s <= 0:
        return None
    return flops_per_step / (step_time_s * num_devices * peak_flops)
