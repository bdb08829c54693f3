"""The benchmark's nine configurations as the CPU suite runs them: each
one's tiny sizes, said ONCE, and the scaffolding every configuration's
tests share (the configuration's module, its sizes, a build, a loader's
stand-in, a relative error). ``tests/step_digests.py`` takes its sizes
from here too, so a size changed for a test moves the digest on record
with it. Nothing here knows one configuration from another: what only
one needs stays in its test file. Call ``module`` / ``sizes`` / ``built``
qualified (``configurations.sizes(NAME)``): the tests' own locals carry
those names. (A helper: pytest collects nothing from it.)"""

from __future__ import annotations

import contextlib
import copy
import functools
import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
GEOMETRY = {"global_batch": 4, "atomic_bsz": 2, "accum_steps": 1}

TINY = {
    "gpt2-124m": {
        "n_layer": 2, "n_embd": 32, "n_head": 2, "vocab_size": 211,
        "n_positions": 32, "compute_dtype": "float32",
    },
    "lfm2-8b-a1b": {
        "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 24, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_experts": 8, "experts_held": 2,
        "num_experts_per_tok": 2, "vocab_size": 97, "sequence_length": 32,
        "compute_dtype": "float32",
    },
    "keye-vl-2.0-30b-a3b": {
        "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 24, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "router_width": 8,
        "experts_held": 8, "num_experts": 8, "num_local_experts": 8,
        "num_experts_per_tok": 2, "vocab_size": 97, "sequence_length": 32,
        "num_hidden_layers": 1, "compute_dtype": "float32",
        "sa_config": {
            "indexer_head_dim": 16, "indexer_num_heads": 3,
            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
            "q_chunk_size": 512, "topk": 8,
        },
    },
    "ouro-2.6b": {
        "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 48, "vocab_size": 97,
        "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
        "sequence_length": 32, "head_chunk_columns": 32,
        "compute_dtype": "float32",
    },
    "kimi-linear-48b-a3b": {
        "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "linear_attn_config": {
            "full_attn_layers": [4], "head_dim": 8,
            "kda_layers": [1, 2, 3, 5],
            "num_heads": 2, "short_conv_kernel_size": 4,
        },
        "kv_lora_rank": 12, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
        "v_head_dim": 8, "router_width": 16, "experts_held": 4,
        "num_experts": 4, "num_experts_per_token": 2,
        "num_experts_per_tok": 2,
        "vocab_size": 97, "sequence_length": 64, "kda_gate_rank": 8,
        "kda_chunk": 16, "head_chunk_rows": 32, "compute_dtype": "float32",
    },
    "qwen3-next-80b-a3b": {
        "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "linear_attn_config": {
            "num_heads": 4, "head_dim": 8, "kda_layers": [1, 2, 3],
        },
        "router_width": 16, "experts_held": 4, "num_experts": 4,
        "num_experts_per_tok": 3, "vocab_size": 97, "sequence_length": 64,
        "kda_chunk": 16, "head_chunk_rows": 32, "compute_dtype": "float32",
    },
    "laguna-xs.2": {
        "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 16,
        "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
        "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
        "sliding_window": 24,
        "router_width": 16, "experts_held": 4, "num_experts": 4,
        "num_experts_per_tok": 3, "vocab_size": 97, "sequence_length": 64,
        "head_chunk_rows": 32, "compute_dtype": "float32",
    },
    "glm-4.7-flash": {
        "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": 16, "kv_lora_rank": 12, "qk_nope_head_dim": 12,
        "qk_rope_head_dim": 4, "v_head_dim": 16,
        "router_width": 16, "experts_held": 4, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "vocab_size": 97, "sequence_length": 64,
        "head_chunk_rows": 32, "compute_dtype": "float32",
    },
    # (The readers' names restate the published keys: both are said.)
    "smallthinker-21b-a3b": {
        "hidden_size": 32, "moe_ffn_hidden_size": 16,
        "moe_intermediate_size": 16,
        "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 8,
        "num_attention_heads_per_layer": [14] * 4,
        "sliding_window_size": 24, "sliding_window": 24,
        "router_width": 16, "experts_held": 4, "moe_num_primary_experts": 4,
        "moe_num_active_primary_experts": 3, "num_experts_per_tok": 3,
        "vocab_size": 97, "sequence_length": 64, "head_chunk_rows": 32,
        "compute_dtype": "float32",
    },
}
NAMES = tuple(TINY)


@functools.cache
def module(name):
    """The configuration's own module (``benchmark/configs/<name>.py``),
    loaded once a process."""
    from benchmark import manifest

    return manifest.load_module(os.path.join(CONFIGS, name + ".py"))


def published(name):
    """The configuration's sizes as the benchmark runs them."""
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def sizes(name, **changes):
    """The published sizes under ``TINY[name]`` under ``changes``: a
    case that needs other sizes says the difference."""
    out = published(name)
    out.update(copy.deepcopy(TINY[name]))
    out.update(changes)
    return out


def built(monkeypatch, name, sizes, seed=3, geometry=GEOMETRY):
    """What the configuration's ``build`` gives a one-replica job."""
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    return module(name).build(sizes, dict(geometry), seed)


@contextlib.contextmanager
def rows_of_several_chunks(name):
    """The delta rule's chunk is a constant of ``ops/kda.py`` (64); a
    configuration whose tiny sizes name a ``kda_chunk`` runs under that
    one, so that its rows of 64 tokens are several chunks. The tests'
    autouse fixtures and the digests' generator both go through here."""
    chunk = TINY[name].get("kda_chunk")
    if chunk is None:
        yield
        return
    from adaptdl_tpu.ops import kda as kda_op

    before, kda_op.CHUNK = kda_op.CHUNK, chunk
    try:
        yield
    finally:
        kda_op.CHUNK = before


def loader_stub(atomic, accum):
    """What ``ElasticTrainer.run_step`` reads of a data loader."""

    class Loader:
        current_atomic_bsz = atomic
        current_accum_steps = accum

    return Loader()


def rel(got, want):
    """The largest error as a share of the largest wanted value."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))
