"""Compilation-cache persistence + remat-policy knob tests."""

import functools
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

WORKER = r"""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import adaptdl_tpu

adaptdl_tpu.initialize_job()
print("CACHE_DIR=" + str(jax.config.jax_compilation_cache_dir))
"""


def _run(extra_env):
    env = dict(os.environ)
    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [repo_root, env.get("PYTHONPATH")])
    )
    env.update({"JAX_PLATFORMS": "cpu"})
    env.pop("ADAPTDL_COMPILE_CACHE", None)
    env.pop("ADAPTDL_SHARE_PATH", None)
    env.pop("ADAPTDL_CHECKPOINT_PATH", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra_env)
    out = subprocess.run(
        [sys.executable, "-c", WORKER],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr
    line = [
        l for l in out.stdout.splitlines() if l.startswith("CACHE_DIR=")
    ][0]
    return line.split("=", 1)[1]


def test_cache_dir_set_from_checkpoint_path(tmp_path):
    got = _run({"ADAPTDL_CHECKPOINT_PATH": str(tmp_path)})
    assert got == os.path.join(str(tmp_path), ".jax_compile_cache")
    assert os.path.isdir(got)


def test_cache_dir_prefers_share_path(tmp_path):
    share = tmp_path / "share"
    ckpt = tmp_path / "ckpt"
    share.mkdir()
    ckpt.mkdir()
    got = _run(
        {
            "ADAPTDL_SHARE_PATH": str(share),
            "ADAPTDL_CHECKPOINT_PATH": str(ckpt),
        }
    )
    assert got == os.path.join(str(share), ".jax_compile_cache")


def test_cache_off_and_explicit_override(tmp_path):
    got = _run(
        {
            "ADAPTDL_CHECKPOINT_PATH": str(tmp_path),
            "ADAPTDL_COMPILE_CACHE": "off",
        }
    )
    assert got == "None"
    override = tmp_path / "elsewhere"
    got = _run(
        {
            "ADAPTDL_CHECKPOINT_PATH": str(tmp_path),
            "ADAPTDL_COMPILE_CACHE": str(override),
        }
    )
    assert got == os.path.join(str(override), ".jax_compile_cache")


def test_external_cache_dir_is_left_alone(tmp_path):
    """A deployment that places the cache with
    ``JAX_COMPILATION_CACHE_DIR`` keeps it there: neither the knob nor
    the checkpoint path may redirect it (jax reads the variable into
    its own config; bootstrap must not overwrite that)."""
    external = tmp_path / "external"
    got = _run(
        {
            "JAX_COMPILATION_CACHE_DIR": str(external),
            "ADAPTDL_CHECKPOINT_PATH": str(tmp_path),
            "ADAPTDL_COMPILE_CACHE": str(tmp_path / "knob"),
        }
    )
    assert got == str(external)
    assert not (tmp_path / ".jax_compile_cache").exists()
    assert not (tmp_path / "knob").exists()


def test_cache_dir_falls_back_to_checkout():
    """No knob, no share path, no checkpoint path: one FIXED directory
    inside the checkout (the path is part of the cache key), never no
    cache and never a temporary name."""
    from adaptdl_tpu import env

    got = _run({})
    assert got == os.path.join(env.checkout_root(), ".jax_compile_cache")
    assert env.checkout_root() == os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )


def _linear_trainer():
    import optax

    from adaptdl_tpu.parallel import create_mesh
    from adaptdl_tpu.trainer import ElasticTrainer

    def loss_fn(params, batch, _rng):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    trainer = ElasticTrainer(
        loss_fn=loss_fn,
        params={"w": jnp.zeros(4)},
        optimizer=optax.adam(0.1),
        init_batch_size=8,
        mesh=create_mesh(devices=jax.devices()[:1]),
    )
    rng = np.random.default_rng(0)
    batch = trainer.shard_batch(
        {
            "x": rng.normal(size=(8, 4)).astype(np.float32),
            "y": rng.normal(size=(8,)).astype(np.float32),
        }
    )
    return trainer, batch


def test_aot_entry_that_cannot_run_falls_back_and_is_dropped(
    tmp_path, monkeypatch, caplog
):
    """A cached executable that deserializes but fails when it RUNS
    (dispatch is asynchronous: the error surfaces at a later
    block_until_ready, not at the call) must still end on the jitted
    path with a logged warning and the entry gone — not kill the
    incarnation and every restart that finds the same entry."""
    from adaptdl_tpu import aot_cache

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    entry = tmp_path / ".jax_aot_cache" / "deadbeef"
    entry.parent.mkdir()
    entry.write_bytes(b"x")

    class FailsLater:
        def block_until_ready(self):
            raise RuntimeError("Function wrapped_reverse.14 not found")

    calls = []

    def poisoned(state, batch, aux):
        calls.append(1)
        return state, {"loss": FailsLater()}

    monkeypatch.setattr(
        aot_cache, "load_or_compile",
        lambda *a, **kw: (poisoned, "deadbeef"),
    )
    trainer, batch = _linear_trainer()
    state = trainer.init_state()
    step = trainer.train_step(8, 0)
    with caplog.at_level("WARNING", logger="adaptdl_tpu.trainer"):
        state, m = step(state, batch)
        state, m = step(state, batch)
    assert np.isfinite(float(m["loss"])) and int(state.step) == 2
    assert calls == [1]  # tried once, then the jitted path for good
    assert "falling back to the jitted path" in caplog.text
    assert not entry.exists()


def test_fresh_and_restored_state_share_an_aot_fingerprint(
    tmp_path, monkeypatch
):
    """Incarnation 0 calls its first step with a FRESH state,
    incarnation 1 with a RESTORED one; the AOT cache keys on the
    arguments' placement, so both must place every leaf the same way
    (``init_state`` used to leave Adam's step count on the default
    device) or the predecessor's executable never serves the restart."""
    from adaptdl_tpu import aot_cache, checkpoint

    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")

    def incarnation():
        trainer, batch = _linear_trainer()
        holder = {"state": trainer.init_state()}
        ckpt = trainer.make_checkpoint_state(
            lambda: holder["state"],
            lambda s: holder.__setitem__("state", s),
        )
        restored = checkpoint.load_state(ckpt)
        fp = aot_cache.fingerprint(
            trainer, (8, 0), (holder["state"], batch, ())
        )
        return restored, fp, ckpt

    restored, fresh_fp, ckpt = incarnation()
    assert not restored
    checkpoint.save_all_states()
    ckpt.unregister()
    restored, restored_fp, ckpt = incarnation()
    ckpt.unregister()
    assert restored
    assert restored_fp == fresh_fp


def test_executable_from_persistent_cache_is_not_reserialized(
    tmp_path, monkeypatch
):
    """On the CPU only an executable this process compiled goes into
    the AOT cache: one that jax's persistent cache served does not
    survive a second serialization on XLA:CPU (the entry loads, then
    fails at run time), and the persistent cache serves the next
    incarnation anyway."""
    from adaptdl_tpu import aot_cache
    from adaptdl_tpu.bootstrap import _enable_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("ADAPTDL_COMPILE_CACHE", str(tmp_path / "pc"))
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    _enable_compilation_cache()

    def first_step_with_aot_dir(name):
        monkeypatch.setenv("ADAPTDL_AOT_CACHE", str(tmp_path / name))
        trainer, batch = _linear_trainer()
        _, m = trainer.train_step(8, 0)(trainer.init_state(), batch)
        jax.block_until_ready(m["loss"])
        aot_cache.wait_for_writes()
        directory = tmp_path / name / ".jax_aot_cache"
        return sorted(directory.iterdir()) if directory.exists() else []

    assert len(first_step_with_aot_dir("a")) == 1  # compiled here
    assert first_step_with_aot_dir("b") == []  # served by the cache


@functools.lru_cache(maxsize=None)
def _remat_loss_and_grads(policy, attention="plain", remat=True):
    """One eager value_and_grad of the tiny LM under a remat policy;
    cached so the references are computed once for all cases."""
    from adaptdl_tpu.models import (
        TransformerConfig,
        init_transformer,
        lm_loss_fn,
    )
    from adaptdl_tpu.ops import make_flash_attention

    causal = "bidirectional" not in attention
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, 64, size=(2, 17)), jnp.int32
        )
    }
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=16, dtype=jnp.float32, remat=remat,
        remat_policy=policy, causal=causal,
        attention_fn=(
            make_flash_attention(causal=causal, block_q=8, block_k=8)
            if "flash" in attention else None
        ),
    )
    model, params = init_transformer(cfg, seq_len=16)
    loss, grads = jax.value_and_grad(lm_loss_fn(model))(
        params, batch, jax.random.key(0)
    )
    return float(loss), grads


@pytest.mark.parametrize(
    "attention", ["plain", "flash", "flash_bidirectional"]
)
@pytest.mark.parametrize(
    "policy",
    [None, "dots_with_no_batch_dims_saveable", "nothing_saveable"],
)
def test_remat_policy_preserves_numerics(policy, attention):
    """Remat policies change the memory/recompute schedule, never the
    values: loss and gradients match the build WITHOUT remat, with
    plain attention and with the flash kernel (causal and not), whose
    output a remat'd block keeps under every policy, the composed
    ``nothing_saveable`` too (``block_remat``)."""
    base_loss, base_grads = _remat_loss_and_grads(
        None, attention, remat=False
    )
    loss, grads = _remat_loss_and_grads(policy, attention)
    assert loss == pytest.approx(base_loss, rel=1e-6)
    for a, b in zip(jax.tree.leaves(base_grads), jax.tree.leaves(grads)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-6, atol=1e-7
        )


def test_remat_policy_typo_fails_eagerly():
    from adaptdl_tpu.models import TransformerConfig, init_transformer

    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=16, dtype=jnp.float32, remat=True,
        remat_policy="dots_savable",  # typo
    )
    with pytest.raises(ValueError, match="remat_policy"):
        init_transformer(cfg, seq_len=16)
