"""``tools/lowered_step_diff.py``: two lowerings of one program are the
same program although a Mosaic kernel carries its source lines; another
schedule, or another line outside the kernels, is not (PR 46)."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
flash_mod = importlib.import_module("adaptdl_tpu.ops.flash_attention")


@pytest.fixture
def tool(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    monkeypatch.setattr(flash_mod, "_use_interpret", lambda: False)
    sys.modules.pop("lowered_step_diff", None)
    return importlib.import_module("lowered_step_diff")


def _lowered(v5e, path, block=128, scale=1.0, lines_down=0):
    """The gradient of the flash kernels at a small head, lowered for
    the described chip from a caller ``lines_down`` lines further down
    its file. (The gradient: since PR 57 the forward's call is jitted
    and traced once a signature, so a second caller finds the first's
    lines in it; the backward kernel carries its own caller's.)"""
    one = SingleDeviceSharding(v5e.devices[0])
    arg = jax.ShapeDtypeStruct((2, 2, 512, 64), jnp.bfloat16, sharding=one)
    scope = {"flash": flash_mod.flash_attention, "block": block}
    exec(  # noqa: S102 - the same caller at another line
        compile(
            "\n" * lines_down
            + "def attend(q, k, v):\n"
            + "    return flash(q, k, v, True, None, block, block)\n",
            "moved.py", "exec",
        ),
        scope,
    )
    text = jax.jit(
        jax.grad(
            lambda q, k, v: jnp.sum(
                scope["attend"](q, k, v).astype(jnp.float32)
            ) * scale
        )
    ).lower(arg, arg, arg).as_text()
    with open(path, "w") as f:
        f.write(text)
    return text


@pytest.mark.parametrize(
    "change,same",
    [
        ({"lines_down": 7}, True),  # the caller moved: locations only
        ({"scale": 2.0}, False),  # a line outside the kernel
    ],
)
def test_same_program_is_told_from_another(
    v5e, tool, tmp_path, capsys, change, same
):
    parent, moved = str(tmp_path / "a.txt"), str(tmp_path / "b.txt")
    before = _lowered(v5e, parent)
    after = _lowered(v5e, moved, **change)
    assert ("tpu_custom_call" in before) and before != after
    assert tool.main(parent, moved) == (0 if same else 1)
    said = capsys.readouterr().out
    assert ("NOT the same program" in said) != same
    assert "Mosaic calls: 2 / 2" in said
