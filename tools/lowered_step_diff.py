"""Are two lowerings of a cell's step the same program? For a change
that must leave a cell as it was, without the chip: lower the cell's
REAL-size step for a described v5e on both trees
(``tools/compile_step_v5e.py <cell> --lower-only --text FILE``, once in
each checkout) and hand both files to this tool.

Two lowerings of one program still differ byte for byte wherever a
source line moved: a Mosaic kernel travels in its custom call's
``backend_config`` as MLIR bytecode WITH its debug locations (file and
line of every op, the callers' too). So every line outside the Mosaic
calls is compared as text, and each kernel body is parsed and printed
without locations, then compared beside the rest of its call's
configuration. Exit code 0 and ``same program`` where nothing else
differs.

    JAX_PLATFORMS=cpu python tools/lowered_step_diff.py PARENT.txt CHANGE.txt
"""

from __future__ import annotations

import base64
import difflib
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_CONFIG = re.compile(r'backend_config = "(\{.*?\})"')


def program(path: str) -> tuple[list[str], list[tuple[str, str]]]:
    """-> (the text with every Mosaic call's configuration cut out,
    [(a call's configuration without its body, the body without debug
    locations)])."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True  # ``stable_mosaic``
    rest, kernels = [], []
    with open(path) as f:
        for line in f:
            found = _CONFIG.search(line) if "tpu_custom_call" in line else None
            if found is None:
                rest.append(line)
                continue
            # StableHLO's string escapes: \22 a quote, \5C a backslash.
            config = json.loads(
                found.group(1).replace("\\22", '"').replace("\\5C", "\\")
            )["custom_call_config"]
            with context:
                body = ir.Module.parse(
                    base64.b64decode(config.pop("body"))
                ).operation.get_asm(enable_debug_info=False)
            kernels.append((json.dumps(config, sort_keys=True), body))
            rest.append(_CONFIG.sub("backend_config = <mosaic>", line))
    return rest, kernels


def main(parent: str, change: str) -> int:
    rest_a, kernels_a = program(parent)
    rest_b, kernels_b = program(change)
    same_rest = rest_a == rest_b
    same = sum(a == b for a, b in zip(kernels_a, kernels_b))
    print(
        f"lines outside Mosaic calls: {len(rest_a)} / {len(rest_b)}, "
        f"{'equal' if same_rest else 'DIFFERENT'}; Mosaic calls: "
        f"{len(kernels_a)} / {len(kernels_b)}, {same} equal without "
        f"debug locations"
    )
    if not same_rest:
        sys.stdout.writelines(
            line[:200] + "\n" for line in list(
                difflib.unified_diff(rest_a, rest_b, parent, change, n=0)
            )[:40]
        )
    for at, (a, b) in enumerate(zip(kernels_a, kernels_b)):
        if a != b:
            print(f"Mosaic call {at}: configuration "
                  f"{'equal' if a[0] == b[0] else 'DIFFERENT'}")
            print("\n".join(
                line[:200] for line in list(difflib.unified_diff(
                    a[1].splitlines(), b[1].splitlines(), lineterm="", n=1
                ))[:40]
            ))
            break
    ok = same_rest and len(kernels_a) == len(kernels_b) == same
    print("same program" if ok else "NOT the same program")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
