"""Rehearsal without the chip: keye-vl-2.0-30b-a3b-steady's step at
ANOTHER depth than the cell's, compiled by the TPU's own compiler for
a described v5e (``compile_cell_v5e.py`` with ``num_hidden_layers``
overridden). Five layers are what ISSUE 34 asked for; the compiler
refuses them (``RESOURCE_EXHAUSTED``, with the allocations alive at
the peak), which is why the cell runs four (PERF.md section 4).

    JAX_PLATFORMS=cpu python benchmark/tests/compile_keye_layers.py 5
"""

from __future__ import annotations

import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(layers: int) -> None:
    from benchmark import manifest

    load_cell = manifest.load_cell

    def deeper(name):
        cell = load_cell(name)
        cell.sizes["num_hidden_layers"] = layers
        return cell

    manifest.load_cell = deeper
    sys.argv = ["compile_cell_v5e.py", "keye-vl-2.0-30b-a3b-steady"]
    runpy.run_path(
        os.path.join(HERE, "compile_cell_v5e.py"), run_name="__main__"
    )


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 5)
