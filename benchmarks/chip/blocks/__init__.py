"""A configuration's block: ``blocks/<name>.py``, named by the ``"block"``
key of the configuration's file (``dense`` where it has none).

A block file holds what is particular to one kind of layer, and the rest of
the harness what every block shares (the seeding, the embedding and head,
the layer scan, the final norm, the rope and attention helpers):

- ``dims(cfg)``: the sizes it reads from the configuration's published keys;
- ``check_program(arch, dm)``: ``{key: (want, have)}`` for each of those
  sizes that the program's ``ArchConfig`` does not share;
- ``layer_leaves(dm)``: one layer's weight leaves in the program's layout,
  ``(path, shape, kind)``, with the leaf's dtype as a fourth entry where the
  program serves it in another dtype than bfloat16 (``weights.py``);
- ``block(x, lp, dm, quant)``: its float32 reference, on ``x (K, T, d)``
  with the layer's leaves ``lp`` in float32 (``reference.py``);
- ``matrix_params(dm)`` and ``vector_params(dm)``: one layer's parameters
  in matrices and in vectors (``roofline.py``).

A block file imports nothing of the program.
"""

from __future__ import annotations

import importlib
import re
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_\-]{0,63}")      # a module name: no "."


def load(name: str) -> ModuleType:
    if not NAME.fullmatch(name) or not (HERE / f"{name}.py").is_file():
        raise FileNotFoundError(f"no block {name!r} ({HERE / name}.py)")
    return importlib.import_module(f"{__name__}.{name}")
