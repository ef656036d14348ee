"""Assigned architecture registry: ``--arch <id>`` resolves here.

Each module defines ``CONFIG`` (the exact assigned full-scale config) and the
registry exposes ``get(name)`` / ``list_archs()``.  ``get`` also resolves
``<arch>-smoke``, the name :meth:`ArchConfig.reduced` gives the tiny
same-family config that CPU tests and examples serve; the plain name is
always the published widths.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

from ..models.config import ArchConfig

_ARCHS = [
    "internvl2_2b",
    "mamba2_1p3b",
    "starcoder2_3b",
    "qwen3_14b",
    "qwen1p5_110b",
    "minicpm_2b",
    "moonshot_v1_16b_a3b",
    "qwen3_moe_235b_a22b",
    "whisper_base",
    "recurrentgemma_2b",
]

_ALIAS = {
    "internvl2-2b": "internvl2_2b",
    "mamba2-1.3b": "mamba2_1p3b",
    "starcoder2-3b": "starcoder2_3b",
    "qwen3-14b": "qwen3_14b",
    "qwen1.5-110b": "qwen1p5_110b",
    "minicpm-2b": "minicpm_2b",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "whisper-base": "whisper_base",
    "recurrentgemma-2b": "recurrentgemma_2b",
}


SMOKE_SUFFIX = "-smoke"


def get(name: str) -> ArchConfig:
    if name.endswith(SMOKE_SUFFIX):
        return get(name[:-len(SMOKE_SUFFIX)]).reduced()
    mod_name = _ALIAS.get(name, name.replace("-", "_").replace(".", "p"))
    if mod_name not in _ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {names()}")
    mod = importlib.import_module(f".{mod_name}", __package__)
    return mod.CONFIG


def list_archs() -> List[str]:
    """The published configs."""
    return list(_ALIAS.keys())


def names() -> List[str]:
    """Every name :func:`get` resolves: each published config and its
    ``-smoke`` reduction."""
    return [a + s for a in list_archs() for s in ("", SMOKE_SUFFIX)]


def all_configs() -> Dict[str, ArchConfig]:
    return {n: get(n) for n in list_archs()}
