"""Assigned-architecture registry: ``get_config(id)`` / ``get_smoke(id)``.

Every architecture is a module exporting CONFIG (the exact published
dims) and SMOKE (a reduced same-family variant for CPU tests).  Shapes
(the 4 assigned input-shape cells) and per-arch skip rules live in
``shapes.py``.
"""

import importlib
from typing import Dict

from repro.models.base import ArchConfig

ARCH_IDS = (
    "qwen3_14b",
    "gemma3_12b",
    "qwen1_5_0_5b",
    "gemma_2b",
    "paligemma_3b",
    "kimi_k2_1t_a32b",
    "phi3_5_moe_42b_a6_6b",
    "jamba_1_5_large_398b",
    "xlstm_350m",
    "seamless_m4t_large_v2",
    "mamba_2_8b",
    # the paper's own evaluation family (CPU-scale analogue)
    "paper_tiny_lm",
)

_ALIAS = {a.replace("_", "-"): a for a in ARCH_IDS}
# also accept the ids exactly as assigned (dots/dashes)
_ALIAS.update({
    "qwen3-14b": "qwen3_14b",
    "gemma3-12b": "gemma3_12b",
    "qwen1.5-0.5b": "qwen1_5_0_5b",
    "gemma-2b": "gemma_2b",
    "paligemma-3b": "paligemma_3b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe_42b_a6_6b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "xlstm-350m": "xlstm_350m",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "mamba-2.8b": "mamba_2_8b",
})


def canonical(arch_id: str) -> str:
    key = arch_id.strip()
    if key in ARCH_IDS:
        return key
    if key in _ALIAS:
        return _ALIAS[key]
    raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ALIAS)}")


def _module(arch_id: str):
    return importlib.import_module(f"repro.configs.{canonical(arch_id)}")


def get_config(arch_id: str) -> ArchConfig:
    return _module(arch_id).CONFIG


def get_smoke(arch_id: str) -> ArchConfig:
    return _module(arch_id).SMOKE


def all_configs() -> Dict[str, ArchConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


from repro.configs.shapes import (  # noqa: E402
    SHAPES,
    input_specs,
    shape_is_applicable,
    applicable_shapes,
)

__all__ = [
    "ARCH_IDS",
    "canonical",
    "get_config",
    "get_smoke",
    "all_configs",
    "SHAPES",
    "input_specs",
    "shape_is_applicable",
    "applicable_shapes",
]
