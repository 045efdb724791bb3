"""Architecture registry: ``get_arch("<id>")`` -> ArchConfig.

One module per assigned architecture; ids match the assignment list.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import (ArchConfig, ShapeSpec, ALL_SHAPES,
                                 SHAPES_BY_NAME, applicable_shapes,
                                 skip_reason)

_ARCH_MODULES: Dict[str, str] = {
    "xlstm-1.3b": "xlstm_1_3b",
    "minicpm-2b": "minicpm_2b",
    "h2o-danube-3-4b": "h2o_danube_3_4b",
    "deepseek-coder-33b": "deepseek_coder_33b",
    "minitron-8b": "minitron_8b",
    "hubert-xlarge": "hubert_xlarge",
    "llama-3.2-vision-11b": "llama_3_2_vision_11b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "mixtral-8x7b": "mixtral_8x7b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

ARCH_IDS: List[str] = list(_ARCH_MODULES)


def get_arch(name: str) -> ArchConfig:
    if name.endswith("-smoke"):
        return get_arch(name[: -len("-smoke")]).reduced()
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.CONFIG


def get_schedule(name: str) -> str:
    """Per-arch LR schedule hint (MiniCPM ships WSD; others cosine)."""
    if name not in _ARCH_MODULES:
        return "cosine"
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return getattr(mod, "SCHEDULE", "cosine")


def all_cells():
    """Every assigned (arch, shape) cell incl. skipped ones with reasons."""
    cells = []
    for aid in ARCH_IDS:
        cfg = get_arch(aid)
        for shape in ALL_SHAPES:
            cells.append((aid, shape.name, skip_reason(cfg, shape)))
    return cells
