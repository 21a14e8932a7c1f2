"""Architecture config registry.

``get_config(arch_id)`` / ``get_smoke_config(arch_id)`` resolve the public
``--arch`` ids (which contain dots/dashes) to the sanitized config modules.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401  (re-exported)
    ArchConfig,
    ArchFamily,
    AttentionKind,
    InputShape,
    NormKind,
    INPUT_SHAPES,
    TRAIN_4K,
    PREFILL_32K,
    DECODE_32K,
    LONG_500K,
)

# public --arch id -> config module name
_ARCH_MODULES: Dict[str, str] = {
    "zamba2-1.2b": "zamba2_1p2b",
    "yi-6b": "yi_6b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "rwkv6-1.6b": "rwkv6_1p6b",
    "tinyllama-1.1b": "tinyllama_1p1b",
    "qwen1.5-0.5b": "qwen1p5_0p5b",
    "granite-moe-3b-a800m": "granite_moe_3b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "stablelm-1.6b": "stablelm_1p6b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
}

ARCH_IDS: List[str] = list(_ARCH_MODULES)


def _module(arch_id: str):
    if arch_id not in _ARCH_MODULES:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {', '.join(ARCH_IDS)}"
        )
    return importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch_id]}")


def get_config(arch_id: str) -> ArchConfig:
    """Full-scale assigned config for ``--arch <id>``."""
    return _module(arch_id).CONFIG


def get_smoke_config(arch_id: str) -> ArchConfig:
    """Reduced same-family variant (<=2 layers, d_model<=512, <=4 experts)."""
    return _module(arch_id).smoke_config()


def get_shape(shape_id: str) -> InputShape:
    if shape_id not in INPUT_SHAPES:
        raise KeyError(
            f"unknown input shape {shape_id!r}; available: {', '.join(INPUT_SHAPES)}"
        )
    return INPUT_SHAPES[shape_id]
