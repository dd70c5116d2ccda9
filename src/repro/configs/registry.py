"""Architecture registry: ``--arch <id>`` resolution for every entry point."""

from __future__ import annotations

from . import (
    deepseek_67b,
    deepseek_v2_236b,
    deepseek_v2_lite,
    gemma_7b,
    granite_moe_1b,
    internvl2_1b,
    qwen1_5_4b,
    qwen2_1_5b,
    whisper_medium,
    xlstm_350m,
    zamba2_2_7b,
)
from .base import SHAPES, MeshConfig, ModelConfig, ShapeConfig

_MODULES = {
    "deepseek-67b": deepseek_67b,
    "qwen2-1.5b": qwen2_1_5b,
    "qwen1.5-4b": qwen1_5_4b,
    "gemma-7b": gemma_7b,
    "whisper-medium": whisper_medium,
    "xlstm-350m": xlstm_350m,
    "internvl2-1b": internvl2_1b,
    "zamba2-2.7b": zamba2_2_7b,
    "granite-moe-1b-a400m": granite_moe_1b,
    "deepseek-v2-236b": deepseek_v2_236b,
    "deepseek-v2-lite": deepseek_v2_lite,
}

ARCH_IDS = tuple(_MODULES)


def get(name: str, smoke: bool = False) -> ModelConfig:
    key = name.removesuffix("-smoke")
    if key not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {', '.join(ARCH_IDS)}")
    mod = _MODULES[key]
    return mod.SMOKE if (smoke or name.endswith("-smoke")) else mod.CONFIG


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; known: {', '.join(SHAPES)}")
    return SHAPES[name]


def cells(include_skipped: bool = False):
    """All assigned (arch, shape) cells; long_500k only for sub-quadratic
    archs unless include_skipped (skips recorded in DESIGN.md §5)."""
    out = []
    for arch in ARCH_IDS:
        cfg = get(arch)
        for shape in SHAPES.values():
            skipped = shape.name == "long_500k" and not cfg.subquadratic
            if skipped and not include_skipped:
                continue
            out.append((arch, shape.name, skipped))
    return out
