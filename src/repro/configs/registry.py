"""--arch lookup: maps architecture ids to their configs."""
from __future__ import annotations

from repro.configs.base import ArchConfig, ShapeConfig, SHAPES
from repro.configs import (
    recurrentgemma_2b,
    musicgen_medium,
    qwen3_0_6b,
    granite_8b,
    qwen2_72b,
    h2o_danube_3_4b,
    mamba2_1_3b,
    moonshot_v1_16b_a3b,
    qwen2_moe_a2_7b,
    qwen2_vl_2b,
    dit_xl,
    pixart_alpha,
)

ARCHS = {
    cfg.name: cfg
    for cfg in [
        recurrentgemma_2b.CONFIG,
        musicgen_medium.CONFIG,
        qwen3_0_6b.CONFIG,
        granite_8b.CONFIG,
        qwen2_72b.CONFIG,
        h2o_danube_3_4b.CONFIG,
        mamba2_1_3b.CONFIG,
        moonshot_v1_16b_a3b.CONFIG,
        qwen2_moe_a2_7b.CONFIG,
        qwen2_vl_2b.CONFIG,
        dit_xl.CONFIG,
        pixart_alpha.CONFIG,
    ]
}

# The ten assigned LM-family architectures (the denoisers are extras).
ASSIGNED = [n for n, cfg in ARCHS.items() if not cfg.is_diffusion]


def get_arch(name: str) -> ArchConfig:
    key = name.replace("_", "-").lower()
    if key not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[key]


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; available: {sorted(SHAPES)}")
    return SHAPES[name]


def all_cells():
    """Every (arch, shape) cell, with skip reasons for inapplicable ones."""
    cells = []
    for arch_name in ASSIGNED:
        arch = ARCHS[arch_name]
        for shape in SHAPES.values():
            ok, reason = arch.supports_shape(shape)
            cells.append((arch, shape, ok, reason))
    return cells
