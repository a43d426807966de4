"""Architecture registry: ``--arch <id>`` resolution for every launcher."""
from __future__ import annotations

from typing import Callable, Dict, List

from repro.configs import (
    command_r_35b,
    dbrx_132b,
    gemma3_4b,
    internvl2_2b,
    mamba2_1_3b,
    moonlight_16b_a3b,
    phi4_mini_3_8b,
    qwen2_1_5b,
    recurrentgemma_2b,
    whisper_tiny,
)
from repro.configs.base import ModelConfig, SHAPES, ShapeConfig

_FACTORIES: Dict[str, Callable[[], ModelConfig]] = {
    "recurrentgemma-2b": recurrentgemma_2b.config,
    "mamba2-1.3b": mamba2_1_3b.config,
    "qwen2-1.5b": qwen2_1_5b.config,
    "phi4-mini-3.8b": phi4_mini_3_8b.config,
    "command-r-35b": command_r_35b.config,
    "gemma3-4b": gemma3_4b.config,
    "whisper-tiny": whisper_tiny.config,
    "dbrx-132b": dbrx_132b.config,
    "moonlight-16b-a3b": moonlight_16b_a3b.config,
    "internvl2-2b": internvl2_2b.config,
}

ARCH_IDS: List[str] = list(_FACTORIES)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _FACTORIES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    return _FACTORIES[arch_id]()


def applicable_shapes(cfg: ModelConfig) -> List[ShapeConfig]:
    """The dry-run grid for one arch.

    long_500k requires sub-quadratic context handling — skipped for pure
    full-attention archs (see DESIGN.md §long_500k skip list).
    """
    out = []
    for shape in SHAPES.values():
        if shape.name == "long_500k" and not cfg.sub_quadratic:
            continue
        out.append(shape)
    return out


def dryrun_cells() -> List[tuple]:
    cells = []
    for arch_id in ARCH_IDS:
        cfg = get_config(arch_id)
        for shape in applicable_shapes(cfg):
            cells.append((arch_id, shape.name))
    return cells
