"""Config registry: ``get_config("gemma2-9b")`` / ``--arch`` resolution."""
from __future__ import annotations

from repro.configs.base import ModelConfig, SHAPES, SHAPE_GRID, ShapeSpec, shape_applicable
from repro.configs import (qwen2_vl_2b, starcoder2_7b, llama3_2_3b, gemma2_9b,
                           gemma3_12b, qwen3_moe_30b_a3b, deepseek_v2_lite_16b,
                           rwkv6_7b, musicgen_medium, jamba_1_5_large_398b,
                           llama3_2_3b_1chip)
from repro.configs.paper_models import PAPER_MODELS

ASSIGNED = {
    m.CONFIG.name: m.CONFIG for m in (
        qwen2_vl_2b, starcoder2_7b, llama3_2_3b, gemma2_9b, gemma3_12b,
        qwen3_moe_30b_a3b, deepseek_v2_lite_16b, rwkv6_7b, musicgen_medium,
        jamba_1_5_large_398b)
}

# one chip's share of an assigned model: published widths, cut depth and
# vocabulary (each module lists its cuts in REDUCED)
ONE_CHIP = {m.CONFIG.name: m for m in (llama3_2_3b_1chip,)}

ALL_CONFIGS = {**ASSIGNED, **PAPER_MODELS,
               **{name: m.CONFIG for name, m in ONE_CHIP.items()}}


def get_config(name: str) -> ModelConfig:
    if name.endswith("-reduced"):
        return get_config(name[: -len("-reduced")]).reduced()
    if name not in ALL_CONFIGS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ALL_CONFIGS)}")
    return ALL_CONFIGS[name]


def dryrun_cells():
    """Every (assigned arch × applicable shape) — the 40-cell grid minus
    long_500k skips (documented in DESIGN.md §5)."""
    for name, cfg in ASSIGNED.items():
        for shape in SHAPE_GRID:
            if shape_applicable(cfg, shape):
                yield name, shape.name
