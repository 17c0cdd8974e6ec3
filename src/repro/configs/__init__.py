"""Assigned-architecture registry: ``get(name)`` / ``--arch <id>``.

Each module defines CONFIG (the exact assigned full-scale config) and
SMOKE (a reduced same-family config for CPU tests). Shapes for the dry-run
cells live in repro.configs.shapes.
"""

from __future__ import annotations

import importlib

from repro.models.config import ModelConfig

ARCHS = (
    "llama3_8b",
    "granite_34b",
    "deepseek_7b",
    "qwen3_14b",
    "zamba2_2p7b",
    "musicgen_medium",
    "mamba2_370m",
    "deepseek_v2_236b",
    "deepseek_v2_lite",
    "mixtral_8x22b",
    "pixtral_12b",
)

_ALIASES = {name.replace("_", "-"): name for name in ARCHS}
_ALIASES.update(
    {
        "llama3-8b": "llama3_8b",
        "granite-34b": "granite_34b",
        "deepseek-7b": "deepseek_7b",
        "qwen3-14b": "qwen3_14b",
        "zamba2-2.7b": "zamba2_2p7b",
        "musicgen-medium": "musicgen_medium",
        "mamba2-370m": "mamba2_370m",
        "deepseek-v2-236b": "deepseek_v2_236b",
        "deepseek-v2-lite": "deepseek_v2_lite",
        "mixtral-8x22b": "mixtral_8x22b",
        "pixtral-12b": "pixtral_12b",
    }
)


def canonical(name: str) -> str:
    key = name.strip().lower()
    if key in ARCHS:
        return key
    if key in _ALIASES:
        return _ALIASES[key]
    raise KeyError(f"unknown arch {name!r}; known: {sorted(_ALIASES)}")


def get(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    return mod.CONFIG.validate()


def get_smoke(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    return mod.SMOKE.validate()
