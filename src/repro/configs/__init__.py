"""Architecture config registry.

``get_config("yi-34b")`` returns the exact assigned full config;
``get_smoke_config("yi-34b")`` returns the reduced same-family variant.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig, MoEConfig, SSMConfig

# arch id -> module name
_ARCH_MODULES = {
    "olmo-1b": "olmo_1b",
    "granite-8b": "granite_8b",
    "zamba2-2.7b": "zamba2_2_7b",
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "yi-34b": "yi_34b",
    "mamba2-1.3b": "mamba2_1_3b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "whisper-base": "whisper_base",
    "internvl2-2b": "internvl2_2b",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
    # the paper's own evaluation models (used by the simulator / perf model)
    "llama-8b": "llama_8b",
    "llama-70b": "llama_70b",
}

ASSIGNED_ARCHS: List[str] = [
    "olmo-1b", "granite-8b", "zamba2-2.7b", "phi3-mini-3.8b", "yi-34b",
    "mamba2-1.3b", "qwen2-moe-a2.7b", "deepseek-moe-16b", "whisper-base",
    "internvl2-2b",
]


def _module(arch: str):
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).smoke_config()


def list_archs() -> List[str]:
    return list(ASSIGNED_ARCHS)


__all__ = [
    "ModelConfig", "MoEConfig", "SSMConfig", "InputShape", "INPUT_SHAPES",
    "ASSIGNED_ARCHS", "get_config", "get_smoke_config", "list_archs",
]
