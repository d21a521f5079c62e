"""Architecture registry: --arch <id> lookup over the assigned pool + the
paper-native diffusion configs. Each config file cites its source."""

from __future__ import annotations

import importlib

from .base import INPUT_SHAPES, InputShape, ModelConfig

ARCH_IDS = [
    "zamba2-7b", "mixtral-8x7b", "qwen2-0.5b", "olmo-1b", "whisper-small",
    "qwen2.5-3b", "granite-moe-3b-a800m", "llama-3.2-vision-90b",
    "deepseek-67b", "mamba2-780m",
    # paper-native diffusion backbones (beyond the assigned pool)
    "dit-i256", "dit-cifar", "pixart-xl2-512",
]

_MODULES = {a: a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.config()


def get_shape(name: str) -> InputShape:
    return INPUT_SHAPES[name]


def all_arch_ids(include_paper_native: bool = False):
    return ARCH_IDS if include_paper_native else ARCH_IDS[:10]
