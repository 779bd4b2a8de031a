"""Architecture registry: ``--arch <id>`` resolves here.

The ten architectures at their published sizes, and a smoke-sized
config of each for CPU tests.
"""

from __future__ import annotations

from typing import List

from . import (arctic_480b, gemma2_9b, gemma3_12b, internvl2_2b, mamba2_1_3b,
               mixtral_8x22b, qwen2_5_32b, recurrentgemma_9b,
               seamless_m4t_medium, stablelm_1_6b)
from .base import ModelConfig

_MODULES = {
    "qwen2.5-32b": qwen2_5_32b,
    "stablelm-1.6b": stablelm_1_6b,
    "gemma3-12b": gemma3_12b,
    "gemma2-9b": gemma2_9b,
    "arctic-480b": arctic_480b,
    "mixtral-8x22b": mixtral_8x22b,
    "seamless-m4t-medium": seamless_m4t_medium,
    "recurrentgemma-9b": recurrentgemma_9b,
    "mamba2-1.3b": mamba2_1_3b,
    "internvl2-2b": internvl2_2b,
}

ARCHS: List[str] = list(_MODULES)


def get_config(arch: str) -> ModelConfig:
    try:
        return _MODULES[arch].CONFIG
    except KeyError:
        raise KeyError(f"unknown arch {arch!r}; choose from {ARCHS}") from None


def get_smoke_config(arch: str) -> ModelConfig:
    return _MODULES[arch].smoke_config()


__all__ = ["ARCHS", "ModelConfig", "get_config", "get_smoke_config"]
