"""Architecture registry: one module per assigned architecture.

``get_config(arch)`` returns the full published configuration;
``smoke_config(arch)`` returns a reduced same-family configuration small
enough for a CPU forward/train step (used by per-arch smoke tests).
``ARCHS`` lists the architectures the per-arch tests sweep; a served-only
architecture (latent attention has no contiguous cache) is registered
with its own tests instead.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

from ..models.config import ModelConfig

ARCHS: List[str] = [
    "llama3-8b",
    "qwen1.5-4b",
    "qwen1.5-0.5b",
    "minicpm-2b",
    "phi3.5-moe-42b-a6.6b",
    "kimi-k2-1t-a32b",
    "rwkv6-7b",
    "internvl2-2b",
    "zamba2-2.7b",
    "seamless-m4t-medium",
]

_MODULES: Dict[str, str] = {
    "llama3-8b": "llama3_8b",
    "qwen1.5-4b": "qwen1_5_4b",
    "qwen1.5-0.5b": "qwen1_5_0_5b",
    "minicpm-2b": "minicpm_2b",
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe",
    "kimi-k2-1t-a32b": "kimi_k2",
    "rwkv6-7b": "rwkv6_7b",
    "internvl2-2b": "internvl2_2b",
    "zamba2-2.7b": "zamba2_2_7b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}


def _module(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; available: {list(_MODULES)}")
    return importlib.import_module(f".{_MODULES[arch]}", __name__)


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def smoke_config(arch: str) -> ModelConfig:
    return _module(arch).SMOKE
