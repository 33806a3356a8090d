"""Architecture registry.

``get_config(name, smoke=False)`` returns the named architecture's
published ``ModelConfig`` (or its reduced smoke config), validated.
"""
from __future__ import annotations

import importlib

from ..models.common import ModelConfig

ARCH_MODULES = {
    "hubert-xlarge": "hubert_xlarge",
    "zamba2-2.7b": "zamba2_2p7b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "arctic-480b": "arctic_480b",
    "stablelm-3b": "stablelm_3b",
    "h2o-danube-1.8b": "h2o_danube_1p8b",
    "h2o-danube-3-4b": "h2o_danube3_4b",
    "qwen3-4b": "qwen3_4b",
    "xlstm-125m": "xlstm_125m",
    "llama-3.2-vision-11b": "llama32_vision_11b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}

ARCHS = tuple(ARCH_MODULES)


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(f".{ARCH_MODULES[name]}", __package__)
    return (mod.SMOKE if smoke else mod.CONFIG).validate()
