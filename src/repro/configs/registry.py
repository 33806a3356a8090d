"""Architecture & shape registry -- the assigned (arch x shape) grid.

``get_config(name, smoke=False)`` returns the exact assigned ModelConfig;
``SHAPES`` defines the four assigned input shapes; ``cell_plan()``
enumerates every runnable (arch, shape) cell plus explicit SKIP records
with rationale (encoder-only archs have no decode; full-attention archs
skip long_500k per assignment).
"""
from __future__ import annotations

import dataclasses
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


@dataclasses.dataclass(frozen=True)
class Shape:
    name: str
    seq_len: int
    global_batch: int
    step: str          # train | prefill | decode


SHAPES = {
    "train_4k": Shape("train_4k", 4_096, 256, "train"),
    "prefill_32k": Shape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": Shape("decode_32k", 32_768, 128, "decode"),
    "long_500k": Shape("long_500k", 524_288, 1, "decode"),
}


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(f".{ARCH_MODULES[name]}", __package__)
    return (mod.SMOKE if smoke else mod.CONFIG).validate()


def skip_reason(cfg: ModelConfig, shape: Shape) -> str | None:
    if shape.step == "decode" and cfg.is_encoder:
        return "encoder-only: no autoregressive decode step"
    if shape.name == "long_500k" and not cfg.long_context_ok:
        return "full quadratic attention: long_500k assigned to " \
               "SSM/hybrid/SWA archs only"
    return None


def cell_plan() -> list[dict]:
    """Every (arch, shape) cell; runnable ones have skip=None."""
    out = []
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            out.append({"arch": arch, "shape": shape.name,
                        "skip": skip_reason(cfg, shape)})
    return out
