"""Architecture registry: ``get_config(arch_id)`` / ``get_smoke_config``."""
from __future__ import annotations

from repro.configs import (
    arctic_480b,
    chatglm3_6b,
    dbrx_132b,
    hymba_1_5b,
    internvl2_2b,
    moonlight_16b_a3b,
    phi3_medium_14b,
    qwen2_5_14b,
    resnet50,
    seamless_m4t_medium,
    stablelm_1_6b,
    xlstm_350m,
)
from repro.configs.base import SHAPES, ModelConfig, MoEConfig, ShapeSpec, input_specs

_MODULES = {
    "chatglm3-6b": chatglm3_6b,
    "arctic-480b": arctic_480b,
    "dbrx-132b": dbrx_132b,
    "internvl2-2b": internvl2_2b,
    "qwen2.5-14b": qwen2_5_14b,
    "stablelm-1.6b": stablelm_1_6b,
    "seamless-m4t-medium": seamless_m4t_medium,
    "hymba-1.5b": hymba_1_5b,
    "phi3-medium-14b": phi3_medium_14b,
    "xlstm-350m": xlstm_350m,
    "moonlight-16b-a3b": moonlight_16b_a3b,
    "resnet50": resnet50,
}

ASSIGNED_ARCHS = tuple(k for k in _MODULES if k != "resnet50")
ALL_ARCHS = tuple(_MODULES)


def get_config(arch: str) -> ModelConfig:
    return _MODULES[arch].CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _MODULES[arch].smoke_config()


__all__ = [
    "ASSIGNED_ARCHS",
    "ALL_ARCHS",
    "SHAPES",
    "ModelConfig",
    "MoEConfig",
    "ShapeSpec",
    "get_config",
    "get_smoke_config",
    "input_specs",
]
