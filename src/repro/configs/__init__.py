"""Config registry: ``get_config("mixtral-8x7b")`` / ``--arch mixtral-8x7b``."""
from __future__ import annotations

from repro.configs.base import (
    AUDIO,
    DENSE,
    FAMILIES,
    HYBRID,
    INPUT_SHAPES,
    MOE,
    SSM,
    VLM,
    ModelConfig,
    ShapeConfig,
)

from repro.configs.stablelm_3b import CONFIG as _stablelm_3b
from repro.configs.mixtral_8x7b import CONFIG as _mixtral_8x7b
from repro.configs.h2o_danube_1_8b import CONFIG as _h2o_danube
from repro.configs.zamba2_1_2b import CONFIG as _zamba2
from repro.configs.rwkv6_1_6b import CONFIG as _rwkv6
from repro.configs.qwen2_vl_2b import CONFIG as _qwen2_vl
from repro.configs.granite_20b import CONFIG as _granite
from repro.configs.tinyllama_1_1b import CONFIG as _tinyllama
from repro.configs.qwen3_moe_30b_a3b import CONFIG as _qwen3_moe
from repro.configs.seamless_m4t_medium import CONFIG as _seamless
from repro.configs.protocol_125m import CONFIG as _protocol_125m
from repro.configs.deepseek_v2_lite import CONFIG as _deepseek_v2_lite

REGISTRY = {
    c.name: c
    for c in (
        _stablelm_3b,
        _mixtral_8x7b,
        _h2o_danube,
        _zamba2,
        _rwkv6,
        _qwen2_vl,
        _granite,
        _tinyllama,
        _qwen3_moe,
        _seamless,
        _protocol_125m,
        _deepseek_v2_lite,
    )
}

#: the assigned pool; protocol-125m is the paper's demonstrator and
#: deepseek-v2-lite the benchmark's latent-attention expert model
ASSIGNED_ARCHS = [n for n in REGISTRY
                  if n not in ("protocol-125m", "deepseek-v2-lite")]


def get_config(name: str) -> ModelConfig:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(REGISTRY)}") from None


def get_shape(name: str) -> ShapeConfig:
    try:
        return INPUT_SHAPES[name]
    except KeyError:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(INPUT_SHAPES)}") from None


def applicable_shapes(cfg: ModelConfig) -> list:
    """The assigned input shapes this architecture runs (DESIGN.md §3)."""
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.supports_long_decode:
        out.append("long_500k")
    return out


__all__ = [
    "ModelConfig",
    "ShapeConfig",
    "REGISTRY",
    "ASSIGNED_ARCHS",
    "INPUT_SHAPES",
    "get_config",
    "get_shape",
    "applicable_shapes",
    "DENSE",
    "MOE",
    "HYBRID",
    "SSM",
    "VLM",
    "AUDIO",
    "FAMILIES",
]
