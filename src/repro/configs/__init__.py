"""Architecture registry: ``get_config(arch_id)`` / ``get_smoke_config``.

Each assigned architecture lives in its own module exposing ``FULL`` (the
exact published config) and ``SMOKE`` (a reduced same-family config for
CPU tests). ``--arch`` ids use dashes; module names use underscores.
"""
from __future__ import annotations

from repro.configs.base import (  # noqa: F401
    AmbdgConfig, MeshConfig, ModelConfig, MoEConfig, RunConfig, ShapeConfig,
    SSMConfig, XLSTMConfig, SHAPES, TRAIN_4K, PREFILL_32K, DECODE_32K,
    LONG_500K, DENSE, MOE, SSM, HYBRID, ENCDEC, VLM, LINREG, CNN,
    LM_FAMILIES,
)

from repro.configs import (
    mixtral_8x7b, mixtral_8x22b, xlstm_125m, paligemma_3b, qwen1_5_0_5b,
    yi_6b, chatglm3_6b, qwen3_1_7b, zamba2_2_7b, seamless_m4t_large_v2,
    moonlight_16b_a3b, amb_linreg, amb_cnn,
)

_MODULES = {
    "mixtral-8x7b": mixtral_8x7b,
    "mixtral-8x22b": mixtral_8x22b,
    "xlstm-125m": xlstm_125m,
    "paligemma-3b": paligemma_3b,
    "qwen1.5-0.5b": qwen1_5_0_5b,
    "yi-6b": yi_6b,
    "chatglm3-6b": chatglm3_6b,
    "qwen3-1.7b": qwen3_1_7b,
    "zamba2-2.7b": zamba2_2_7b,
    "seamless-m4t-large-v2": seamless_m4t_large_v2,
    "moonlight-16b-a3b": moonlight_16b_a3b,
    # paper's own experiments
    "amb-linreg": amb_linreg,
    "amb-cnn": amb_cnn,
}

ARCH_IDS = tuple(k for k in _MODULES if not k.startswith("amb-"))
ALL_IDS = tuple(_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return _MODULES[arch].FULL


def get_smoke_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return _MODULES[arch].SMOKE


def applicable_shapes(arch: str) -> list:
    """Which of the four assigned shapes run for this arch (spec skips)."""
    cfg = get_config(arch)
    if cfg.family not in LM_FAMILIES:
        return []
    out = [TRAIN_4K, PREFILL_32K]
    if cfg.has_decoder:
        out.append(DECODE_32K)
    if cfg.is_subquadratic:
        out.append(LONG_500K)
    return out
