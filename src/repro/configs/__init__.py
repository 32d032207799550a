"""Registered architecture configs + shapes."""

import importlib

from .base import (
    ArchConfig,
    MoEConfig,
    RWKVConfig,
    SSMConfig,
    ShapeConfig,
    SHAPES,
    all_archs,
    cells,
    get_arch,
)

_MODULES = [
    "nemotron_4_340b",
    "granite_3_8b",
    "command_r_35b",
    "qwen1_5_110b",
    "musicgen_large",
    "internvl2_1b",
    "rwkv6_3b",
    "zamba2_2_7b",
    "zamba2_7b",
    "mixtral_8x7b",
    "phi3_5_moe_42b",
    "llama3_70b",
]

_loaded = False


def _load_all() -> None:
    global _loaded
    if _loaded:
        return
    _loaded = True
    for m in _MODULES:
        importlib.import_module(f"repro.configs.{m}")
