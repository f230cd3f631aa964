"""Config registry: the 10 assigned architectures + the paper's SLAYformer.

    cfg = configs.get_config("qwen3-32b")          # published widths
    cfg = configs.get_smoke_config("qwen3-32b")    # reduced (CPU smoke test)

Every arch runs with the paper's SLAY attention by default
(``attn_kind="slay"``); pass ``attn_kind="softmax"`` via
``dataclasses.replace`` for the quadratic baseline variant.
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import ArchConfig

_MODULES = {
    "phi4-mini-3.8b": "phi4_mini",
    "qwen3-32b": "qwen3_32b",
    "granite-20b": "granite_20b",
    "gemma2-27b": "gemma2_27b",
    "hymba-1.5b": "hymba_1_5b",
    "whisper-small": "whisper_small",
    "internvl2-76b": "internvl2_76b",
    "mamba2-780m": "mamba2_780m",
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "grok-1-314b": "grok1_314b",
    "slayformer-124m": "slayformer_124m",
}

ASSIGNED_ARCHS = tuple(k for k in _MODULES if k != "slayformer-124m")
ALL_ARCHS = tuple(_MODULES)


def _module(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[name]}")


def get_config(name: str, **overrides) -> ArchConfig:
    cfg = _module(name).CONFIG
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def get_smoke_config(name: str, **overrides) -> ArchConfig:
    cfg = _module(name).smoke_config()
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


__all__ = [
    "ArchConfig", "ASSIGNED_ARCHS", "ALL_ARCHS", "get_config",
    "get_smoke_config",
]
