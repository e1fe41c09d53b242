"""Architecture registry: ``--arch <id>`` resolves here.

Each module defines ``CONFIG`` (the exact assigned configuration) and
``smoke_config()`` (a reduced same-family variant for CPU smoke tests).
"""
from __future__ import annotations

import importlib

ARCH_IDS = (
    "granite_3_2b",
    "minitron_4b",
    "gemma3_27b",
    "deepseek_67b",
    "llava_next_34b",
    "seamless_m4t_medium",
    "deepseek_v3_671b",
    "olmoe_1b_7b",
    "mamba2_780m",
    "recurrentgemma_9b",
    "granite_4_0_h_micro",
)

# canonical dashed ids (CLI spelling) -> module names
ALIASES = {i.replace("_", "-"): i for i in ARCH_IDS}


def get_config(arch: str):
    mod = importlib.import_module(f"repro.configs.{ALIASES.get(arch, arch)}")
    return mod.CONFIG


def get_smoke_config(arch: str):
    mod = importlib.import_module(f"repro.configs.{ALIASES.get(arch, arch)}")
    return mod.smoke_config()


def all_configs():
    return {a: get_config(a) for a in ARCH_IDS}
