"""Architecture registry: ``--arch <id>`` -> exact public-literature config.

Each module defines ``config()`` (the exact assigned numbers) and
``smoke()`` (a reduced same-family config for CPU tests).
"""

from __future__ import annotations

import importlib

from repro.configs.base import (  # noqa: F401
    SHAPES,
    SMOKE_SHAPE,
    ModelConfig,
    ShapeConfig,
    applicable_shapes,
)

ARCH_IDS = [
    "zamba2-1.2b",
    "qwen3-moe-235b-a22b",
    "grok-1-314b",
    "hubert-xlarge",
    "olmo-1b",
    "codeqwen1.5-7b",
    "internlm2-1.8b",
    "deepseek-67b",
    "xlstm-350m",
    "internvl2-76b",
    "moonlight-16b-a3b",
]

#: the paper's own workload (HyperSense sensing config)
PAPER_CONFIG_ID = "hypersense"


def _module(arch_id: str):
    name = arch_id.replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{name}")


def get_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).config()


def get_smoke(arch_id: str) -> ModelConfig:
    return _module(arch_id).smoke()
