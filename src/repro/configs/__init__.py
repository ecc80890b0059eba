"""Assigned-architecture registry (``--arch <id>``) + input-shape sets.

Each module defines ``CONFIG`` (the exact public-literature configuration)
and ``SMOKE`` (a reduced same-family config for CPU tests). Sources are cited
in each file.
"""
from __future__ import annotations

import dataclasses
import importlib

from ..models.config import ModelConfig

ARCH_IDS = [
    "command_r_35b",
    "minitron_4b",
    "mistral_nemo_12b",
    "olmo_1b",
    "llama32_vision_11b",
    "olmoe_1b_7b",
    "qwen3_moe_235b",
    "jamba_v01_52b",
    "seamless_m4t_medium",
    "mamba2_130m",
    "granite_4_h_small",
    # paper's own workloads, runnable through the same stack
    "gpt3_175b",
]


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    phase: str               # 'train' | 'prefill' | 'decode'


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    arch = arch.replace("-", "_").replace(".", "")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.SMOKE if smoke else mod.CONFIG


def cells(arch: str) -> list[str]:
    """Applicable shape names for an arch (long_500k only for sub-quadratic
    families — full-attention archs skip it, per DESIGN.md §4)."""
    cfg = get_config(arch)
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.subquadratic:
        names.append("long_500k")
    return names
