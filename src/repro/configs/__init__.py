"""Architecture registry: ``--arch <id>`` -> exact published config.

Also defines the four assigned input shapes and the per-(arch x shape)
applicability rules (long_500k needs sub-quadratic attention; see
DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

import dataclasses
import importlib

from repro.models.config import ModelConfig

_MODULES = {
    "qwen2-0.5b": "qwen2_0_5b",
    "minicpm-2b": "minicpm_2b",
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "stablelm-12b": "stablelm_12b",
    "rwkv6-1.6b": "rwkv6_1_6b",
    "zamba2-1.2b": "zamba2_1_2b",
    "whisper-tiny": "whisper_tiny",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    mode: str          # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

SHAPE_IDS = tuple(SHAPES)


def cell_status(cfg: ModelConfig, shape: str) -> str:
    """'run' or a documented skip reason for one (arch x shape) cell."""
    sp = SHAPES[shape]
    if sp.name == "long_500k" and not cfg.sub_quadratic:
        return "skipped_full_attention"
    return "run"


def all_cells() -> list[tuple[str, str, str]]:
    """Every (arch, shape, status) — 40 cells."""
    out = []
    for a in ARCH_IDS:
        cfg = get_config(a)
        for s in SHAPE_IDS:
            out.append((a, s, cell_status(cfg, s)))
    return out
