"""Architecture registry: the 10 assigned architectures plus the paper's
own evaluation models (BERT-Large, GPT-3 24L).

Each ``<arch>.py`` module exports ``CONFIG`` (exact assigned spec, source
cited) and ``SMOKE`` (reduced same-family variant: <=2 periods,
d_model<=512, <=4 experts, runnable on CPU).  A module may also export
``CHIP``: the published widths cut to one chip's share of a stated
deployment, with the cuts listed in the module's ``reduced``.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Dict, Tuple

from repro.models.config import ModelConfig

_ARCH_MODULES = {
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "gemma3-12b": "gemma3_12b",
    "qwen1.5-32b": "qwen1_5_32b",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "musicgen-medium": "musicgen_medium",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "rwkv6-7b": "rwkv6_7b",
    "qwen3-8b": "qwen3_8b",
    "llama3-405b": "llama3_405b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    # the paper's own estimation subjects (§4, Figs. 4-6)
    "bert-large": "bert_large",
    "gpt3-24l": "gpt3_24l",
}

ASSIGNED_ARCHS: Tuple[str, ...] = tuple(list(_ARCH_MODULES)[:10])
ALL_ARCHS: Tuple[str, ...] = tuple(_ARCH_MODULES)


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def get_config(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.CONFIG


def get_smoke_config(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.SMOKE


def get_chip_config(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    if not hasattr(mod, "CHIP"):
        raise ValueError(f"{name} has no chip-share preset (CHIP) yet")
    return mod.CHIP


def shape_applicable(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """Whether (arch, shape) is part of the baseline matrix.
    long_500k needs a sub-quadratic decode path (SSM / hybrid / SWA) —
    pure full-attention archs are skipped per the assignment carve-out."""
    if shape.name == "long_500k" and not cfg.supports_long_decode:
        return False, "pure full-attention arch: no sub-quadratic long-context path"
    return True, ""


def baseline_pairs():
    """All (arch, shape) pairs in the baseline matrix, plus skip notes."""
    pairs, skips = [], []
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in INPUT_SHAPES.values():
            ok, why = shape_applicable(cfg, shape)
            (pairs if ok else skips).append((arch, shape.name) if ok
                                            else (arch, shape.name, why))
    return pairs, skips
