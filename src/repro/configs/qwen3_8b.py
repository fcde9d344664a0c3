"""Qwen3-8B — dense decoder, GQA kv=8, per-head qk-norm.
[hf:Qwen/Qwen3-8B]
"""
import dataclasses

from repro.models import config as _mc
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-8b",
    arch_type="dense",
    n_layers=36,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=12288,
    vocab_size=151936,
    qk_norm=True,
    rope_theta=1_000_000.0,
    source="hf:Qwen/Qwen3-8B",
)

SMOKE = _mc.reduced(CONFIG, n_layers=2, period=CONFIG.period * 2)

# Chip-share preset: what one TPU v5e (16 GB) holds when the model is
# served as a two-stage pipeline — the paper's split of layers into
# stages over devices — so each chip holds 18 of the 36 layers.  Every
# published width is kept.  This chip also holds the embedding and the
# output head (one per stage in the deployment), so it serves tokens end
# to end: ~4.7B parameters, ~9.4 GB in bf16; KV is ~72 KiB per token
# over the 18 layers.
deployment = ("2-stage pipeline over 2 TPU v5e chips, 18 layers per "
              "chip; embedding and head both held here")
reduced = {"n_layers": "36 -> 18"}
CHIP = dataclasses.replace(CONFIG, name="qwen3-8b-chip", n_layers=18)
