"""Reads the benchmark's data by name: ``BENCHMARK.json`` at the root of
the checkout, ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent

# config-file key -> (ModelConfig field, reference/work width key)
WIDTHS = {
    "hidden_size": ("d_model", "d_model"),
    "num_hidden_layers": ("n_layers", "n_layers"),
    "num_attention_heads": ("n_heads", "n_heads"),
    "num_key_value_heads": ("n_kv_heads", "n_kv_heads"),
    "head_dim": ("head_dim", "head_dim"),
    "intermediate_size": ("d_ff", "d_ff"),
    "vocab_size": ("vocab_size", "vocab_size"),
    "rope_theta": ("rope_theta", "rope_theta"),
    "rms_norm_eps": ("norm_eps", "norm_eps"),
    "qk_norm": ("qk_norm", "qk_norm"),
    "attention_bias": ("attn_bias", "attn_bias"),
}


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(CHECKOUT / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload named {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def widths(cfg: dict) -> dict:
    """The dict of widths the reference and the work counters read."""
    m = {w: cfg[k] for k, (_, w) in WIDTHS.items()}
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not handled by the reference")
    return m


def model_config(cfg: dict):
    """The system's ModelConfig for this configuration file: the
    architecture's own config with every width set from the file."""
    from repro.configs import get_config
    base = get_config(cfg["arch"])
    over = {f: cfg[k] for k, (f, _) in WIDTHS.items()}
    return dataclasses.replace(base, name=cfg["name"],
                               tie_embeddings=False, **over)


def launcher_argv(cfg: dict, mix: dict, weight_seed: int) -> list:
    """The serving launcher's flags for this cell."""
    return (list(cfg["launcher"]) + list(mix["launcher"])
            + ["--seed", str(weight_seed)])
