"""Reads the benchmark's data by name: ``BENCHMARK.json`` at the root of
the checkout, ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``; and the configuration's family module,
``bench/reference/<reference>.py``."""
from __future__ import annotations

import importlib
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent

# what the harness calls of a family module, ``bench/reference/<name>.py``
FAMILY = ("model_config", "widths", "describe", "prepare_params",
          "draw_weights", "logits_at", "gap_of", "first_choice", "_pad",
          "decode_step_flops", "prefill_flops", "decode_attention_need")


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


def family(cfg: dict):
    """The module of the configuration's architecture family, named by
    its ``"reference"`` key: ``bench.reference.<reference>``, which
    exports every function of ``FAMILY``."""
    name = cfg.get("reference")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"configuration {cfg.get('name')!r}: key "
                         f"'reference' must name a module of bench/reference"
                         f" (got {name!r})")
    mod = importlib.import_module(f"bench.reference.{name}")
    missing = [f for f in FAMILY if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"family module bench.reference.{name} lacks "
                         f"{', '.join(missing)}")
    return mod


def launcher_argv(cfg: dict, mix: dict, weight_seed: int) -> list:
    """The serving launcher's flags for this cell."""
    return (list(cfg["launcher"]) + list(mix["launcher"])
            + ["--seed", str(weight_seed)])
