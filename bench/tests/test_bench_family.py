"""The family interface.  Every configuration names a module of
``bench/reference`` that exports what the harness calls; the loader
refuses a file or a module that falls short, naming what is missing; the
dense family builds the system's config as the harness always has; the
harness names no family; and a family added as a file alone runs a whole
cell, its check, its work counts and its log line."""
import ast
import dataclasses
import hashlib
import importlib
import sys
import time

import jax
import pytest

import bench.reference
from bench.harness import spec
from bench.harness.cell import run_cell
from bench.tests import tiny

# what the shared harness is made of: it may name no family
HARNESS = [*sorted((spec.BENCH / "harness").glob("*.py")),
           *sorted((spec.BENCH / "metrics").glob("*.py")),
           spec.BENCH / "run.py", spec.BENCH / "sweep.py"]
DENSE_KEYS = ("qwen_dense", "num_key_value_heads", "attention_bias",
              "qk_norm", "intermediate_size", "head_dim")


@pytest.fixture
def plug_in(tmp_path, monkeypatch):
    """Writes a module into a reference directory of its own, outside the
    checkout, that ``bench.reference`` searches too."""
    monkeypatch.setattr(bench.reference, "__path__",
                        [*bench.reference.__path__, str(tmp_path)])
    names = []

    def write(name, source):
        (tmp_path / f"{name}.py").write_text(source)
        importlib.invalidate_caches()
        names.append(name)

    yield write
    for n in names:
        sys.modules.pop(f"bench.reference.{n}", None)


def _configs():
    return sorted(p.stem for p in (spec.BENCH / "configs").glob("*.json"))


@pytest.mark.parametrize("name", _configs())
def test_every_configuration_resolves_to_a_whole_family(name):
    cfg = spec.config(name)
    fam = spec.family(cfg)
    assert all(callable(getattr(fam, f)) for f in spec.FAMILY)
    m = fam.widths(cfg)
    assert m["vocab_size"] == cfg["vocab_size"]
    assert fam.describe(m)


@pytest.mark.parametrize("ref", [None, "", "../qwen_dense", "qwen dense", 7])
def test_a_configuration_without_a_family_is_refused(ref):
    cfg = dict(tiny.QWEN3)
    if ref is None:
        del cfg["reference"]
    else:
        cfg["reference"] = ref
    with pytest.raises(ValueError, match="key 'reference'"):
        spec.family(cfg)


@pytest.mark.parametrize("missing", spec.FAMILY)
def test_a_family_missing_a_function_is_refused(missing, plug_in):
    name = f"lacks_{missing.strip('_')}"
    plug_in(name, "from bench.reference.qwen_dense import *  # noqa\n"
                  "from bench.reference.qwen_dense import _pad  # noqa\n"
                  f"del {missing}\n")
    with pytest.raises(ValueError, match=f"lacks {missing}$"):
        spec.family(dict(tiny.QWEN3, reference=name))


@pytest.mark.parametrize("name", ["qwen3-8b-chip", "qwen1.5-32b-chip"])
def test_dense_model_config_is_the_architecture_at_the_files_widths(name):
    from repro.configs import get_config
    cfg = spec.config(name)
    want = dataclasses.replace(
        get_config(cfg["arch"]), name=cfg["name"], tie_embeddings=False,
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        qk_norm=cfg["qk_norm"], attn_bias=cfg["attention_bias"])
    got = spec.family(cfg).model_config(cfg)
    for f in dataclasses.fields(want):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
    assert got.n_kv_heads == 8


@pytest.mark.parametrize("path", HARNESS, ids=lambda p: p.name)
def test_the_harness_names_no_family(path):
    text = path.read_text()
    assert [k for k in DENSE_KEYS if k in text] == []


STUB = '''"""A family added as a file alone: the dense reference, with work
counters that return sentinels and a description of its own."""
from bench.reference import qwen_dense as _dense
from bench.reference.qwen_dense import (  # noqa: F401
    _pad, draw_weights, first_choice, gap_of, model_config, prepare_params,
    widths)

CHECKED = []


def logits_at(w, m, tokens, rows, **kw):
    CHECKED.append(len(rows))
    return _dense.logits_at(w, m, tokens, rows, **kw)


def describe(m):
    return "sentinel family of " + str(m["n_layers"]) + " layers"


def decode_step_flops(m, positions):
    return 1_000_033 if positions else 0


def prefill_flops(m, start, stop, chunk):
    return 1_000_003 * (stop - start)


def decode_attention_need(m, positions, page):
    return (1_000_037, 1_000_039) if positions else (0, 0)
'''


def _digest(root):
    h = hashlib.sha256()
    for p in sorted(root.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(root)).encode() + b"\0")
            h.update(p.read_bytes())
    return h.hexdigest()


def test_a_family_plugs_in_by_files_alone(plug_in, capsys):
    name = "sentinel_family"
    assert not any(name in p.read_text() for p in HARNESS)
    before = _digest(spec.BENCH)
    plug_in(name, STUB)
    cfg = dict(tiny.QWEN3, reference=name)
    b = tiny.bench("tiny-cell")
    out = run_cell(b, b["workloads"][0], cfg, tiny.CLOSED, seed=2 ** 31 + 7,
                   seconds=1.5, trace=False, devs=jax.devices(),
                   t_start=time.perf_counter())
    err = capsys.readouterr().err
    fam = sys.modules[f"bench.reference.{name}"]

    # the check ran through the family's reference
    assert out["correct"] is True
    assert fam.CHECKED and sum(fam.CHECKED) > 0
    # the engine log line came from its describe
    assert "bench: engine: qwen3-tiny sentinel family of 2 layers; slots=" \
        in err
    # the work counts are its counters' sentinels, summed over the window
    tag = "bench: work: "
    line = next(ln for ln in err.splitlines() if ln.startswith(tag))
    work = ast.literal_eval(line[len(tag):])
    steps = work["decode_steps"]
    assert steps > 0
    assert work["decode_flops"] == 1_000_033 * steps
    assert work["kernel_flops"] == 1_000_037 * steps
    assert work["kernel_bytes"] == 1_000_039 * steps
    assert work["prefill_tokens"] > 0
    assert work["prefill_flops"] == 1_000_003 * work["prefill_tokens"]
    # and no file of the benchmark was written to add it
    assert _digest(spec.BENCH) == before
