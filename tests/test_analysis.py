"""repro.analysis — rule true/false positives, suppression, baseline
lifecycle, the PAL002 dynamic cost-plan cross-check, and the CLI.

Fixture sources are analyzed in-memory via ``analyze_source`` with a
fake repo-relative path (path scoping is part of the contract: DET001
and HOT001 only fire in replay-/host-critical trees).
"""
import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (analyze_source, apply_baseline, load_baseline,
                            repo_root, run_analysis, write_baseline)
from repro.analysis.baseline import BASELINE_NAME

SERVE = "src/repro/serve/mod.py"
KERN = "src/repro/kernels/mod.py"


def lint(src, rel=SERVE, only=None, config=None):
    return analyze_source(src, rel, only=only, config=config)


def rules_of(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# DET001 — unseeded nondeterminism
# ---------------------------------------------------------------------------

DET_TP = """
import random
import time
import numpy as np

def pick(xs):
    if np.random.rand() > 0.5:          # hidden global numpy state
        return random.choice(xs)        # hidden global stdlib state
    return time.time()                  # wall clock
"""


def test_det001_flags_unseeded_and_clocks():
    found = lint(DET_TP, only=["DET001"])
    assert len(found) == 3
    assert all(f.rule == "DET001" for f in found)
    assert found[0].symbol == "pick"


def test_det001_allows_seeded_and_jax_random():
    src = """
import random
import numpy as np
import jax

def pick(xs, key):
    rng = np.random.default_rng(0)
    st = np.random.RandomState(1234)
    r = random.Random(7)
    k = jax.random.split(key)
    return rng.integers(3), st.rand(), r.random(), k
"""
    assert lint(src, only=["DET001"]) == []


def test_det001_scoped_to_replay_critical_trees():
    assert lint(DET_TP, rel="src/repro/train/mod.py", only=["DET001"]) == []
    assert lint(DET_TP, rel="tests/test_mod.py", only=["DET001"]) == []
    assert lint(DET_TP, rel="src/repro/core/mod.py", only=["DET001"]) != []


def test_det001_ignores_local_names_shadowing_modules():
    src = """
def draw(random):
    return random.random()              # parameter, not the module
"""
    assert lint(src, only=["DET001"]) == []


# ---------------------------------------------------------------------------
# JIT001 — donated buffer read before rebinding
# ---------------------------------------------------------------------------

def test_jit001_direct_kwarg_read_after_donation():
    src = """
import jax

class Engine:
    def setup(self, f):
        self._step = jax.jit(f, donate_argnums=(1,))

    def tick(self):
        logits = self._step(self.params, self.caches)
        return logits, self.caches      # caches donated, never rebound
"""
    found = lint(src, only=["JIT001"])
    assert len(found) == 1
    assert "self.caches" in found[0].message


def test_jit001_conditional_dn_dict_counts_as_donating():
    src = """
import jax

class Engine:
    def setup(self, f, donate):
        dn = dict(donate_argnums=(1, 2)) if donate else {}
        self._step = jax.jit(f, **dn)

    def tick(self):
        logits = self._step(self.params, self.caches, self.seen)
        x = self.seen.sum()             # donated at position 2
        return logits, x
"""
    found = lint(src, only=["JIT001"])
    assert len(found) == 1
    assert "self.seen" in found[0].message


def test_jit001_same_statement_rebind_is_clean():
    src = """
import jax

class Engine:
    def setup(self, f):
        self._step = jax.jit(f, donate_argnums=(1,))

    def tick(self):
        logits, self.caches = self._step(self.params, self.caches)
        return logits, self.caches      # rebound: alive again
"""
    assert lint(src, only=["JIT001"]) == []


def test_jit001_loop_carried_donation():
    src = """
import jax

class Engine:
    def setup(self, f):
        self._step = jax.jit(f, donate_argnums=(1,))

    def run(self, n):
        for _ in range(n):
            tokens = self.caches.tokens    # stale on iteration 2+
            _ = self._step(self.params, self.caches)
"""
    found = lint(src, only=["JIT001"])
    # both the attribute read AND the re-donation of the dead buffer
    # into the next call are loop-carried hazards
    assert len(found) == 2
    assert all("self.caches" in f.message for f in found)
    assert {f.line for f in found} == {10, 11}


def test_jit001_branch_donation_unions():
    src = """
import jax

class Engine:
    def setup(self, f):
        self._step = jax.jit(f, donate_argnums=(1,))

    def tick(self, fast):
        if fast:
            out = self._step(self.params, self.caches)
        else:
            out = None
        return self.caches              # dead on the fast path
"""
    assert len(lint(src, only=["JIT001"])) == 1


def test_jit001_partial_decorator():
    src = """
import jax
from functools import partial

@partial(jax.jit, donate_argnums=(0,))
def step(caches, tokens):
    return caches

def drive(caches, tokens):
    out = step(caches, tokens)
    return caches.mean()                # donated into step()
"""
    assert len(lint(src, only=["JIT001"])) == 1


# ---------------------------------------------------------------------------
# HOT001 — per-element dispatch in host loops
# ---------------------------------------------------------------------------

def test_hot001_jnp_and_at_update_in_loop():
    src = """
import jax.numpy as jnp

def admit(reqs, table):
    for i, r in enumerate(reqs):
        x = jnp.asarray(r.tokens)       # one dispatch per request
        table = table.at[i].set(x)      # one full copy per request
    return table
"""
    found = lint(src, only=["HOT001"])
    assert len(found) == 2
    assert all(f.rule == "HOT001" for f in found)


def test_hot001_batched_outside_loop_is_clean():
    src = """
import numpy as np
import jax.numpy as jnp

def admit(reqs):
    buf = np.zeros((len(reqs), 8), np.int32)
    for i, r in enumerate(reqs):
        buf[i] = r.tokens               # numpy in the loop: fine
    return jnp.asarray(buf)             # one conversion per tick
"""
    assert lint(src, only=["HOT001"]) == []


def test_hot001_only_in_serve_tree():
    src = """
import jax.numpy as jnp

def body(xs):
    for x in xs:                        # traced/unrolled code: fine
        xs = jnp.sin(xs)
    return xs
"""
    assert lint(src, rel="src/repro/models/mod.py", only=["HOT001"]) == []
    assert lint(src, rel=SERVE, only=["HOT001"]) != []


# ---------------------------------------------------------------------------
# ALLOC001 — free() return ignored
# ---------------------------------------------------------------------------

ALLOC_SRC = """
from repro.serve.engine import BlockAllocator

def release(a, blocks):
    a.free(blocks){suffix}
"""


def test_alloc001_bare_free_statement():
    found = lint(ALLOC_SRC.format(suffix=""), only=["ALLOC001"])
    assert len(found) == 1
    assert "physically-freed" in found[0].message


def test_alloc001_consumed_return_is_clean():
    src = """
from repro.serve.engine import BlockAllocator

def release(a, blocks, pool):
    for b in a.free(blocks):
        pool[b] = 0
"""
    assert lint(src, only=["ALLOC001"]) == []


def test_alloc001_requires_block_allocator_in_module():
    src = """
def close(handle):
    handle.free()                       # unrelated free() API
"""
    assert lint(src, only=["ALLOC001"]) == []


# ---------------------------------------------------------------------------
# PAL001 — grid/BlockSpec consistency
# ---------------------------------------------------------------------------

def test_pal001_index_map_arity_mismatch():
    src = """
import jax.experimental.pallas as pl

def run(x, kernel):
    return pl.pallas_call(
        kernel,
        grid=(4, 4),
        in_specs=[pl.BlockSpec((1, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 8), lambda i, j: (i, 0)),
    )(x)
"""
    found = lint(src, rel=KERN, only=["PAL001"])
    assert len(found) == 1
    assert "takes 1 arg(s)" in found[0].message


def test_pal001_scalar_prefetch_extends_arity():
    src = """
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

def run(x, kernel):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(4, 4),
        in_specs=[pl.BlockSpec((1, 8), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((1, 8), lambda i, j, tab, qp: (i, 0)),
    )
    return pl.pallas_call(kernel, grid_spec=grid_spec)(x)
"""
    found = lint(src, rel=KERN, only=["PAL001"])
    # in_spec lambda has 2 args but grid rank 2 + 2 prefetch refs = 4
    assert len(found) == 1
    assert "2 scalar-prefetch" in found[0].message


def test_pal001_block_rank_vs_index_coords():
    src = """
import jax.experimental.pallas as pl

def run(x, kernel):
    return pl.pallas_call(
        kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 8, 16), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 8, 16), lambda i: (i, 0, 0)),
    )(x)
"""
    found = lint(src, rel=KERN, only=["PAL001"])
    assert len(found) == 1
    assert "returns 2 coordinate(s)" in found[0].message


def test_pal001_named_local_index_fn_resolved():
    src = """
import jax.experimental.pallas as pl

def run(x, kernel, Hq):
    def kv_index(bh, iq):               # arity 2 vs grid rank 3
        return (bh // Hq, iq, 0)
    return pl.pallas_call(
        kernel,
        grid=(2, 4, 4),
        in_specs=[pl.BlockSpec((1, 8, 8), kv_index)],
        out_specs=pl.BlockSpec((1, 8, 8), lambda b, i, k: (b, i, 0)),
    )(x)
"""
    found = lint(src, rel=KERN, only=["PAL001"])
    assert len(found) == 1 and "takes 2 arg(s)" in found[0].message


def test_pal001_vmem_budget():
    src = """
import jax.experimental.pallas as pl

def run(x, kernel):
    return pl.pallas_call(
        kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((4096, 4096), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((4096, 4096), lambda i: (i, 0)),
    )(x)
"""
    found = lint(src, rel=KERN, only=["PAL001"])
    assert len(found) == 1 and "VMEM budget" in found[0].message
    # raising the budget clears it without touching the code
    assert lint(src, rel=KERN, only=["PAL001"],
                config={"vmem_budget": 256 * 1024 * 1024}) == []


def test_pal001_consistent_site_is_clean():
    src = """
import jax.experimental.pallas as pl

def run(x, kernel, n):
    return pl.pallas_call(
        kernel,
        grid=(n, 4),
        in_specs=[pl.BlockSpec((1, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, 128), lambda i, j: (i, j)),
    )(x)
"""
    assert lint(src, rel=KERN, only=["PAL001"]) == []


def test_pal001_dynamic_specs_are_skipped():
    # specs built elsewhere and passed through a name: not statically
    # visible, must not false-positive
    src = """
import jax.experimental.pallas as pl

def run(x, kernel, specs, out_spec):
    return pl.pallas_call(
        kernel, grid=(4, 4), in_specs=specs, out_specs=out_spec)(x)
"""
    assert lint(src, rel=KERN, only=["PAL001"]) == []


# ---------------------------------------------------------------------------
# PAL002 — cost_estimate provenance (static half)
# ---------------------------------------------------------------------------

PAL2_TP = """
import jax.experimental.pallas as pl

def plan(n):
    specs = [pl.BlockSpec((1, 8), lambda i, j: (i, j))]
    return specs, pl.BlockSpec((1, 8), lambda i, j: (i, j)), n * 64

def run(x, kernel, n):
    in_specs, out_spec, _ = plan(n)
    cost = pl.CostEstimate(flops=1, transcendentals=0, bytes_accessed=999)
    return pl.pallas_call(
        kernel, grid=(n, 4), in_specs=in_specs, out_specs=out_spec,
        cost_estimate=cost)(x)
"""


def test_pal002_literal_cost_next_to_plan_specs():
    found = lint(PAL2_TP, rel=KERN, only=["PAL002"])
    assert len(found) == 1
    assert "`plan(...)`" in found[0].message


def test_pal002_cost_derived_from_plan_is_clean():
    src = PAL2_TP.replace(
        "cost = pl.CostEstimate(flops=1, transcendentals=0, "
        "bytes_accessed=999)",
        "cost = make_cost(n)") + """

def make_cost(n):
    _, _, byt = plan(n)
    return pl.CostEstimate(flops=1, transcendentals=0, bytes_accessed=byt)
"""
    assert lint(src, rel=KERN, only=["PAL002"]) == []


def test_pal002_real_kernel_clean_and_drift_caught():
    """The shipped paged_attention derives its cost from _spec_plan; a
    literal cost spliced into the same source must trip PAL002."""
    path = repo_root() / "src/repro/kernels/paged_attention.py"
    src = path.read_text()
    rel = "src/repro/kernels/paged_attention.py"
    assert lint(src, rel=rel, only=["PAL002"]) == []

    munged = re.sub(
        r"cost = paged_attention_cost\(.*?interpret=interpret\)",
        "cost = pl.CostEstimate(flops=1, transcendentals=0, "
        "bytes_accessed=12345)",
        src, count=1, flags=re.S)
    assert munged != src, "fixture out of date: cost call not found"
    assert rules_of(lint(munged, rel=rel, only=["PAL002"])) == ["PAL002"]


# ---------------------------------------------------------------------------
# PAL002 — dynamic cross-check: simulate the DMA schedule the kernel
# executes and compare against the advertised CostEstimate
# ---------------------------------------------------------------------------

def test_paged_attention_cost_matches_simulated_dma_schedule(monkeypatch):
    """Walk the real grid over the specs actually handed to pallas_call:
    a BlockSpec'd operand is fetched whenever its index_map output
    changes between consecutive grid steps; a pool left in HBM is copied
    by hand, one page row block of the operand per walked table column,
    and the cost states the walk's worst case, every column of every
    slot.  The summed bytes must equal paged_attention_cost's
    bytes_accessed."""
    import jax.numpy as jnp

    from repro.kernels import paged_attention as pa

    B, Hq, Hkv, page, n_cols, D = 2, 4, 2, 8, 3, 16
    N = B * n_cols                       # fully allocated, all distinct
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, 1, Hq, D), jnp.float32)
    k = jnp.asarray(rng.randn(N, page, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(N, page, Hkv, D), jnp.float32)
    table = jnp.arange(N, dtype=jnp.int32).reshape(B, n_cols)
    pos = jnp.broadcast_to(
        jnp.arange(page, dtype=jnp.int32)[None], (N, page))
    pos = (pos + jnp.arange(N, dtype=jnp.int32)[:, None] * page) % (
        page * n_cols)
    q_pos = jnp.full((B, 1), page * n_cols - 1, jnp.int32)

    captured = {}
    real_call = pa.pl.pallas_call

    def spy(kernel, **kw):
        captured.update(kw)
        inner = real_call(kernel, **kw)

        def runner(*operands):
            captured["operands"] = operands
            return inner(*operands)
        return runner

    monkeypatch.setattr(pa.pl, "pallas_call", spy)
    pa.paged_attention_fwd(q, k, v, pos, table, q_pos, interpret=True)

    gs = captured["grid_spec"]
    cost = captured["cost_estimate"]
    nsp = gs.num_scalar_prefetch
    prefetch = captured["operands"][:nsp]
    arrays = captured["operands"][nsp:]
    out_specs = gs.out_specs
    if not isinstance(out_specs, (list, tuple)):
        out_specs = [out_specs]
    out_isz = np.dtype(captured["out_shape"].dtype).itemsize
    assert len(gs.in_specs) == len(arrays)

    # scalar-prefetch operands live in SMEM and are read once, whole
    simulated = sum(int(np.asarray(p).size) * np.dtype(p.dtype).itemsize
                    for p in prefetch)
    n_tab_b, n_tab_cols = prefetch[0].shape          # the block table
    blocked = [(s, np.dtype(a.dtype).itemsize)
               for s, a in zip(gs.in_specs, arrays)
               if s.block_shape is not None]
    blocked += [(s, out_isz) for s in out_specs]
    pools = [a for s, a in zip(gs.in_specs, arrays)
             if s.block_shape is None]
    assert len(pools) == 2                           # K and V, in HBM

    (g0,) = gs.grid                                  # one step per slot
    for spec, isz in blocked:
        fetches, prev = 0, None
        for b in range(g0):
            idx = tuple(int(x) for x in spec.index_map(b, *prefetch))
            if idx != prev:
                fetches += 1
                prev = idx
        simulated += fetches * int(np.prod(spec.block_shape)) * isz
    for pool in pools:                               # (N, page*Hkv, D)
        row_block = int(np.prod(pool.shape[1:])) * pool.dtype.itemsize
        simulated += n_tab_b * n_tab_cols * row_block

    assert simulated == cost.bytes_accessed, (
        "advertised DMA bytes drifted from the kernel's plan: "
        f"simulated {simulated} vs CostEstimate {cost.bytes_accessed}")


def test_engine_decode_pages_walked_counts_live_columns():
    """``stats["decode_pages_walked"]`` is the sum over decode ticks of
    each slot's live table columns, ceil((position + 1) / page) for an
    active slot and 0 for an idle one, recounted here from the decode
    steps' own position operands; ``decode_pages_tabled`` is slots x
    table width per tick."""
    import dataclasses

    import jax

    from repro.configs import get_smoke_config
    from repro.models.transformer import init_params
    from repro.serve.engine import Request, ServingEngine

    cfg = dataclasses.replace(get_smoke_config("gpt3-24l"), vocab_size=64,
                              d_model=64, d_ff=128, n_heads=4,
                              n_kv_heads=2, head_dim=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    page, slots = 8, 3
    eng = ServingEngine(params, cfg, slots=slots, cache_len=48, chunk=8,
                        paged=True, page_size=page, use_kernel=True)
    seen = []
    real_step = eng._call_step

    def spy(toks, pos, decode):
        if decode:
            seen.append(np.asarray(pos)[:, 0].copy())
        return real_step(toks, pos, decode)

    eng._call_step = spy
    for i, n in enumerate([3, 9, 16, 5]):
        eng.submit(Request(i, list(range(1, n + 1)), max_new=6))
    eng.run()
    assert seen and eng.stats["decode_calls"] == len(seen)
    walked = sum(-(-(int(p) + 1) // page) for tick in seen for p in tick
                 if p >= 0)
    assert eng.stats["decode_pages_walked"] == walked
    assert eng.stats["decode_pages_tabled"] == len(seen) * slots * eng.n_cols
    assert 0 < walked < eng.stats["decode_pages_tabled"]


# ---------------------------------------------------------------------------
# suppression + baseline machinery
# ---------------------------------------------------------------------------

def test_inline_suppression_by_rule_and_all():
    base = """
import time

def stamp():
    return time.time(){comment}
"""
    assert len(lint(base.format(comment=""), only=["DET001"])) == 1
    assert lint(base.format(
        comment="  # repro-lint: disable=DET001"), only=["DET001"]) == []
    assert lint(base.format(
        comment="  # repro-lint: disable=all"), only=["DET001"]) == []
    # unrelated rule name does not suppress
    assert len(lint(base.format(
        comment="  # repro-lint: disable=HOT001"), only=["DET001"])) == 1


def test_baseline_grandfathers_by_key_and_count(tmp_path):
    found = lint(DET_TP, only=["DET001"])
    assert len(found) == 3
    bl_path = tmp_path / BASELINE_NAME
    bl = write_baseline(bl_path, found)
    assert len(bl.entries) == 1 and bl.entries[0].count == 3
    assert bl.entries[0].justification.startswith("TODO")

    # same findings: all grandfathered, nothing stale
    new, old, stale = apply_baseline(found, load_baseline(bl_path))
    assert (len(new), len(old), len(stale)) == (0, 3, 0)

    # a FOURTH violation at the same key is new, not grandfathered
    extra = lint(DET_TP + "\n\ndef more():\n    return time.time()\n",
                 only=["DET001"])
    new, old, stale = apply_baseline(extra, load_baseline(bl_path))
    assert (len(new), len(old)) == (1, 3)


def test_baseline_stale_entries_expire(tmp_path):
    found = lint(DET_TP, only=["DET001"])
    bl_path = tmp_path / BASELINE_NAME
    write_baseline(bl_path, found)

    # violations fixed -> every entry is stale; rewrite drops them but
    # keeps the justification of entries that still match
    new, old, stale = apply_baseline([], load_baseline(bl_path))
    assert len(stale) == 1
    rewritten = write_baseline(bl_path, [], load_baseline(bl_path))
    assert rewritten.entries == []


def test_missing_baseline_is_empty():
    assert load_baseline(Path("/nonexistent/baseline.json")).entries == []


# ---------------------------------------------------------------------------
# e2e: the shipped tree is clean under the checked-in baseline
# ---------------------------------------------------------------------------

def test_repo_clean_under_checked_in_baseline():
    root = repo_root()
    report = run_analysis(root)
    assert report.parse_errors == []
    assert report.files_scanned > 50
    baseline = load_baseline(root / BASELINE_NAME)
    new, _, stale = apply_baseline(report.findings, baseline)
    assert new == [], "\n".join(f.format() for f in new)
    assert stale == [], [e.key for e in stale]
    # the baseline file itself carries real justifications
    assert all(not e.justification.startswith("TODO")
               for e in baseline.entries)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _cli(*args, cwd=None):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=cwd or str(repo_root()),
        env={"PYTHONPATH": str(repo_root() / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"})


def test_cli_strict_exits_zero_on_shipped_tree():
    proc = _cli("--strict")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 new finding(s)" in proc.stdout


def test_cli_unknown_rule_is_usage_error():
    proc = _cli("--only", "NOPE999")
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr


def _fixture_root(tmp_path):
    pkg = tmp_path / "src" / "repro" / "serve"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\n\ndef stamp():\n    return time.time()\n")
    return tmp_path


def test_cli_json_report_and_only_filter(tmp_path):
    root = _fixture_root(tmp_path)
    out = tmp_path / "report.json"
    proc = _cli("--root", str(root), "--only", "DET001,HOT001",
                "--format", "json", "--output", str(out))
    assert proc.returncode == 1          # one new finding
    blob = json.loads(out.read_text())
    assert blob["summary"]["new"] == 1
    assert blob["summary"]["by_rule"] == {"DET001": 1}
    assert blob["findings"][0]["status"] == "new"
    assert "DET001" in blob["rules"] and "HOT001" in blob["rules"]


def test_cli_baseline_write_then_strict_then_expiry(tmp_path):
    root = _fixture_root(tmp_path)
    bad = root / "src" / "repro" / "serve" / "bad.py"

    assert _cli("--root", str(root)).returncode == 1
    proc = _cli("--root", str(root), "--write-baseline")
    assert proc.returncode == 0 and "1 baseline entry" in proc.stdout
    # grandfathered now (TODO justification is a review-time concern)
    assert _cli("--root", str(root), "--strict").returncode == 0

    # fix the violation: non-strict passes, strict refuses stale entries
    bad.write_text("def stamp(tick):\n    return tick\n")
    assert _cli("--root", str(root)).returncode == 0
    proc = _cli("--root", str(root), "--strict")
    assert proc.returncode == 1
    assert "stale" in proc.stdout


def test_cli_list_rules_names_all_shipped_rules():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rid in ("DET001", "JIT001", "PAL001", "PAL002", "HOT001",
                "ALLOC001"):
        assert rid in proc.stdout
