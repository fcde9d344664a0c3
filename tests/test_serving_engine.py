"""Chunked-prefill continuous-batching engine tests: greedy parity with
``generate()`` as the correctness oracle (mixed prompt lengths, slot
reuse, SSM + SWA cache kinds), slot-recycle hygiene for every cache kind,
admission call-count bound (ceil(S/chunk) jitted steps), and input
validation."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.transformer import init_cache, init_params
from repro.serve.engine import (Request, ServingEngine, _clear_slot, generate)


def _tiny_cfg():
    cfg = get_smoke_config("gpt3-24l")
    return dataclasses.replace(cfg, vocab_size=128, d_model=128, d_ff=256,
                               n_heads=4, n_kv_heads=4, head_dim=32)


# ---------------------------------------------------------------------------
# Greedy parity vs generate() — mixed prompt lengths, slot reuse, chunking
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["gpt3-24l", "gemma3-12b", "rwkv6-7b"])
def test_chunked_engine_matches_generate(arch):
    """Prompt lengths straddle the chunk size (1, <chunk, crossing one
    boundary, crossing two with a remainder); 4 requests over 2 slots
    forces slot reuse.  Covers full-attention KV, SWA ring and RWKV
    state caches."""
    cfg = _tiny_cfg() if arch == "gpt3-24l" else get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, slots=2, cache_len=64, chunk=4)
    prompts = [[7], [1, 2, 3], [5, 6, 7, 8, 9], [9, 8, 7, 6, 5, 4, 3, 2, 1]]
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new=4))
    done = {r.req_id: r.generated for r in eng.run()}
    assert sorted(done) == [0, 1, 2, 3]
    for i, p in enumerate(prompts):
        ref = generate(params, cfg, jnp.asarray([p], jnp.int32),
                       max_new=4)[0, len(p):].tolist()
        assert done[i] == ref, (arch, i, done[i], ref)


def test_late_arrival_joins_running_batch():
    """A request submitted mid-decode is admitted by chunked prefill into
    a shared cache that already holds other requests' live KV."""
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    eng = ServingEngine(params, cfg, slots=2, cache_len=64, chunk=4)
    eng.submit(Request(0, [1, 2, 3, 4, 5, 6], max_new=8))
    ticks = 0
    while eng.tick():
        ticks += 1
        if ticks == 2:
            eng.submit(Request(1, [9, 8, 7, 6, 5], max_new=4))
    done = {r.req_id: r.generated for r in eng.finished}
    for rid, p in [(0, [1, 2, 3, 4, 5, 6]), (1, [9, 8, 7, 6, 5])]:
        ref = generate(params, cfg, jnp.asarray([p], jnp.int32),
                       max_new=len(done[rid]))[0, len(p):].tolist()
        assert done[rid] == ref, (rid, done[rid], ref)


def test_mamba_hybrid_chunked_parity():
    """Jamba (Mamba + attention + MoE hybrid): chunked admission with an
    idle masked slot must reproduce generate() — covers the conv-history
    and SSM-state carry across chunk boundaries and the row-wise state
    restore for masked slots."""
    cfg = get_smoke_config("jamba-1.5-large-398b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, slots=2, cache_len=64, chunk=4)
    prompts = [[1, 2, 3], [5, 6, 7, 8, 9], [9, 8, 7, 6, 5, 4, 3]]
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new=4))
    done = {r.req_id: r.generated for r in eng.run()}
    for i, p in enumerate(prompts):
        ref = generate(params, cfg, jnp.asarray([p], jnp.int32),
                       max_new=4)[0, len(p):].tolist()
        assert done[i] == ref, (i, done[i], ref)


def test_mla_latent_cache_parity():
    """DeepSeek-V3 (MLA latent cache + MoE): engine parity vs generate()
    through the per-row masked latent ring write and the absorbed decode
    path.  MoE capacity-factor dropping depends on the per-call token
    count, so chunked prefill is NOT bitwise-equal for MoE models —
    admission here is shape-identical to generate()'s prefill (slots=1,
    chunk >= prompt), which isolates the MLA cache machinery."""
    cfg = get_smoke_config("deepseek-v3-671b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[1, 2, 3], [5, 6, 7, 8, 9], [9, 8, 7, 6, 5, 4, 3, 2, 1]]
    for p in prompts:
        eng = ServingEngine(params, cfg, slots=1, cache_len=64,
                            chunk=len(p))
        eng.submit(Request(0, p, max_new=4))
        out = eng.run()[0].generated
        ref = generate(params, cfg, jnp.asarray([p], jnp.int32),
                       max_new=4)[0, len(p):].tolist()
        assert out == ref, (p, out, ref)


# ---------------------------------------------------------------------------
# Slot recycle: no stale cache/state leaks into the next occupant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["gpt3-24l", "gemma3-12b", "rwkv6-7b"])
def test_slot_recycle_no_stale_leak(arch):
    """Second request reuses slot 0 after a LONGER first occupant: any
    surviving KV entries (valid positions past the new prompt) or carried
    recurrent state would change its greedy decode."""
    cfg = _tiny_cfg() if arch == "gpt3-24l" else get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(2), cfg)
    eng = ServingEngine(params, cfg, slots=1, cache_len=64, chunk=4)
    eng.submit(Request(0, [5, 6, 7, 8, 9, 10, 11], max_new=4))
    eng.submit(Request(1, [1, 2, 3], max_new=4))
    done = {r.req_id: r.generated for r in eng.run()}
    ref = generate(params, cfg, jnp.asarray([[1, 2, 3]], jnp.int32),
                   max_new=4)[0, 3:].tolist()
    assert done[1] == ref, (arch, done[1], ref)


@pytest.mark.parametrize("arch", ["gpt3-24l", "gemma3-12b", "rwkv6-7b",
                                  "jamba-1.5-large-398b", "deepseek-v3-671b"])
def test_clear_slot_all_cache_kinds(arch):
    """_clear_slot must zero exactly one slot's leaves for every cache
    kind (KV / MLA-latent / SSM-state / SWA-ring), set its positions to
    -1, and leave other slots untouched — including stack caches whose
    leading period axis happens to EQUAL the slot count (the seed bug
    picked the slot axis by shape comparison)."""
    cfg = get_smoke_config(arch)
    n_periods = cfg.stacks[0].n_periods if cfg.stacks else 2
    slots = max(2, n_periods)      # force the shape collision when possible
    caches = init_cache(cfg, slots, 16)
    # fill every leaf with a nonzero pattern ("pos" leaves get valid >= 0)
    def fill(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "pos":
            return jnp.zeros_like(leaf) + 3
        return jnp.ones_like(leaf)
    caches = jax.tree_util.tree_map_with_path(fill, caches)
    cleared = _clear_slot(caches, 0)

    def check(path, before, after):
        name = str(getattr(path[-1], "key", path[-1]))
        top = str(getattr(path[0], "key", path[0]))
        bdim = 1 if top == "stack" else 0
        b, a = np.asarray(before), np.asarray(after)
        if bdim:
            b = np.moveaxis(b, 0, -1).reshape(b.shape[1], -1)
            a = np.moveaxis(a, 0, -1).reshape(a.shape[1], -1)
        else:
            b, a = b.reshape(b.shape[0], -1), a.reshape(a.shape[0], -1)
        want = -1 if name == "pos" else 0
        assert (a[0] == want).all(), (arch, path, "slot 0 not cleared")
        np.testing.assert_array_equal(a[1:], b[1:],
                                      err_msg=f"{arch} {path}: other slots")
    jax.tree_util.tree_map_with_path(check, caches, cleared)


# ---------------------------------------------------------------------------
# Admission cost: ceil(S/chunk) jitted forward calls, not S
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,chunk", [(37, 8), (16, 8), (3, 8), (1, 8),
                                     (10, 1)])
def test_admission_call_count(S, chunk):
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(3), cfg)
    eng = ServingEngine(params, cfg, slots=2, cache_len=64, chunk=chunk)
    calls = []

    def counting(kind, orig):
        def f(p, c, seen, toks, pos, *rest):
            calls.append((kind, tuple(toks.shape)))
            return orig(p, c, seen, toks, pos, *rest)
        return f
    eng._prefill_fn = counting("prefill", eng._prefill_fn)
    eng._decode_fn = counting("decode", eng._decode_fn)
    eng.submit(Request(0, list(range(1, S + 1)), max_new=2))
    eng._admit()
    expect = math.ceil(S / chunk)
    assert len(calls) == expect, (calls, expect)
    assert eng.stats["prefill_calls"] == expect
    # every admission step is batched over all slots, through the
    # prefill program
    assert all(kind == "prefill" and shape[0] == eng.slots
               for kind, shape in calls)
    # one decode tick = exactly one more jitted call for all slots
    eng.tick()
    assert len(calls) == expect + 1
    assert calls[-1] == ("decode", (eng.slots, 1))
    assert eng.stats["decode_calls"] == 1


def test_chunked_vs_tokenwise_same_output():
    """chunk=1 degenerates to the seed's token-level admission; any chunk
    size must produce identical greedy output."""
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(4), cfg)
    outs = []
    for chunk in (1, 3, 8, 64):
        eng = ServingEngine(params, cfg, slots=2, cache_len=64, chunk=chunk)
        eng.submit(Request(0, [4, 3, 2, 1, 2, 3, 4], max_new=5))
        outs.append(eng.run()[0].generated)
    assert all(o == outs[0] for o in outs), outs


# ---------------------------------------------------------------------------
# Per-row masked ring write (the cache primitive under chunked prefill)
# ---------------------------------------------------------------------------

def test_ring_write_per_row_matches_static_and_masks():
    from repro.models.layers import ring_write
    B, T, S, H, D = 3, 16, 5, 2, 4
    buf = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, D))
    val = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    # row-uniform contiguous prefill: per_row path == static path
    pos = jnp.broadcast_to(jnp.arange(4, 4 + S)[None], (B, S))
    np.testing.assert_allclose(np.asarray(ring_write(buf, val, pos)),
                               np.asarray(ring_write(buf, val, pos,
                                                     per_row=True)))
    # full-length wrap (S == T ring prefill)
    val2 = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, D))
    pos2 = jnp.broadcast_to(jnp.arange(10, 10 + T)[None], (B, T))
    np.testing.assert_allclose(np.asarray(ring_write(buf, val2, pos2)),
                               np.asarray(ring_write(buf, val2, pos2,
                                                     per_row=True)))
    # mixed per-row starts (one wrapping) + a fully masked row
    pos3 = jnp.stack([jnp.arange(2, 2 + S), jnp.full((S,), -1),
                      jnp.arange(14, 14 + S)])
    got = np.asarray(ring_write(buf, val, pos3, per_row=True))
    exp = np.asarray(buf).copy()
    for s in range(S):
        exp[0, (2 + s) % T] = np.asarray(val)[0, s]
        exp[2, (14 + s) % T] = np.asarray(val)[2, s]
    np.testing.assert_allclose(got, exp)   # row 1 (masked) untouched


# ---------------------------------------------------------------------------
# Input validation
# ---------------------------------------------------------------------------

def test_empty_prompt_rejected():
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(5), cfg)
    eng = ServingEngine(params, cfg, slots=1, cache_len=16)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(0, [], max_new=4))
    assert not eng.queue


def test_oversize_prompt_rejected_for_full_attention():
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(5), cfg)
    eng = ServingEngine(params, cfg, slots=1, cache_len=8)
    with pytest.raises(ValueError, match="not wrap"):
        eng.submit(Request(0, list(range(9)), max_new=1))


def test_attention_free_long_prompt_served():
    """Recurrent models (RWKV) have no cache_len-sized buffer — a prompt
    longer than cache_len must be admitted and stay parity-correct (the
    context bound applies to full-attention caches only).  150 tokens
    also exceeds SCAN_CHUNK=128 with a remainder, regression-covering the
    padded-scan state corruption in _chunked_scan (padded decay steps
    must be state no-ops or generate()'s own prefill carry is wrong)."""
    cfg = get_smoke_config("rwkv6-7b")
    params = init_params(jax.random.PRNGKey(9), cfg)
    prompt = [(i * 5 + 1) % cfg.vocab_size for i in range(150)]
    eng = ServingEngine(params, cfg, slots=2, cache_len=64, chunk=16)
    eng.submit(Request(0, prompt, max_new=4))     # 150 > cache_len
    out = eng.run()[0].generated
    ref = generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                   max_new=4)[0, len(prompt):].tolist()
    assert out == ref, (out, ref)


def test_chunked_scan_padded_tail_is_state_noop():
    """_chunked_scan pads length to a chunk multiple; the padded steps
    must not advance the carry (a decay step on zero input is not the
    identity)."""
    from repro.models.ssm import _chunked_scan
    step = lambda h, x: (h + 1.0, h)
    for L in (5, 128, 150, 257):
        h, ys = _chunked_scan(step, jnp.zeros(()), jnp.zeros((L,)), L)
        assert float(h) == L, (L, float(h))
        assert ys.shape[0] == L


@pytest.mark.parametrize("chunk", [16, 80])
def test_swa_ring_wrap_chunked_prefill_parity(chunk):
    """Prompt LONGER than the sliding window: mid-prefill the chunk write
    wraps the SWA ring and evicts slots whose keys are still inside the
    earliest in-chunk queries' windows.  Attention must run against the
    pre-write ring ∥ chunk, so greedy output equals generate() for any
    chunk size (regression: write-then-attend silently diverged here)."""
    cfg = get_smoke_config("gemma3-12b")          # window 64
    params = init_params(jax.random.PRNGKey(7), cfg)
    prompt = [(i * 7 + 3) % cfg.vocab_size for i in range(80)]  # > window
    eng = ServingEngine(params, cfg, slots=1, cache_len=128, chunk=chunk)
    eng.submit(Request(0, prompt, max_new=6))
    out = eng.run()[0].generated
    ref = generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                   max_new=6)[0, len(prompt):].tolist()
    assert out == ref, (chunk, out, ref)


def test_full_attention_ring_wrap_rejected():
    """prompt + max_new beyond cache_len would wrap a full-attention ring
    and silently overwrite early KV — submit() must reject it."""
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(5), cfg)
    eng = ServingEngine(params, cfg, slots=1, cache_len=16)
    with pytest.raises(ValueError, match="not wrap"):
        eng.submit(Request(0, list(range(1, 13)), max_new=10))
    eng.submit(Request(1, list(range(1, 13)), max_new=4))    # exactly fits


def test_warmup_on_busy_engine_preserves_live_slots():
    """warmup() after traffic has started must not clear a live slot's
    cache (the compile-the-reset step may only touch a free slot)."""
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(8), cfg)
    prompt = [3, 1, 4, 1, 5]
    eng = ServingEngine(params, cfg, slots=1, cache_len=32, chunk=4)
    eng.submit(Request(0, prompt, max_new=6))
    eng.tick()                     # admit + first token; slot 0 now live
    eng.warmup()                   # no free slot: must leave cache alone
    eng.run()
    ref = generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                   max_new=6)[0, len(prompt):].tolist()
    assert eng.finished[0].generated == ref


def test_warmup_is_state_noop():
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(6), cfg)
    eng = ServingEngine(params, cfg, slots=2, cache_len=32, chunk=4)
    eng.warmup()
    eng.submit(Request(0, [1, 2, 3, 4, 5], max_new=3))
    warm = eng.run()[0].generated
    eng2 = ServingEngine(params, cfg, slots=2, cache_len=32, chunk=4)
    eng2.submit(Request(0, [1, 2, 3, 4, 5], max_new=3))
    assert warm == eng2.run()[0].generated
