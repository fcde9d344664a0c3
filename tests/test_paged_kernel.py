"""Fused Pallas paged-decode attention tests.

Two layers of coverage:

* **kernel vs scan path** — ``repro.kernels.ops.paged_attention``
  against the chunked-gather reference in
  ``repro.models.layers.attention(..., table=...)`` across dtypes
  (f32/bf16), head dims, GQA ratios, softcap, SWA windows crossing page
  boundaries, ``-1``-padded table columns, the MLA second-contraction
  path, and idle (position ``-1``) slots;
* **engine under ``use_kernel=True``** — token-level parity with the
  scan-path paged engine, the dense engine and ``generate()`` on the
  KV / GQA / SWA / MLA / hybrid configs, plus a recycled-block scrub
  regression under the kernel path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models.layers import attention, swa_ring_blocks
from repro.models.transformer import init_params
from repro.serve.engine import Request, ServingEngine, generate

KEY = jax.random.PRNGKey(3)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


def _pool_case(B, Hq, Hkv, D, page, n_cols, dtype, *, used_cols, seed=0,
               Dv=None, De=0):
    """Build a pool + table where each row has ``used_cols`` allocated
    pages (the rest are -1) and the last allocated page is PARTIALLY
    written — trailing entries keep position -1 like a real pool."""
    rng = np.random.RandomState(seed)
    Dv = Dv or D
    N = B * n_cols + 2                       # spare blocks stay unused
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    k = jax.random.normal(ks[0], (N, page, Hkv, D), dtype)
    v = jax.random.normal(ks[1], (N, page, Hkv, Dv), dtype)
    ke = jax.random.normal(ks[2], (N, page, Hkv, De), dtype) if De else None
    # positions: block b holds its logical page's positions, partially
    pos = np.full((N, page), -1, np.int32)
    table = np.full((B, n_cols), -1, np.int32)
    q_pos = np.zeros((B, 1), np.int32)
    perm = rng.permutation(N)      # one shared permutation -> rows get
    blk = 0                        # disjoint (scattered) pool blocks
    for b in range(B):
        t_total = used_cols * page - rng.randint(0, page)  # partial tail
        q_pos[b, 0] = t_total                              # next position
        for c in range(used_cols):
            table[b, c] = perm[blk]
            lo, hi = c * page, min((c + 1) * page, t_total)
            if hi > lo:
                pos[perm[blk], : hi - lo] = np.arange(lo, hi)
            blk += 1
    q = jax.random.normal(ks[3], (B, 1, Hq, D), dtype)
    qe = jax.random.normal(ks[4], (B, 1, Hq, De), dtype) if De else None
    return (q, k, v, jnp.asarray(pos), jnp.asarray(table),
            jnp.asarray(q_pos), qe, ke)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,D,page,n_cols,used,window,softcap",
    [(2, 4, 2, 32, 8, 4, 3, 0, 0.0),      # GQA, -1 tail columns
     (1, 8, 8, 64, 16, 4, 4, 0, 0.0),     # MHA, full table
     (3, 4, 1, 80, 8, 6, 2, 0, 0.0),      # MQA, odd head dim
     (2, 4, 2, 32, 8, 8, 5, 20, 0.0),     # SWA window crossing pages
     (1, 4, 2, 32, 8, 4, 3, 0, 30.0),     # gemma-style softcap
     (2, 16, 4, 128, 16, 3, 3, 0, 0.0)])  # wide heads, MXU-aligned
def test_paged_kernel_vs_scan(B, Hq, Hkv, D, page, n_cols, used, window,
                              softcap, dtype):
    q, k, v, pos, table, q_pos, _, _ = _pool_case(
        B, Hq, Hkv, D, page, n_cols, dtype, used_cols=used, seed=B + used)
    out = ops.paged_attention(q, k, v, pos, table, q_pos, window=window,
                              softcap=softcap)
    exp = attention(q, k, v, q_pos, pos, window=window, softcap=softcap,
                    table=table, kv_chunk=page)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def _walk_case(q_positions, n_cols, dtype, *, B_heads=(4, 2), D=32,
               page=8, seed=0):
    """One slot per entry of ``q_positions``, each written like the
    engine's pool: positions 0..q_pos in its first ``ceil((q_pos+1) /
    page)`` columns, scattered over the pool; the rest of the row -1
    (idle slots, q_pos -1, have none).  Blocks past the slots' pages
    stay spare."""
    Hq, Hkv = B_heads
    B = len(q_positions)
    N = B * n_cols + 4
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k = jax.random.normal(ks[0], (N, page, Hkv, D), dtype)
    v = jax.random.normal(ks[1], (N, page, Hkv, D), dtype)
    q = jax.random.normal(ks[2], (B, 1, Hq, D), dtype)
    pos = np.full((N, page), -1, np.int32)
    table = np.full((B, n_cols), -1, np.int32)
    perm = iter(rng.permutation(N))
    for b, qp in enumerate(q_positions):
        for c in range(-(-(qp + 1) // page)):
            table[b, c] = blk = next(perm)
            hi = min(page, qp + 1 - c * page)
            pos[blk, :hi] = np.arange(c * page, c * page + hi)
    q_pos = np.asarray(q_positions, np.int32).reshape(B, 1)
    return q, k, v, pos, table, q_pos


def _block_pages(page=8, Hkv=2, D=32, dtype=jnp.float32):
    return pa._block_pages(page, Hkv, D, D, 0, jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("edge", ["mid_block", "block_boundary",
                                  "p_not_dividing_n_cols", "single_page",
                                  "all_idle"])
def test_paged_kernel_walk_edges(edge, dtype):
    """The live walk in blocks of P pages against the scan path: a
    slot's live columns ending inside a block, on a block boundary, a
    table width P does not divide (walked whole), one live page, and no
    live slot at all (exact zeros)."""
    page = 8
    P = _block_pages(dtype=dtype)
    n_cols = 2 * P + 3
    last = n_cols * page - 1                 # the table's last position
    q_positions = {
        "mid_block": [(P + 3) * page - 5, P * page + 2],
        "block_boundary": [2 * P * page - 1, P * page - 1],
        "p_not_dividing_n_cols": [last, last - page],
        "single_page": [0, page - 1],
        "all_idle": [-1, -1],
    }[edge]
    q, k, v, pos, table, q_pos = _walk_case(q_positions, n_cols, dtype,
                                            page=page)
    assert list(pa.live_columns(q_pos[:, 0], page, n_cols)) == [
        max(0, -(-(p + 1) // page)) for p in q_positions]
    out = ops.paged_attention(q, k, v, jnp.asarray(pos),
                              jnp.asarray(table), jnp.asarray(q_pos))
    if edge == "all_idle":
        assert not np.asarray(out).any()
        return
    exp = attention(q, k, v, jnp.asarray(q_pos), jnp.asarray(pos),
                    table=jnp.asarray(table), kv_chunk=page)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_paged_kernel_never_reads_past_live_columns():
    """Columns past a slot's live count point at pool blocks of NaN
    whose positions look attendable: the kernel never copies them, so
    the output is finite and equals the scan path with those columns
    set to -1."""
    page, n_cols = 8, 6
    q, k, v, pos, table, q_pos = _walk_case([19, 8], n_cols, jnp.float32,
                                            page=page, seed=4)
    live = pa.live_columns(q_pos[:, 0], page, n_cols)          # 3, 2
    N = k.shape[0]
    bad = [N - 1, N - 2, N - 3]              # spare blocks, never written
    k = k.at[jnp.asarray(bad)].set(jnp.nan)
    v = v.at[jnp.asarray(bad)].set(jnp.nan)
    pos[bad] = np.arange(page)               # positions <= every q_pos
    stale = table.copy()
    stale[0, live[0]:live[0] + 2] = bad[:2]
    stale[1, live[1]] = bad[2]
    out = ops.paged_attention(q, k, v, jnp.asarray(pos), jnp.asarray(stale),
                              jnp.asarray(q_pos))
    assert np.isfinite(np.asarray(out)).all()
    exp = attention(q, k, v, jnp.asarray(q_pos), jnp.asarray(pos),
                    table=jnp.asarray(table), kv_chunk=page)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_rejects_odd_bf16_kv_heads():
    """bf16 rows pair up in 32-bit words, so several kv heads in a bf16
    pool must come in pairs: three are refused, not misread."""
    q, k, v, pos, table, q_pos = _walk_case([5], 2, jnp.bfloat16,
                                            B_heads=(6, 3))
    with pytest.raises(ValueError, match="kv heads"):
        ops.paged_attention(q, k, v, jnp.asarray(pos), jnp.asarray(table),
                            jnp.asarray(q_pos))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_mla_second_contraction(dtype):
    """MLA absorbed decode: latent pool is both K and V (Dv == D == kr),
    the rope pool enters as q_extra/k_extra."""
    B, H, kr, dr, page, n_cols = 2, 4, 48, 16, 8, 4
    q, k, _, pos, table, q_pos, qe, ke = _pool_case(
        B, H, 1, kr, page, n_cols, dtype, used_cols=3, seed=11, De=dr)
    scale = (kr + dr) ** -0.5
    out = ops.paged_attention(q, k, k, pos, table, q_pos, scale=scale,
                              q_extra=qe, k_extra=ke)
    exp = attention(q, k, k, q_pos, pos, scale=scale, q_extra=qe,
                    k_extra=ke, table=table, kv_chunk=page)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_paged_kernel_idle_slot_outputs_zero():
    """Rows with q_pos -1 (idle serving slots) must produce exactly-zero
    output, like the scan path (all keys masked -> l == 0)."""
    q, k, v, pos, table, q_pos, _, _ = _pool_case(
        2, 4, 2, 32, 8, 4, jnp.float32, used_cols=3, seed=5)
    q_pos = q_pos.at[1, 0].set(-1)
    table = table.at[1].set(-1)
    out = ops.paged_attention(q, k, v, pos, table, q_pos)
    assert np.asarray(out)[1].max() == 0.0 and np.asarray(out)[1].min() == 0.0
    exp = attention(q, k, v, q_pos, pos, table=table, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(exp)[0],
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_fully_unallocated_table_column_is_neutral():
    """A -1 table column must contribute exactly-zero probability mass:
    inserting one changes nothing."""
    q, k, v, pos, table, q_pos, _, _ = _pool_case(
        1, 4, 2, 32, 8, 4, jnp.float32, used_cols=4, seed=7)
    out_full = ops.paged_attention(q, k, v, pos, table, q_pos)
    # same pages + two extra -1 columns interleaved at the end
    wide = jnp.concatenate(
        [table, jnp.full((1, 2), -1, jnp.int32)], axis=1)
    out_wide = ops.paged_attention(q, k, v, pos, wide, q_pos)
    np.testing.assert_array_equal(np.asarray(out_full), np.asarray(out_wide))


def test_swa_ring_column_windowing_matches_scan():
    """SWA hands the kernel only the ring columns; positions wrap the
    ring across page boundaries and the window mask must stay exact."""
    window, page, n_cols = 20, 8, 8
    nb = swa_ring_blocks(window, page, n_cols)          # 3 ring pages
    B, Hq, Hkv, D = 1, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    N = 8
    k = jax.random.normal(ks[0], (N, page, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[1], (N, page, Hkv, D), jnp.float32)
    ring = nb * page
    # a long sequence wrapped into the ring: position p lives at
    # (p % ring) — fill pages so every ring slot holds its LATEST owner
    q_pos_val = 45
    pos = np.full((N, page), -1, np.int32)
    table = np.asarray([[2, 5, 1] + [-1] * (n_cols - 3)], np.int32)
    for p in range(q_pos_val + 1):
        sl = p % ring
        pos[table[0, sl // page], sl % page] = p
    q = jax.random.normal(ks[2], (B, 1, Hq, D), jnp.float32)
    q_pos = jnp.asarray([[q_pos_val]], jnp.int32)
    tab = jnp.asarray(table)[:, :nb]
    out = ops.paged_attention(q, k, v, jnp.asarray(pos), tab, q_pos,
                              window=window)
    exp = attention(q, k, v, q_pos, jnp.asarray(pos), window=window,
                    table=tab, kv_chunk=page)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Engine-level parity under use_kernel=True
# ---------------------------------------------------------------------------

def _tiny_cfg(n_kv=2):
    cfg = get_smoke_config("gpt3-24l")
    return dataclasses.replace(cfg, vocab_size=128, d_model=128, d_ff=256,
                               n_heads=4, n_kv_heads=n_kv, head_dim=32)


def _run(params, cfg, prompts, *, paged, use_kernel, max_new=4, **kw):
    eng = ServingEngine(params, cfg, paged=paged, use_kernel=use_kernel,
                        **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new=max_new))
    return {r.req_id: r.generated for r in eng.run()}


@pytest.mark.parametrize("arch", ["gpt3-24l", "gemma3-12b",
                                  "deepseek-v3-671b"])
def test_kernel_engine_matches_scan_engine_and_generate(arch):
    """KV-GQA / SWA / MLA configs: the kernel-path engine must emit the
    same tokens as the scan-path paged engine, the dense engine, and
    generate().  Mixed prompt lengths straddle page and chunk
    boundaries; 2 slots over more requests exercise slot recycling."""
    if arch == "gpt3-24l":
        cfg = _tiny_cfg(n_kv=2)            # GQA ratio 2 through the engine
        prompts = [[7], [1, 2, 3], list(range(5, 22)),
                   [9, 8, 7, 6, 5, 4, 3, 2, 1]]
        kw = dict(slots=2, cache_len=64, chunk=4, page_size=16)
    elif arch == "gemma3-12b":             # SWA window 64 + softcap
        cfg = get_smoke_config(arch)
        prompts = [[(i * 7 + 3) % cfg.vocab_size for i in range(80)], [5, 6]]
        kw = dict(slots=2, cache_len=128, chunk=16, page_size=16)
    else:                                  # MLA latent pool (MoE caveat:
        cfg = get_smoke_config(arch)       # whole-prompt admits)
        prompts = [[5, 6, 7, 8, 9]]
        kw = dict(slots=1, cache_len=64, chunk=16, page_size=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    scan = _run(params, cfg, prompts, paged=True, use_kernel=False, **kw)
    kern = _run(params, cfg, prompts, paged=True, use_kernel=True, **kw)
    dense = _run(params, cfg, prompts, paged=False, use_kernel=False, **kw)
    refs = [generate(params, cfg, jnp.asarray([p], jnp.int32),
                     max_new=4)[0, len(p):].tolist() for p in prompts]
    for i in range(len(prompts)):
        assert kern[i] == scan[i] == dense[i] == refs[i], (
            arch, i, kern[i], scan[i], dense[i], refs[i])


def test_kernel_engine_hybrid_ssm_state_coexists():
    """Jamba: paged KV pools walked by the kernel coexist with per-slot
    recurrent state (which ignores use_kernel)."""
    cfg = get_smoke_config("jamba-1.5-large-398b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[1, 2, 3], [5, 6, 7, 8, 9]]
    kw = dict(slots=2, cache_len=64, chunk=64, page_size=16)
    scan = _run(params, cfg, prompts, paged=True, use_kernel=False, **kw)
    kern = _run(params, cfg, prompts, paged=True, use_kernel=True, **kw)
    assert kern == scan


def test_kernel_engine_recycled_blocks_scrubbed():
    """slots=1, pool exactly one request wide: request 2 decodes through
    the kernel on request 1's recycled blocks — scrubbing must hold
    under the kernel read path too."""
    cfg = _tiny_cfg(n_kv=4)
    params = init_params(jax.random.PRNGKey(2), cfg)
    eng = ServingEngine(params, cfg, slots=1, cache_len=32, chunk=4,
                        paged=True, page_size=8, num_blocks=2,
                        use_kernel=True)
    eng.submit(Request(0, [5, 6, 7, 8, 9, 10, 11], max_new=4))
    eng.submit(Request(1, [1, 2, 3], max_new=4))
    done = {r.req_id: r.generated for r in eng.run()}
    for rid, p in [(0, [5, 6, 7, 8, 9, 10, 11]), (1, [1, 2, 3])]:
        ref = generate(params, cfg, jnp.asarray([p], jnp.int32),
                       max_new=4)[0, len(p):].tolist()
        assert done[rid] == ref, (rid, done[rid], ref)


def test_use_kernel_requires_paged():
    """Dense rings have no block table to walk — asking for the kernel
    without paging must fail loudly, not silently serve the scan path."""
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="use_kernel"):
        ServingEngine(params, cfg, paged=False, use_kernel=True)


def test_kernel_engine_sampled_and_greedy_slots():
    """Kernel path + in-jit sampling: the greedy slot stays bitwise equal
    to the all-greedy scan engine while a top-k/penalty slot samples."""
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ref = _run(params, cfg, [[1, 2, 3]], paged=True, use_kernel=False,
               max_new=6, slots=2, cache_len=64, chunk=4, page_size=16)
    eng = ServingEngine(params, cfg, slots=2, cache_len=64, chunk=4,
                        paged=True, page_size=16, use_kernel=True)
    eng.submit(Request(0, [1, 2, 3], max_new=6))
    eng.submit(Request(1, [4, 5, 6], max_new=6, temperature=1.0,
                       top_p=0.9, top_k=8, rep_penalty=1.3))
    done = {r.req_id: r.generated for r in eng.run()}
    assert done[0] == ref[0], (done[0], ref[0])
    assert all(0 <= t < cfg.vocab_size for t in done[1])
