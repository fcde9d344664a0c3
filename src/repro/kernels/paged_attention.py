"""Pallas paged-decode attention: block-table-aware online-softmax.

The serving engine's decode hot path attends one query token per slot
against a paged KV pool (PR 2).  The scan-path reference in
``repro.models.layers.attention`` pays for every online-softmax step
with a ``pool[safe_table]`` gather that materializes a (B, C, Hkv, D)
K/V copy in HBM before the math starts.  This kernel fuses the
block-table walk into the attention loop instead:

* grid ``(slot * kv_head, page_column)`` with the page axis innermost
  ("arbitrary" = sequential) so the (m, l, acc) online-softmax running
  statistics live in VMEM scratch across pages;
* the per-slot block table (and the query positions) are
  **scalar-prefetched** (``pltpu.PrefetchScalarGridSpec``) and drive the
  K/V/pos ``BlockSpec`` index_maps — each grid step DMAs exactly one
  pool page HBM -> VMEM, so no gathered K/V copy ever lands in HBM;
* ``-1`` table columns (unallocated pages) are clamped to block 0 for
  the DMA and force-masked in the kernel body, making them
  exactly-neutral in the same online-softmax as the scan path — the
  masking/accumulation math is identical, preserving the paged engine's
  parity story;
* GQA via the index_map (each kv head's pages are read once and shared
  by its G query heads — no KV replication in HBM);
* SWA by handing the kernel only the ring columns of the table
  (``swa_ring_blocks``) plus the window mask — ring pages wrap exactly
  as in the scan path;
* MLA absorbed decode via the optional second score contraction
  (``q_extra @ k_extra^T``, the rope term): k IS the latent pool, v the
  same pool, k_extra the rope pool — all three walked page-wise.

The call carries an analytic ``pl.CostEstimate`` built by
``paged_attention_cost`` — the kernel's exact DMA schedule (each page
read once per kv head, q/out once per (slot, head), no intermediate
copies), which is what ``compiled.cost_analysis()`` reports for the
fused op on a Mosaic compile and what ``benchmarks/micro.py::
paged_kernel_bench`` compares against the gather path's XLA-costed
bytes.  (Interpret mode emulates block DMA with loop-carried copies, so
its own XLA byte count measures the interpreter, not the kernel.)

Mosaic tiles the last two dims of every block in (8, 128) units or
takes them whole, so the kernel reads the pools through a free
``(N, page, Hkv*D)`` view — one head's page is a ``(page, D)`` block —
and wants ``page_size`` a sublane multiple (8 for f32 pools, 16 for
bf16).  A head dim that is not a lane multiple is zero-padded (exact)
only where several kv heads share the lane axis.  ``interpret=True``
runs the same body as traced JAX ops: ``repro.kernels.ops`` selects it
on the CPU backend only, which is also what the production dry-run
lowers on the host-device mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _spec_plan(B: int, Hq: int, Hkv: int, page: int, n_cols: int,
               D: int, Dv: int, De: int, itemsize: int):
    """The kernel's block layout AND its DMA schedule from one source.

    Block shapes are over the kernel's operand views (see
    ``paged_attention_fwd``): q/out ``(B, Hkv, G, D)``, K/V/rope pools
    ``(N, page, Hkv*D)`` and positions ``(N, 1, page)``, so every block's
    last two dims are whole or ``(page, D)`` tiles Mosaic accepts.

    Returns (in_specs, out_spec, bytes_accessed) where each entry of the
    plan is one ``BlockSpec`` plus the number of distinct fetches the
    grid performs for it: kv/pos blocks are re-indexed every page column
    (``B*Hkv*n_cols`` fetches), q/out blocks depend only on the parallel
    axis (``B*Hkv`` fetches — they revisit across the sequential page
    axis, so Mosaic keeps them in VMEM).  ``bytes_accessed`` is the sum
    over the same plan (+ the scalar-prefetch operands), so any change
    to the block shapes or index_maps changes the advertised cost with
    it — this is the ``pl.CostEstimate`` a Mosaic compile reports
    through ``cost_analysis()``."""
    G = Hq // Hkv

    def head_index(bh, ic, tab, qp):
        return (bh // Hkv, bh % Hkv, 0, 0)

    def kv_index(bh, ic, tab, qp):
        # the scalar-prefetched table drives the page DMA: one head of
        # one pool page per grid step, straight from HBM (unallocated ->
        # block 0, the body force-masks it); the last index picks the
        # head's D-wide lane block of the flattened (Hkv*D) axis
        return (jnp.maximum(tab[bh // Hkv, ic], 0), 0, bh % Hkv)

    def pos_index(bh, ic, tab, qp):
        return (jnp.maximum(tab[bh // Hkv, ic], 0), 0, 0)

    per_head = B * Hkv                      # fetched once per (slot, head)
    per_page = B * Hkv * n_cols             # re-fetched every page column
    plan = [  # (BlockSpec, fetches, itemsize)
        (pl.BlockSpec((1, 1, G, D), head_index), per_head, itemsize),
        (pl.BlockSpec((1, page, D), kv_index), per_page, itemsize),
        (pl.BlockSpec((1, page, Dv), kv_index), per_page, itemsize),
        (pl.BlockSpec((1, 1, page), pos_index), per_page, 4),
    ]
    if De:
        plan += [(pl.BlockSpec((1, 1, G, De), head_index), per_head,
                  itemsize),
                 (pl.BlockSpec((1, page, De), kv_index), per_page,
                  itemsize)]
    out_spec = pl.BlockSpec((1, 1, G, Dv), head_index)
    byt = B * n_cols * 4 + B * 4            # scalar-prefetch table + q_pos
    for spec, fetches, isz in plan + [(out_spec, per_head, itemsize)]:
        blk = 1
        for s in spec.block_shape:
            blk *= s
        byt += blk * fetches * isz
    return [s for s, _, _ in plan], out_spec, byt


def _lane_pad(dim: int, Hkv: int, interpret: bool) -> int:
    """Zero columns that make a head's ``dim``-wide lane block of the
    flattened ``(N, page, Hkv*dim)`` pool Mosaic-legal: a block's last
    dim must be a multiple of 128 or the whole axis.  With one kv head
    the block IS the whole axis, so only multi-head pools with a
    non-lane-multiple head dim pay the pad (a copy of the pool);
    interpret mode never pads."""
    if interpret or Hkv == 1:
        return 0
    return -dim % 128


def paged_attention_cost(q, k, v, table, q_extra=None, *,
                         interpret: bool) -> pl.CostEstimate:
    """Analytic cost of one paged-decode call — the DMA schedule the
    grid actually executes, derived from the SAME spec plan the kernel
    is built from (``_spec_plan``): every table column's K/V (+rope)
    page read once per kv head, q and the output touched once per
    (slot, kv head), scalar table/q_pos in SMEM.  No gathered copy, so
    no other HBM term exists.  Pass the same ``interpret`` flag as the
    call being costed: compiled (Mosaic) mode lane-pads head dims to
    128 where several kv heads share the pool's lane axis
    (``_lane_pad``), so its blocks — and therefore its DMA bytes — can be
    wider than interpret mode's."""
    B, _, Hq, D = q.shape
    N, page, Hkv, Dv = v.shape
    n_cols = table.shape[1]
    De = 0 if q_extra is None else q_extra.shape[-1]
    D += _lane_pad(D, Hkv, interpret)      # mirror the fwd lane padding
    Dv += _lane_pad(Dv, Hkv, interpret)
    De += _lane_pad(De, Hkv, interpret)
    _, _, byt = _spec_plan(B, Hq, Hkv, page, n_cols, D, Dv, De,
                           q.dtype.itemsize)
    T = n_cols * page
    flops = 2 * B * Hq * T * (D + Dv + De)
    return pl.CostEstimate(flops=flops, transcendentals=B * Hq * T,
                           bytes_accessed=byt)


def _paged_decode_kernel(tab_ref, qpos_ref, q_ref, k_ref, v_ref, pos_ref,
                         *rest, hkv: int, scale: float, window: int,
                         softcap: float, n_cols: int, has_extra: bool):
    """One (slot*kv_head, page_column) grid step.

    Blocks: q (1, 1, G, D); k (1, page, D); v (1, page, Dv);
    pos (1, 1, page); [qe (1, 1, G, De); ke (1, page, De)];
    out (1, 1, G, Dv); scratch m/l (G, 1), acc (G, Dv) — all f32.
    """
    if has_extra:
        qe_ref, ke_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    bh = pl.program_id(0)
    ic = pl.program_id(1)
    b = bh // hkv

    @pl.when(ic == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    blk = tab_ref[b, ic]                                 # -1 = unallocated
    q_pos = qpos_ref[b]                                  # -1 = idle slot
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0].astype(jnp.float32)                     # (page, D)
    v = v_ref[0].astype(jnp.float32)                     # (page, Dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, page)
    if has_extra:
        qe = qe_ref[0, 0].astype(jnp.float32) * scale    # (G, De)
        ke = ke_ref[0].astype(jnp.float32)               # (page, De)
        s = s + jax.lax.dot_general(qe, ke, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    pj = pos_ref[0]                                      # (1, page) int32
    ok = (blk >= 0) & (pj >= 0) & (pj <= q_pos)          # causal + validity
    if window > 0:
        ok &= pj > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    # fully-masked pages: exp(NEG_INF - NEG_INF) = 1 — zero it like the
    # scan path so unallocated pages carry exactly-zero probability mass
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ic == n_cols - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        pos: jax.Array, table: jax.Array, q_pos: jax.Array,
                        *, scale: float | None = None, window: int = 0,
                        softcap: float = 0.0,
                        q_extra: jax.Array | None = None,
                        k_extra: jax.Array | None = None,
                        interpret: bool) -> jax.Array:
    """Paged single-token decode attention.

    q: (B, 1, Hq, D); k: (N, page, Hkv, D); v: (N, page, Hkv, Dv);
    pos: (N, page) int32 (entries < 0 = unwritten); table: (B, n_cols)
    int32 block table (entries < 0 = unallocated); q_pos: (B, 1) int32
    (< 0 = idle slot, whose output is exactly 0 like the scan path).
    q_extra: (B, 1, Hq, De) / k_extra: (N, page, Hkv, De) add a second
    score contraction before the softmax (MLA rope term).

    Returns (B, 1, Hq, Dv) in q.dtype; accumulation in float32.
    """
    B, S, Hq, D = q.shape
    assert S == 1, "paged decode kernel is single-token (S == 1) only"
    N, page, Hkv, Dv = v.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    n_cols = table.shape[1]
    if scale is None:
        scale = D ** -0.5
    has_extra = q_extra is not None
    # the advertised cost comes from the same spec plan the blocks are
    # built from below (one source of truth; see paged_attention_cost)
    cost = paged_attention_cost(q, k, v, table, q_extra,
                                interpret=interpret)

    # Mosaic views: each pool as (N, page, Hkv*D) and the positions as
    # (N, 1, page) — free reshapes — so a head's page is a (page, D)
    # block; q as (B, Hkv, G, D), whose (G, D) blocks are whole.  Lane
    # padding (exact: zero columns add nothing to either dot) only where
    # the head dim alone cannot make a legal block (``_lane_pad``)
    d_pad = _lane_pad(D, Hkv, interpret)
    dv_pad = _lane_pad(Dv, Hkv, interpret)
    qh = q.reshape(B, Hkv, G, D)
    if d_pad:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
    if dv_pad:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dv_pad)))
    Dp, Dvp = D + d_pad, Dv + dv_pad

    def lanes(pool):
        return pool.reshape(N, page, -1)

    operands = [qh, lanes(k), lanes(v), pos.reshape(N, 1, page)]
    Dep = 0
    if has_extra:
        De = q_extra.shape[-1]
        de_pad = _lane_pad(De, Hkv, interpret)
        qe = q_extra.reshape(B, Hkv, G, De)
        ke = k_extra
        if de_pad:
            qe = jnp.pad(qe, ((0, 0), (0, 0), (0, 0), (0, de_pad)))
            ke = jnp.pad(ke, ((0, 0), (0, 0), (0, 0), (0, de_pad)))
        Dep = De + de_pad
        operands += [qe, lanes(ke)]

    in_specs, out_spec, _ = _spec_plan(B, Hq, Hkv, page, n_cols, Dp,
                                       Dvp, Dep, q.dtype.itemsize)

    kernel = functools.partial(
        _paged_decode_kernel, hkv=Hkv, scale=float(scale), window=window,
        softcap=softcap, n_cols=n_cols, has_extra=has_extra)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hkv, n_cols),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dvp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dvp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=cost,
        interpret=interpret,
    )(table, q_pos.reshape(B), *operands)
    return out.reshape(B, 1, Hq, Dvp)[..., :Dv]
