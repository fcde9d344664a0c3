"""Pallas paged-decode attention: block-table-aware online-softmax.

The serving engine's decode hot path attends one query token per slot
against a paged KV pool.  The scan-path reference in
``repro.models.layers.attention`` pays for every online-softmax step
with a ``pool[safe_table]`` gather that materializes a (B, C, Hkv, D)
K/V copy in HBM before the math starts.  This kernel fuses the
block-table walk into the attention loop instead, and walks only what a
slot can attend:

* **grid** ``(slot,)``, sequential ("arbitrary").  One grid step serves
  every kv head of one slot; inside it a ``lax.fori_loop`` walks the
  slot's *live* table columns in blocks of ``P`` pages, so the work
  follows the live pages, not slots x heads x table width;
* **live bound** — a slot at position ``q_pos`` attends positions
  ``0..q_pos``, which lie in its first ``live_columns`` =
  ``ceil((q_pos + 1) / page)`` columns (clamped to the table; the ring
  table of an SWA layer fills its ring columns in the same order).  An
  idle slot (``q_pos < 0``) has none: it copies nothing and writes
  exact zeros.  Columns past the bound are never copied, whatever they
  point at;
* **DMA schedule** — the K/V (and MLA rope) pools stay in HBM
  (``pltpu.HBM``) as ``(N, page*Hkv, D)`` views: a bitcast of the
  pool's own tiled layout, in which one page of every kv head is one
  contiguous row block (row ``t*Hkv + h``).  The scalar-prefetched
  block table drives one ``make_async_copy`` per pool and live,
  allocated page into double-buffered VMEM, and the next block's copies
  start before the current block's math.  No gathered K/V copy and no
  relayout of a pool lands in HBM;
* **P adapts to the shape** (``_block_pages``): the bytes of one table
  column across heads, ``page * Hkv * (D + Dv + De) * itemsize``,
  against a fixed per-buffer VMEM budget, capped at
  ``MAX_BLOCK_PAGES`` (32 pages of 64 KiB at qwen widths).  It does not
  depend on the table width, so a wider table of ``-1`` columns
  computes the same blocks;
* **heads** — each kv head's ``(P*page, D)`` rows are every ``Hkv``-th
  buffer row (``_head_rows``): one strided load for f32 pools, one per
  head pair for bf16.  Its G query heads' scores ``(G, D) x (D,
  P*page)`` and value product run from the shared page copy (GQA, no KV
  replication);
* **masking** is per entry, as in the scan path: an entry counts iff
  its table column is allocated (``>= 0``) and live, its position is
  written (``>= 0``) and ``<= q_pos``, and (SWA) inside the window.
  The positions of every walked column are gathered once per call into
  a ``(B, n_blocks, P*page)`` operand (``-1`` where the column is
  unallocated or past the live bound), so a block's mask is one
  ``(1, P*page)`` row.  Masked entries carry exactly-zero probability
  mass; buffer rows not copied for a block hold finite data from
  earlier copies (the buffers are zeroed once per call);
* SWA: the caller hands only the ring columns (``swa_ring_blocks``)
  plus the window mask; MLA absorbed decode via the optional second
  score contraction (``q_extra @ k_extra^T``, the rope term): k IS the
  latent pool, v the same pool, k_extra the rope pool.

Both products take the pools' dtype into the MXU with float32
accumulation (bf16 x bf16 products are exact in f32; the probabilities
are rounded to it for the value product); softmax and the running
statistics stay float32.

The call carries an analytic ``pl.CostEstimate`` built by
``paged_attention_cost`` from the same plan (``_spec_plan``) as the
kernel's blocks and copies: the walk's worst case, every column of
every slot with each page copied once across heads, q/positions/out
once per slot.  (Interpret mode emulates DMA with traced copies, so its
own XLA byte count measures the interpreter, not the kernel.)

Mosaic tiles the last two dims in (8, 128) units, so ``page_size``
should be a sublane multiple (8 for f32 pools, 16 for bf16), and a
head dim that is not a lane multiple is zero-padded (exact) when
compiling.  bf16 pools need one kv head or an even count.
``interpret=True`` runs the same body as traced JAX ops:
``repro.kernels.ops`` selects it on the CPU backend only, which is also
what the production dry-run lowers on the host-device mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
# VMEM for one buffered block of pages (K + V + rope, one of two buffers)
BLOCK_BYTES = 2 << 20
MAX_BLOCK_PAGES = 32


def live_columns(q_pos, page: int, n_cols: int):
    """Table columns a decode step walks per slot: ``ceil((q_pos + 1) /
    page)`` clamped to ``[0, n_cols]``, so 0 for an idle slot (``q_pos <
    0``).  Works on numpy and jax arrays alike: the kernel wrapper and
    the engine's host-side page counters share it."""
    return ((q_pos + page) // page).clip(0, n_cols)


def _block_pages(page: int, Hkv: int, D: int, Dv: int, De: int,
                 itemsize: int) -> int:
    """Pages per block: as many table columns (every kv head's page of
    K, V and rope) as fit ``BLOCK_BYTES``, at least 1, at most
    ``MAX_BLOCK_PAGES``."""
    col = page * Hkv * (D + Dv + De) * itemsize
    return max(1, min(MAX_BLOCK_PAGES, BLOCK_BYTES // col))


def _spec_plan(B: int, Hq: int, Hkv: int, page: int, n_cols: int,
               D: int, Dv: int, De: int, itemsize: int):
    """The kernel's block layout AND its DMA schedule from one source.

    Operands, in order: q ``(B, Hkv, G, D)``; positions gathered per
    walked column ``(B, n_blocks, P*page)``; the K and V pools as
    ``(N, page*Hkv, D*)`` views left in HBM; [q_extra ``(B, Hkv, G,
    De)``; the rope pool].  BlockSpec'd operands are fetched once per
    slot (the grid is ``(B,)``).  Each pool is copied by hand, one
    ``(page*Hkv, D*)`` row block per walked column.

    Returns (in_specs, out_spec, P, bytes_accessed): ``bytes_accessed``
    sums the plan over the walk's worst case (every column of every
    slot) plus the scalar-prefetch operands, so a change to a block
    shape or to what a walked column copies changes the advertised cost
    with it — this is the ``pl.CostEstimate`` a Mosaic compile reports
    through ``cost_analysis()``."""
    G = Hq // Hkv
    P = _block_pages(page, Hkv, D, Dv, De, itemsize)
    nb = -(-n_cols // P)

    def slot_index(b, tab, qp, nl):
        return (b, 0, 0, 0)

    def pos_index(b, tab, qp, nl):
        return (b, 0, 0)

    pool = pl.BlockSpec(memory_space=pltpu.HBM)
    walked = B * n_cols                     # worst case: every column
    plan = [  # (BlockSpec, bytes of one fetch, fetches)
        (pl.BlockSpec((1, Hkv, G, D), slot_index), Hkv * G * D * itemsize,
         B),
        (pl.BlockSpec((1, nb, P * page), pos_index), nb * P * page * 4, B),
        (pool, page * Hkv * D * itemsize, walked),
        (pool, page * Hkv * Dv * itemsize, walked),
    ]
    if De:
        plan += [(pl.BlockSpec((1, Hkv, G, De), slot_index),
                  Hkv * G * De * itemsize, B),
                 (pool, page * Hkv * De * itemsize, walked)]
    out_spec = pl.BlockSpec((1, Hkv, G, Dv), slot_index)
    byt = B * n_cols * 4 + 2 * B * 4        # table, q_pos, live columns
    for _, blk, fetches in plan + [(out_spec, Hkv * G * Dv * itemsize, B)]:
        byt += blk * fetches
    return [s for s, _, _ in plan], out_spec, P, byt


def _lane_pad(dim: int, interpret: bool) -> int:
    """Zero columns that make a head dim a lane multiple when compiling:
    Mosaic copies a page only in whole 128-lane tiles, so a head dim
    that is not one pays the pad (a copy of the pool); interpret mode
    never pads."""
    if interpret:
        return 0
    return -dim % 128


def paged_attention_cost(q, k, v, table, q_extra=None, *,
                         interpret: bool) -> pl.CostEstimate:
    """Analytic cost of one paged-decode call, derived from the SAME
    plan the kernel is built from (``_spec_plan``): the walk's worst
    case, every table column's K/V (+rope) page of every kv head copied
    once, q/positions/out once per slot, scalar table/q_pos/live counts
    in SMEM.  No gathered copy, so no other HBM term exists.  Pass the
    same ``interpret`` flag as the call being costed: compiled (Mosaic)
    mode lane-pads head dims to 128 (``_lane_pad``), so its copies can
    be wider than interpret mode's."""
    B, _, Hq, D = q.shape
    N, page, Hkv, Dv = v.shape
    n_cols = table.shape[1]
    De = 0 if q_extra is None else q_extra.shape[-1]
    D += _lane_pad(D, interpret)      # mirror the fwd lane padding
    Dv += _lane_pad(Dv, interpret)
    De += _lane_pad(De, interpret)
    _, _, _, byt = _spec_plan(B, Hq, Hkv, page, n_cols, D, Dv, De,
                              q.dtype.itemsize)
    T = n_cols * page
    flops = 2 * B * Hq * T * (D + Dv + De)
    return pl.CostEstimate(flops=flops, transcendentals=B * Hq * T,
                           bytes_accessed=byt)


def _head_rows(buf, slot, hkv: int, rows: int):
    """Yield each kv head's ``(rows, D)`` block of buffer ``slot``, in
    token order and the pool's dtype.  Buffer row ``t*hkv + h`` is head
    ``h`` of token ``t`` (the pool's own layout), so a head is every
    ``hkv``-th row.  A bf16 pair of rows ``(2i, 2i+1)`` shares one
    32-bit word (row ``2i`` in the low half), and Mosaic loads strided
    rows of 32-bit words only: heads ``2a`` and ``2a+1`` come from one
    strided word load, each half moved into an f32's high bits (exact)."""
    if hkv == 1:
        yield buf[slot]
    elif buf.dtype.itemsize == 4:
        for h in range(hkv):
            yield buf[slot, pl.ds(h, rows, stride=hkv), :]
    else:
        words = buf.bitcast(jnp.uint32)
        for a in range(hkv // 2):
            w = words[slot, pl.ds(a, rows, stride=hkv // 2), :]
            for bits in (w << 16, w & jnp.uint32(0xFFFF0000)):
                yield pltpu.bitcast(bits, jnp.float32).astype(buf.dtype)


def _paged_decode_kernel(tab_ref, qpos_ref, live_ref, q_ref, pos_ref,
                         k_hbm, v_hbm, *rest, hkv: int, page: int,
                         pages: int, scale: float, window: int,
                         softcap: float, n_cols: int, has_extra: bool):
    """One slot: walk its live columns in blocks of ``pages`` pages.

    Blocks: q (1, Hkv, G, D); pos (1, n_blocks, pages*page);
    [qe (1, Hkv, G, De)]; out (1, Hkv, G, Dv).  HBM pools k/v[/ke]
    (N, page*Hkv, D*).  Scratch: a (2, pages*page*Hkv, D*) buffer per
    pool, DMA semaphores (n_pools, 2), and per-head m/l (Hkv, G, 1),
    acc (Hkv, G, Dv) in f32.
    """
    if has_extra:
        qe_ref, ke_hbm, o_ref, *scratch = rest
        hbm = (k_hbm, v_hbm, ke_hbm)
    else:
        o_ref, *scratch = rest
        hbm = (k_hbm, v_hbm)
    bufs = scratch[:len(hbm)]
    sems, m_scr, l_scr, acc_scr = scratch[len(hbm):]
    b = pl.program_id(0)
    n_live = live_ref[b]
    q_pos = qpos_ref[b]
    n_blk = (n_live + pages - 1) // pages
    rows = pages * page                                  # tokens per block
    span = page * hkv                                    # buffer rows/page

    @pl.when(b == 0)
    def _zero_buffers():
        # rows a block does not copy keep earlier data; zero them once
        # so that data is finite (p == 0 there, and 0 * NaN is NaN)
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def copies(i, slot, fn):
        """Start (or wait for) block ``i``'s page copies into buffer
        ``slot``: one per pool and live, allocated column."""
        def page_copies(j, carry):
            c = i * pages + j
            blk = tab_ref[b, jnp.minimum(c, n_cols - 1)]

            @pl.when((c < n_live) & (blk >= 0))
            def _copy():
                for p, (src, buf) in enumerate(zip(hbm, bufs)):
                    fn(pltpu.make_async_copy(
                        src.at[blk], buf.at[slot, pl.ds(j * span, span)],
                        sems.at[p, slot]))
            return carry

        jax.lax.fori_loop(0, pages, page_copies, 0)

    @pl.when(n_blk > 0)
    def _first():
        copies(0, 0, lambda cp: cp.start())

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blk)
        def _next():
            copies(i + 1, 1 - slot, lambda cp: cp.start())

        copies(i, slot, lambda cp: cp.wait())
        pj = pos_ref[0, pl.ds(i, 1), :]                  # (1, rows)
        ok = (pj >= 0) & (pj <= q_pos)                   # causal + validity
        if window > 0:
            ok &= pj > q_pos - window
        heads = zip(*(_head_rows(buf, slot, hkv, rows) for buf in bufs))
        for h, (kh, vh, *eh) in enumerate(heads):
            s = jax.lax.dot_general(
                q_ref[0, h].astype(kh.dtype), kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # (G, rows)
            if has_extra:
                s = s + jax.lax.dot_general(
                    qe_ref[0, h].astype(eh[0].dtype), eh[0],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            # fully-masked blocks: exp(NEG_INF - NEG_INF) = 1 — zero it
            # like the scan path so masked entries carry no mass
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + p.sum(axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(
                p.astype(vh.dtype), vh, preferred_element_type=jnp.float32)
            m_scr[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)
    out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


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
    int32 block table (entries < 0 = unallocated) whose column c holds
    positions [c*page, (c+1)*page) (or their ring slots); q_pos: (B, 1)
    int32 (< 0 = idle slot, whose output is exactly 0 like the scan
    path).  q_extra: (B, 1, Hq, De) / k_extra: (N, page, Hkv, De) add a
    second score contraction before the softmax (MLA rope term).

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
    # the advertised cost comes from the same plan the blocks and copies
    # are built from below (one source of truth; see paged_attention_cost)
    cost = paged_attention_cost(q, k, v, table, q_extra,
                                interpret=interpret)

    # views: each pool as (N, page*Hkv, D) — free, the pool's own tiled
    # layout (a page is one contiguous row block, row t*Hkv + h); q as
    # (B, Hkv, G, D).  Lane padding (exact: zero columns add nothing to
    # either dot) only where Mosaic cannot copy a page (``_lane_pad``)
    if Hkv > 1 and k.dtype.itemsize != 4 and (
            k.dtype != jnp.bfloat16 or Hkv % 2):
        raise ValueError(
            f"paged_attention: {k.dtype} pools with {Hkv} kv heads — "
            f"several kv heads need f32 pools or bf16 with an even count")
    d_pad = _lane_pad(D, interpret)
    dv_pad = _lane_pad(Dv, interpret)
    qh = q.reshape(B, Hkv, G, D)
    if d_pad:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
    if dv_pad:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dv_pad)))
    Dp, Dvp = D + d_pad, Dv + dv_pad

    def rows(pool):
        return pool.reshape(N, page * Hkv, pool.shape[-1])

    pools = [rows(k), rows(v)]
    qe = None
    Dep = 0
    if has_extra:
        De = q_extra.shape[-1]
        de_pad = _lane_pad(De, interpret)
        qe = q_extra.reshape(B, Hkv, G, De)
        ke = k_extra
        if de_pad:
            qe = jnp.pad(qe, ((0, 0), (0, 0), (0, 0), (0, de_pad)))
            ke = jnp.pad(ke, ((0, 0), (0, 0), (0, 0), (0, de_pad)))
        Dep = De + de_pad
        pools.append(rows(ke))

    in_specs, out_spec, P, _ = _spec_plan(B, Hq, Hkv, page, n_cols, Dp,
                                          Dvp, Dep, q.dtype.itemsize)

    # each walked column's positions, -1 where the column is unallocated
    # or past the slot's live bound: one (1, P*page) mask row per block
    q_pos = q_pos.reshape(B)
    n_live = live_columns(q_pos, page, n_cols)
    nb = -(-n_cols // P)
    tab = jnp.pad(table, ((0, 0), (0, nb * P - n_cols)),
                  constant_values=-1)
    keep = (tab >= 0) & (jnp.arange(nb * P)[None] < n_live[:, None])
    pos_walk = jnp.where(keep[..., None], pos[jnp.maximum(tab, 0)], -1)
    pos_walk = pos_walk.reshape(B, nb, P * page)

    kernel = functools.partial(
        _paged_decode_kernel, hkv=Hkv, page=page, pages=P,
        scale=float(scale), window=window, softcap=softcap, n_cols=n_cols,
        has_extra=has_extra)

    R = P * page
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((2, R * Hkv, p.shape[-1]), p.dtype)
                        for p in pools] + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, Dvp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dvp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=cost,
        interpret=interpret,
    )(table, q_pos, n_live, qh, pos_walk, *pools[:2],
      *([qe, pools[2]] if has_extra else []))
    return out.reshape(B, 1, Hq, Dvp)[..., :Dv]
