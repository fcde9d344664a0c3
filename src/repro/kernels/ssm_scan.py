"""Pallas TPU kernels for the linear-recurrence hot spots:

* ``mamba_scan``: Mamba selective scan — the (B, S, d_inner, d_state)
  hidden state is never materialized in HBM; each grid step keeps a
  (d_inner_block, d_state) state tile in VMEM scratch and walks a chunk
  of timesteps sequentially.
* ``rwkv_scan``: RWKV6 wkv recurrence with data-dependent decay — the
  per-head (head_dim, head_dim) state lives in VMEM scratch.

Grid layout (both): sequence chunks innermost + "arbitrary" so scratch
carries across chunks; batch/feature axes parallel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

DEFAULT_CHUNK = 64
DEFAULT_DI_BLOCK = 512


# ===========================================================================
# Mamba selective scan
# ===========================================================================

def _mamba_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_scr, *,
                  chunk: int):
    """Blocks: x,dt,y (1, chunk, bdi); b,c (1, chunk, ds); a (bdi, ds);
    scratch h (bdi, ds) f32. Grid (B, di_blocks, chunks)."""
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...]                                       # (bdi, ds) f32

    def step(t, h):
        x_t = x_ref[0, t, :].astype(jnp.float32)         # (bdi,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)       # (bdi,)
        b_t = b_ref[0, t, :].astype(jnp.float32)         # (ds,)
        c_t = c_ref[0, t, :].astype(jnp.float32)         # (ds,)
        dA = jnp.exp(dt_t[:, None] * a)                  # (bdi, ds)
        h = dA * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1)          # (bdi,)
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])


def mamba_scan(x: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
               a: jax.Array, *, chunk: int = DEFAULT_CHUNK,
               di_block: int = DEFAULT_DI_BLOCK,
               interpret: bool) -> jax.Array:
    """x, dt: (B, S, di); b, c: (B, S, ds); a: (di, ds) [negative].
    Returns y: (B, S, di) with y_t = C_t · h_t,
    h_t = exp(dt_t·A)·h_{t-1} + (dt_t·x_t)·B_t."""
    B, S, di = x.shape
    ds = b.shape[-1]
    chunk = min(chunk, S)
    s_pad = -S % chunk
    bdi = min(di_block, di)
    di_pad = -di % bdi

    pad3 = lambda t: jnp.pad(t, ((0, 0), (0, s_pad), (0, di_pad)))
    xp, dtp = pad3(x), pad3(dt)
    bp = jnp.pad(b, ((0, 0), (0, s_pad), (0, 0)))
    cp = jnp.pad(c, ((0, 0), (0, s_pad), (0, 0)))
    ap = jnp.pad(a.astype(jnp.float32), ((0, di_pad), (0, 0)))
    Sp, dip = S + s_pad, di + di_pad
    n_chunks = Sp // chunk
    n_di = dip // bdi

    y = pl.pallas_call(
        functools.partial(_mamba_kernel, chunk=chunk),
        grid=(B, n_di, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, bdi), lambda ib, idi, ic: (ib, ic, idi)),
            pl.BlockSpec((1, chunk, bdi), lambda ib, idi, ic: (ib, ic, idi)),
            pl.BlockSpec((1, chunk, ds), lambda ib, idi, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, ds), lambda ib, idi, ic: (ib, ic, 0)),
            pl.BlockSpec((bdi, ds), lambda ib, idi, ic: (idi, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bdi), lambda ib, idi, ic: (ib, ic, idi)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, dip), x.dtype),
        scratch_shapes=[pltpu.VMEM((bdi, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, dtp, bp, cp, ap)
    return y[:, :S, :di]


# ===========================================================================
# RWKV6 wkv recurrence
# ===========================================================================

def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *,
                 chunk: int):
    """Blocks: r,k,v,w,o (1, chunk, hd); u (1, hd); scratch S (hd, hd) f32.
    Grid (B*H, chunks)."""
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0].astype(jnp.float32)                     # (hd,)

    def step(t, S):
        r_t = r_ref[0, t, :].astype(jnp.float32)
        k_t = k_ref[0, t, :].astype(jnp.float32)
        v_t = v_ref[0, t, :].astype(jnp.float32)
        w_t = w_ref[0, t, :].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]                 # (hd, hd)
        o_t = jnp.sum(r_t[:, None] * (S + u[:, None] * kv), axis=0)
        o_ref[0, t, :] = o_t.astype(o_ref.dtype)
        return w_t[:, None] * S + kv

    s_scr[...] = jax.lax.fori_loop(0, chunk, step, s_scr[...])


def rwkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, *, chunk: int = DEFAULT_CHUNK,
              interpret: bool) -> jax.Array:
    """r,k,v,w: (B, S, H, hd); u: (H, hd).  Returns o: (B, S, H, hd) with
    o_t = r_t·(S_{t-1} + diag(u)·k_t v_tᵀ), S_t = diag(w_t)·S_{t-1} + k_t v_tᵀ."""
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    s_pad = -S % chunk
    tohb = lambda t: jnp.moveaxis(jnp.pad(t, ((0, 0), (0, s_pad), (0, 0), (0, 0))),
                                  2, 1).reshape(B * H, S + s_pad, hd)
    rp, kp, vp, wp = tohb(r), tohb(k), tohb(v), tohb(w)
    Sp = S + s_pad
    n_chunks = Sp // chunk

    o = pl.pallas_call(
        functools.partial(_rwkv_kernel, chunk=chunk),
        grid=(B * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, hd), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, hd), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, hd), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, hd), lambda ib, ic: (ib, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda ib, ic: (ib, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rp, kp, vp, wp, jnp.tile(u, (B, 1)).reshape(B * H, hd))
    o = o[:, :S].reshape(B, H, S, hd)
    return jnp.moveaxis(o, 1, 2)
