"""Pallas TPU flash attention (forward): online-softmax over KV blocks.

TPU-native tiling: grid (batch*q_heads, q_blocks, kv_blocks) with the KV
axis innermost ("arbitrary" = sequential), so the (m, l, acc) running
statistics live in VMEM scratch across KV steps.  Block shapes are
MXU-aligned (q/k blocks of 128, head dim padded to a multiple of 128 by
the wrapper).  GQA is handled by the kv index_map (no KV replication in
HBM).  Supports causal and sliding-window masking.

This is the TARGET kernel (pl.pallas_call + BlockSpec); correctness is
validated in interpret mode against ``ref.attention_ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      scale: float, causal: bool, window: int,
                      bq: int, bk: int, n_kv_blocks: int, t_real: int):
    """One (head, q-block, kv-block) grid step."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (bq, D)
    k = k_ref[0].astype(jnp.float32)                    # (bk, D)
    v = v_ref[0].astype(jnp.float32)                    # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < t_real                               # kv padding
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        scale: float | None = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D).  Returns (B, Hq, S, D).

    Pads S/T/D to block multiples; D padding is free for the softmax
    (zero dot contributions) and sliced off on output.
    """
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5

    bq = min(block_q, max(8, 1 << (S - 1).bit_length() if S < block_q else block_q))
    bk = min(block_k, max(8, 1 << (T - 1).bit_length() if T < block_k else block_k))
    d_pad = -D % 128 if D % 128 else 0
    s_pad = -S % bq
    t_pad = -T % bk

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad), (0, d_pad)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, t_pad), (0, d_pad)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, t_pad), (0, d_pad)))
    Dp = D + d_pad
    Sp, Tp = S + s_pad, T + t_pad
    qp = qp.reshape(B * Hq, Sp, Dp)
    kp = kp.reshape(B * Hkv, Tp, Dp)
    vp = vp.reshape(B * Hkv, Tp, Dp)

    n_q_blocks = Sp // bq
    n_kv_blocks = Tp // bk

    def kv_index(bh, iq, ik):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // G, ik, 0)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, n_kv_blocks=n_kv_blocks, t_real=T)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, n_q_blocks, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, Dp), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, Dp), kv_index),
            pl.BlockSpec((1, bk, Dp), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, Dp), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sp, Dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out.reshape(B, Hq, Sp, Dp)[:, :, :S, :D]
