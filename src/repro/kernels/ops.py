"""jit'd public wrappers for the Pallas kernels.

The backend decides how a kernel runs, and nothing else does: on a TPU
every wrapper compiles through Mosaic, on the CPU backend it runs in
interpret mode (the kernel body as traced JAX ops), and any other
backend is an error.  Model code calls these wrappers, never
``pallas_call`` directly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import quantize as _q
from repro.kernels import ssm_scan as _s


def _interpret() -> bool:
    """Interpret mode on the CPU backend, Mosaic on a TPU.  Read while the
    calling wrapper traces, so importing this module touches no device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"repro.kernels: no Pallas path for backend "
                       f"{backend!r} (TPU compiles, CPU interprets)")


@partial(jax.jit, static_argnames=("causal", "window", "scale",
                                   "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None,
                    block_q: int = _fa.DEFAULT_BLOCK_Q,
                    block_k: int = _fa.DEFAULT_BLOCK_K):
    """q: (B,Hq,S,D); k,v: (B,Hkv,T,D) -> (B,Hq,S,D)."""
    return _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   scale=scale, block_q=block_q,
                                   block_k=block_k, interpret=_interpret())


@partial(jax.jit, static_argnames=("scale", "window", "softcap"))
def paged_attention(q, k, v, pos, table, q_pos, *,
                    scale: float | None = None, window: int = 0,
                    softcap: float = 0.0, q_extra=None, k_extra=None):
    """Paged single-token decode attention over a block-table pool.

    q: (B,1,Hq,D); k/v: (N,page,Hkv,D*) pools; pos: (N,page); table:
    (B,n_cols); q_pos: (B,1) -> (B,1,Hq,Dv).  The block table is
    scalar-prefetched and drives the page DMA — no gathered K/V copy
    lands in HBM (see ``repro.kernels.paged_attention``)."""
    return _pa.paged_attention_fwd(q, k, v, pos, table, q_pos, scale=scale,
                                   window=window, softcap=softcap,
                                   q_extra=q_extra, k_extra=k_extra,
                                   interpret=_interpret())


@partial(jax.jit, static_argnames=("block",))
def int8_quantize(x, *, block: int = _q.DEFAULT_BLOCK):
    return _q.int8_quantize(x, block=block, interpret=_interpret())


@partial(jax.jit, static_argnames=("shape", "dtype"))
def int8_dequantize(q, scales, shape, dtype=jnp.float32):
    return _q.int8_dequantize(q, scales, shape, dtype, interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk", "di_block"))
def mamba_scan(x, dt, b, c, a, *, chunk: int = _s.DEFAULT_CHUNK,
               di_block: int = _s.DEFAULT_DI_BLOCK):
    """Selective scan: x,dt (B,S,di); b,c (B,S,ds); a (di,ds)."""
    return _s.mamba_scan(x, dt, b, c, a, chunk=chunk, di_block=di_block,
                         interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def rwkv_scan(r, k, v, w, u, *, chunk: int = _s.DEFAULT_CHUNK):
    """RWKV6 wkv: r,k,v,w (B,S,H,hd); u (H,hd)."""
    return _s.rwkv_scan(r, k, v, w, u, chunk=chunk, interpret=_interpret())
