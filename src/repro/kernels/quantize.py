"""Pallas TPU int8 block quantization — the §2.3 communication-compression
hot path (quantize gradients/activations before crossing slow links).

Per-row absmax scaling over a (rows_tile, block) VMEM tile; encode emits
int8 codes + f32 scales, decode reverses.  Elementwise + row-reduce only,
so tiles just need VREG-friendly lane widths (block multiple of 128).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

DEFAULT_BLOCK = 256
DEFAULT_ROWS = 64


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                  # (rows, block)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(
        x_ref.dtype)


def int8_quantize(x: jax.Array, *, block: int = DEFAULT_BLOCK,
                  rows_tile: int = DEFAULT_ROWS, interpret: bool):
    """x: any shape -> (codes int8 (n_rows, block), scales f32 (n_rows, 1)).
    Rows are contiguous ``block``-element groups of the flattened input."""
    flat = x.reshape(-1)
    n = flat.size
    pad = -n % block
    flat = jnp.pad(flat, (0, pad))
    rows = flat.size // block
    xb = flat.reshape(rows, block)
    row_pad = -rows % rows_tile
    xb = jnp.pad(xb, ((0, row_pad), (0, 0)))
    n_tiles = xb.shape[0] // rows_tile

    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((rows_tile, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows_tile, block), lambda i: (i, 0)),
                   pl.BlockSpec((rows_tile, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(xb.shape, jnp.int8),
                   jax.ShapeDtypeStruct((xb.shape[0], 1), jnp.float32)],
        interpret=interpret,
    )(xb)
    return q[:rows], s[:rows]


def int8_dequantize(q: jax.Array, scales: jax.Array, shape, dtype=jnp.float32,
                    *, rows_tile: int = DEFAULT_ROWS, interpret: bool):
    rows, block = q.shape
    row_pad = -rows % rows_tile
    qb = jnp.pad(q, ((0, row_pad), (0, 0)))
    sb = jnp.pad(scales, ((0, row_pad), (0, 0)))
    n_tiles = qb.shape[0] // rows_tile

    x = pl.pallas_call(
        _dequant_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((rows_tile, block), lambda i: (i, 0)),
                  pl.BlockSpec((rows_tile, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows_tile, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(qb.shape, dtype),
        interpret=interpret,
    )(qb, sb)
    n = math.prod(shape)
    return x[:rows].reshape(-1)[:n].reshape(shape)
